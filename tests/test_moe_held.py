"""The held-expert layer (DeepSeek-V2's MoE as one expert-parallel rank
runs it) at a small size on the CPU: group-limited routing against a
numpy transcription of HF ``MoEGate``, no drop when every token routes to
one held expert, and the share test: over every split of the experts into
held ranges, the ranges' parts plus the shared experts counted once add up
to the uncut layer."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.moe import HeldMoE, HeldMoEConfig, route

CFG = HeldMoEConfig(d_model=32, d_ff=16, n_experts=16, top_k=3,
                    held=(0, 16), n_shared=2, n_group=4, topk_group=2,
                    routed_scaling=16.0, norm_topk=False)
KEY = jax.random.PRNGKey(5)


def _hf_gate(logits, c):
    """HF ``MoEGate.forward`` (softmax, group_limited_greedy), numpy."""
    scores = np.exp(logits - logits.max(-1, keepdims=True))
    scores /= scores.sum(-1, keepdims=True)
    n = scores.shape[0]
    group_scores = scores.reshape(n, c.n_group, -1).max(-1)
    group_idx = np.argsort(-group_scores, -1, kind="stable")[:, :c.topk_group]
    group_mask = np.zeros_like(group_scores)
    np.put_along_axis(group_mask, group_idx, 1, -1)
    score_mask = np.repeat(group_mask, c.n_experts // c.n_group, -1)
    tmp = np.where(score_mask > 0, scores, 0.0)
    idx = np.argsort(-tmp, -1, kind="stable")[:, :c.top_k]
    w = np.take_along_axis(tmp, idx, -1)
    if c.norm_topk:
        w = w / w.sum(-1, keepdims=True)
    else:
        w = w * c.routed_scaling
    return w, idx


def _as_sets(w, e):
    return [dict(zip(ei.tolist(), wi.tolist())) for wi, ei in zip(w, e)]


@pytest.mark.parametrize("norm_topk", [False, True])
def test_group_limited_routing_matches_hf(norm_topk):
    c = dataclasses.replace(CFG, norm_topk=norm_topk)
    logits = np.asarray(jax.random.normal(KEY, (64, c.n_experts)) * 2.0)
    w, e = route(jnp.asarray(logits), c)
    hw, he = _hf_gate(logits.astype(np.float64), c)
    got, want = _as_sets(np.asarray(w), np.asarray(e)), _as_sets(hw, he)
    for g, h in zip(got, want):
        assert g.keys() == h.keys()
        np.testing.assert_allclose([g[k] for k in h], list(h.values()),
                                   rtol=1e-5)
    # Every pick lies in one of the token's best topk_group groups.
    best = logits.reshape(64, c.n_group, -1).max(-1)
    top = np.argsort(-best, -1)[:, :c.topk_group]
    groups = np.asarray(e) // (c.n_experts // c.n_group)
    assert all(set(g) <= set(t) for g, t in zip(groups, top))


def _dense_routed(layer, p, x):
    """Each token's held experts, run one token at a time: no capacity."""
    c = layer.cfg
    logits = x @ np.asarray(p["router"], np.float32)
    w, e = _hf_gate(logits.astype(np.float64), c)
    y = np.zeros_like(x)
    for t in range(len(x)):
        for wt, ex in zip(w[t], e[t]):
            if not c.held[0] <= ex < c.held[1]:
                continue
            j = ex - c.held[0]
            g = x[t] @ np.asarray(p["w_gate"][j])
            u = x[t] @ np.asarray(p["w_up"][j])
            y[t] += wt * ((g / (1 + np.exp(-g)) * u) @ np.asarray(
                p["w_down"][j]))
    return y


def test_no_drop_when_every_token_routes_to_one_held_expert():
    """64 tokens, all routed to held expert 0 (the router's column 0 far
    ahead of the rest): each is computed, with no capacity cap."""
    c = dataclasses.replace(CFG, held=(0, 4))
    layer = HeldMoE(c)
    p = layer.init(KEY)
    p["router"] = p["router"].at[:, 0].set(5.0)
    x = np.abs(np.asarray(jax.random.normal(KEY, (64, c.d_model))))
    y, n_held, n_else = layer.routed(p, jnp.asarray(x))
    e = np.asarray(route(jnp.asarray(x) @ p["router"], c)[1])
    assert (e == 0).any(-1).all()
    assert int(n_held) == int(((e >= 0) & (e < 4)).sum()) >= 64
    assert int(n_held) + int(n_else) == 64 * c.top_k
    np.testing.assert_allclose(np.asarray(y), _dense_routed(layer, p, x),
                               rtol=2e-4, atol=2e-4)


SPLITS = [[(0, 16)], [(0, 8), (8, 16)], [(0, 4), (4, 8), (8, 12), (12, 16)],
          [(0, 1), (1, 7), (7, 16)], [(0, 15), (15, 16)]]


@pytest.mark.parametrize("split", SPLITS, ids=str)
def test_shares_add_up_to_the_uncut_layer(split):
    """Each range's held-expert part, from its slice of the uncut layer's
    expert weights and the same router, plus the shared experts counted
    once, equals the uncut layer; the ranges' counts add up to every
    (token, expert) pair."""
    whole = HeldMoE(CFG)
    p = whole.init(KEY)
    x = jax.random.normal(jax.random.PRNGKey(6), (2, 24, CFG.d_model))
    want, counts = whole.apply(p, x)
    assert int(counts["routed_held"]) == 2 * 24 * CFG.top_k
    assert int(counts["routed_elsewhere"]) == 0
    total = whole.shared(p, x)
    pairs = 0
    for lo, hi in split:
        share = HeldMoE(dataclasses.replace(CFG, held=(lo, hi)))
        ps = dict(p, **{k: p[k][lo:hi] for k in ("w_gate", "w_up",
                                                  "w_down")})
        y, n_held, n_else = share.routed(ps, x.reshape(-1, CFG.d_model))
        assert int(n_held) + int(n_else) == 2 * 24 * CFG.top_k
        total = total + y.reshape(x.shape)
        pairs += int(n_held)
    assert pairs == 2 * 24 * CFG.top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_uncut_layer_matches_hf_per_token():
    """The uncut layer (every expert held) against HF's per-token loop."""
    layer = HeldMoE(CFG)
    p = layer.init(KEY)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(7), (40, CFG.d_model)))
    y, _, _ = layer.routed(p, jnp.asarray(x))
    np.testing.assert_allclose(np.asarray(y), _dense_routed(layer, p, x),
                               rtol=2e-4, atol=2e-4)
