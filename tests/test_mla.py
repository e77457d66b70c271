"""Multi-head latent attention (DeepSeek-V2) at a small size on the CPU:
the absorbed decode through the latent cache against the plain
full-sequence path, YaRN against a direct transcription of HF's formulas,
and ``Engine.generate`` of the smoke config against the plain reference,
compared by logits."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model
from repro.models.blocks import make_mixer
from repro.models.mla import rope_inv_freq, yarn_mscale

ARCH = "deepseek-v2-236b-a21b"
KEY = jax.random.PRNGKey(3)


def _hf_yarn(dim, base, factor, orig, beta_fast, beta_slow, mscale,
             mscale_all_dim):
    """HF ``DeepseekV2YarnRotaryEmbedding`` and ``yarn_*`` helpers,
    transcribed line by line (torch ops as numpy): (inv_freq, _mscale)."""
    def find_correction_dim(num_rotations):
        return (dim * math.log(orig / (num_rotations * 2 * math.pi))) / (
            2 * math.log(base))

    def get_mscale(scale=1, m=1):
        if scale <= 1:
            return 1.0
        return 0.1 * m * math.log(scale) + 1.0

    def linear_ramp_mask(lo, hi, n):
        if lo == hi:
            hi += 0.001
        return np.clip((np.arange(n, dtype=np.float32) - lo) / (hi - lo),
                       0, 1)

    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                 / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    inv_freq_mask = 1.0 - linear_ramp_mask(low, high, dim // 2)
    inv_freq = freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask
    _mscale = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    return inv_freq, _mscale


@pytest.mark.parametrize("arch_smoke", [False, True])
def test_yarn_matches_hf(arch_smoke):
    cfg = get_config(ARCH, smoke=arch_smoke)
    y = cfg.yarn
    want, want_scale = _hf_yarn(cfg.qk_rope_head_dim, cfg.rope_theta,
                                y.factor, y.original_max_position,
                                y.beta_fast, y.beta_slow, y.mscale,
                                y.mscale_all_dim)
    got = rope_inv_freq(cfg.qk_rope_head_dim, cfg.rope_theta, y)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    mla = make_mixer(cfg, cfg.pattern[0]).cfg
    assert mla.rope_scale == pytest.approx(want_scale)
    # softmax_scale = q_head_dim**-0.5 * mscale(factor, mscale_all_dim)**2
    m = 0.1 * y.mscale_all_dim * math.log(y.factor) + 1.0
    assert mla.softmax_scale == pytest.approx(
        (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m)
    assert yarn_mscale(1.0, 0.707) == 1.0


def test_published_yarn_blends_both_frequencies():
    cfg = get_config(ARCH)
    plain = rope_inv_freq(64, 10_000.0, None)
    got = rope_inv_freq(64, 10_000.0, cfg.yarn)
    # The fastest rotations are kept, the slowest interpolated 40x.
    assert got[0] == pytest.approx(plain[0])
    assert got[-1] == pytest.approx(plain[-1] / 40.0)


@pytest.mark.parametrize("prefix", [1, 7])
def test_absorbed_decode_matches_full_sequence(prefix):
    """Token by token through the latent cache (query and output absorb
    ``kv_b``) against keys and values expanded per head over the whole
    sequence, in float32: the same attention to rounding."""
    cfg = get_config(ARCH, smoke=True)
    mla = make_mixer(cfg, cfg.pattern[0])
    p = mla.init(KEY)
    b, s = 2, 24
    x = jax.random.normal(jax.random.PRNGKey(4), (b, s, cfg.d_model))
    full = mla.apply(p, x)
    cache = mla.init_cache(b, s, dtype=jnp.float32)
    assert set(cache) == {"c_kv", "k_pe"}
    assert cache["c_kv"].shape == (b, s, cfg.kv_lora_rank)
    assert cache["k_pe"].shape == (b, s, cfg.qk_rope_head_dim)
    outs = []
    for i in range(s):
        y, cache = mla.decode(p, x[:, i:i + 1], cache, jnp.int32(i))
        outs.append(y)
    dec = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(dec[:, prefix:]),
                               np.asarray(full[:, prefix:]),
                               rtol=1e-4, atol=1e-4)
    # The cache holds what the full path derives: the normed latent and
    # the roped shared key of every position.
    c_kv, k_pe = mla._latent(p, x, jnp.arange(s)[None].repeat(b, 0))
    np.testing.assert_allclose(np.asarray(cache["c_kv"]), np.asarray(c_kv),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(cache["k_pe"]), np.asarray(k_pe),
                               rtol=1e-5, atol=1e-5)


def _hf_config(cfg) -> dict:
    """The smoke config under HF DeepSeek-V2's key names, as the
    reference reads a configuration file."""
    y = cfg.yarn
    return {"hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "kv_lora_rank": cfg.kv_lora_rank, "rope_theta": cfg.rope_theta,
            "rope_scaling": {"factor": y.factor,
                             "original_max_position_embeddings":
                                 y.original_max_position,
                             "beta_fast": y.beta_fast,
                             "beta_slow": y.beta_slow, "mscale": y.mscale,
                             "mscale_all_dim": y.mscale_all_dim},
            "rms_norm_eps": 1e-6, "num_experts_per_tok": cfg.top_k,
            "n_group": cfg.n_group, "topk_group": cfg.topk_group,
            "routed_scaling_factor": cfg.routed_scaling,
            "norm_topk_prob": cfg.norm_topk,
            "first_k_dense_replace": cfg.first_k_dense,
            "held_experts": list(cfg.experts_held or (0, cfg.n_experts)),
            "vocab_size": cfg.vocab,
            "check": {"route_tie_logit": 0.05}}


@pytest.mark.parametrize("held", [None, (4, 12)])
def test_engine_generate_matches_the_reference(held, mesh1):
    """The served path (bfloat16 serving copy, latent cache, held-expert
    layers with their counters) against the float32 reference on the same
    weights, by logits along the served tokens.  Tolerance: bfloat16
    activations and weights move the logits by a few hundredths at this
    width; the reference's positions routed within 0.05 logits of a tie
    that concerns the held experts are left out, since a rounding may
    route them either way (the benchmark's rule)."""
    from bench.reference import deepseek_v2 as ref
    from bench.systems.mla_moe_serve import plain_weights
    from repro.serving import Engine
    cfg = dataclasses.replace(get_config(ARCH, smoke=True),
                              experts_held=held)
    model = make_model(cfg)
    params = model.init(KEY)
    eng = Engine(model, cfg, max_len=32)
    prompt = jax.random.randint(KEY, (4, 6), 0, cfg.vocab)
    out = np.asarray(eng.generate(params, prompt, n_new=10))
    hf = _hf_config(cfg)
    want, margin = ref.forward(hf, lambda k: plain_weights(params, hf, k),
                               out)
    tied = margin < hf["check"]["route_tie_logit"]
    served = model.serving_params(params)
    caches, counts = model.init_caches(4, 32), model.init_counts()
    got = []
    for i in range(out.shape[1]):
        lg, caches, counts = eng._step(
            served, jnp.asarray(out[:, i:i + 1]), caches, jnp.int32(i),
            None, counts)
        got.append(lg[..., :cfg.vocab])
    got = np.asarray(jnp.concatenate(got, axis=1))
    err = np.abs(got - np.asarray(want)).max(-1)
    assert tied.mean() < 0.5
    assert err[~tied].max() < 0.15, err
    # Each served token lies within the same tolerance of the reference's
    # best logit where it was picked (position t picks token t + 1).
    want = np.asarray(want)[:, 5:-1]
    gap = want.max(-1) - np.take_along_axis(want, out[:, 6:, None],
                                            -1)[..., 0]
    assert gap[~tied[:, 5:-1]].max() < 0.15
    # The counters: every (token, expert) pair of every expert layer.
    n = eng.step_counters()
    moe_layers = cfg.n_layers - cfg.first_k_dense
    steps = out.shape[1] - 1
    assert n["routed_held"] + n["routed_elsewhere"] == \
        4 * cfg.top_k * moe_layers * steps
    assert (n["routed_elsewhere"] == 0) == (held is None)
