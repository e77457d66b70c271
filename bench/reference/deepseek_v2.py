"""Plain reference of DeepSeek-V2 (HF ``DeepseekV2ForCausalLM``) as one
chip of an expert-parallel deployment computes it: the full forward pass
over whole sequences in float32 at the highest matmul precision, with no
cache, no absorption, no kernel and no batching trick, one layer at a time
and in blocks of rows so that it fits on the chip beside the program.

Per layer: RMSNorm; multi-head latent attention (``DeepseekV2Attention``):
``q = q_b(RMSNorm(q_a(x)))`` split into a 128-value part and a 64-value
rotary part per head; ``kv_a(x)`` split into a 512-value latent, RMSNormed,
and a 64-value rotary key shared by the heads; ``kv_b`` expands the latent
into each head's key part and value; the rotary parts are de-interleaved
(even values, then odd) and rotated by halves at YaRN's frequencies
(``DeepseekV2YarnRotaryEmbedding``, cos and sin times its ``_mscale``);
causal softmax over ``q.k * q_head_dim**-0.5 * mscale_all_dim**2``;
``o_proj``; residual.  RMSNorm, then the layer's ffn: the dense SwiGLU MLP
for the first ``first_k_dense_replace`` layers, else the expert layer
(``MoEGate``, ``DeepseekV2MoE``): softmax scores over every routed expert
of the published count, the ``topk_group`` groups with the highest best
score kept, the top ``num_experts_per_tok`` of those, weights times
``routed_scaling_factor`` (``norm_topk_prob`` false); each held expert's
SwiGLU output times its weight for the tokens routed to it; plus the
shared experts (one SwiGLU MLP of ``n_shared_experts`` times the expert
width) on every token.  A final RMSNorm and the LM head give the logits.

Departures from HF, each the deployment's cut (the configuration's file):
only the experts held here (``held_experts``) contribute, the others' terms
are left out exactly as the chip leaves them out; the vocabulary is the
configuration's slice; the position ids are 0..S-1 of each row with no
padding mask (padding only ever follows the compared positions).

Weights come in as plain arrays, one dict per layer: ``ln1``/``ln2 (d,)``,
``wq_a (d, q_lora)``, ``q_norm (q_lora,)``, ``wq_b (q_lora, H, nope+rope)``,
``wkv_a (d, kv_lora+rope)``, ``kv_norm (kv_lora,)``, ``wkv_b (kv_lora, H,
nope+v)``, ``wo (H, v, d)``; the dense layer's ``w_gate``/``w_up (d, F)``,
``w_down (F, d)``; an expert layer's ``router (d, E)``, the held experts'
``w_gate``/``w_up (n, d, F_e)`` and ``w_down (n, F_e, d)``, and
``shared_gate``/``shared_up (d, F_s)``, ``shared_down (F_s, d)``; and
``embed (V, d)``, ``final_norm (d,)``, ``lm_head (d, V)``.

``quant="fp8"`` is the control: every matmul operand rounded to float8
(e4m3) after scaling, per tensor for weights and per row for activations,
the precision step below the bfloat16 the configuration serves in.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_lm import _mm, _q8, _rms

# Sequences per block of the reference's attention and expert passes.
ROW_BLOCK = 16


def _yarn_mscale(scale, mscale):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_tables(cfg: dict, length: int):
    """cos and sin (length, rope/2) of the configuration's YaRN rotary
    embedding, times its ``_mscale``."""
    dim, base = cfg["qk_rope_head_dim"], cfg["rope_theta"]
    rs = cfg["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (rs["factor"] * base ** (np.arange(0, dim, 2) / dim))

    def corr(rot):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(corr(rs["beta_fast"])), 0)
    high = min(math.ceil(corr(rs["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = inter * (1 - mask) + extra * mask
    scale = (_yarn_mscale(rs["factor"], rs["mscale"])
             / _yarn_mscale(rs["factor"], rs["mscale_all_dim"]))
    ang = np.outer(np.arange(length), inv)
    return (np.cos(ang) * scale).astype(np.float32), \
        (np.sin(ang) * scale).astype(np.float32)


def softmax_scale(cfg: dict) -> float:
    s = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    rs = cfg["rope_scaling"]
    if rs.get("mscale_all_dim"):
        m = _yarn_mscale(rs["factor"], rs["mscale_all_dim"])
        s *= m * m
    return s


def _rotate(x, cos, sin):
    """x (B, S, ..., rope): HF DeepSeek's de-interleave, then rotate_half
    with cos/sin repeated over the two halves."""
    shape = x.shape
    x = x.reshape(*shape[:-1], shape[-1] // 2, 2)
    x = jnp.concatenate([x[..., 0], x[..., 1]], -1)
    extra = (1,) * (len(shape) - 3)
    c = jnp.concatenate([cos, cos], -1).reshape(1, cos.shape[0], *extra, -1)
    s = jnp.concatenate([sin, sin], -1).reshape(1, sin.shape[0], *extra, -1)
    half = shape[-1] // 2
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * c + rot * s


def _swiglu(x, wg, wu, wd, quant):
    a = jax.nn.silu(_mm("bsd,df->bsf", x, wg, quant))
    return _mm("bsf,fd->bsd", a * _mm("bsd,df->bsf", x, wu, quant), wd,
               quant)


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def attention(x, w, cos, sin, *, dims, quant=None):
    """The attention half of a layer over rows x (B, S, d), residual
    included.  ``dims``: (eps, nope, rope, kv_lora, scale)."""
    eps, nope, rope, kv_lora, scale = dims
    with jax.default_matmul_precision("highest"):
        s = x.shape[1]
        h = _rms(x, w["ln1"], eps)
        q = _mm("bsd,dr->bsr", h, w["wq_a"], quant)
        q = _mm("bsr,rhk->bshk", _rms(q, w["q_norm"], eps), w["wq_b"], quant)
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        kv = _mm("bsd,dr->bsr", h, w["wkv_a"], quant)
        c_kv = _rms(kv[..., :kv_lora], w["kv_norm"], eps)
        k_pe = kv[..., kv_lora:]
        kv = _mm("bsr,rhk->bshk", c_kv, w["wkv_b"], quant)
        k_nope, v = kv[..., :nope], kv[..., nope:]
        q_pe = _rotate(q_pe, cos, sin)
        k_pe = _rotate(k_pe[:, :, None, :], cos, sin)
        q = jnp.concatenate([q_nope, q_pe], -1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, k_nope.shape[:-1] + (rope,))], -1)
        if quant == "fp8":
            q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
        scores = jnp.einsum("bqhk,bthk->bhqt", q, k) * scale
        scores = jnp.where(np.tril(np.ones((s, s), bool)), scores, -jnp.inf)
        p = jax.nn.softmax(scores, axis=-1)
        if quant == "fp8":
            p = _q8(p, -1)
        o = jnp.einsum("bhqt,bthv->bqhv", p, v)
        return x + _mm("bshv,hvd->bsd", o, w["wo"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def dense_ffn(x, w, *, eps, quant=None):
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["ln2"], eps)
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)


def route(logits, k, n_group, topk_group):
    """HF ``MoEGate`` (group_limited_greedy) on logits (..., E): softmax
    scores, the best ``topk_group`` groups kept, top ``k`` of those.
    Returns (scores (..., E), mask (..., E) of the experts chosen)."""
    scores = jax.nn.softmax(logits, axis=-1)
    shape = scores.shape
    groups = scores.reshape(*shape[:-1], n_group, shape[-1] // n_group)
    best = groups.max(-1)
    keep_g = best >= jax.lax.top_k(best, topk_group)[0][..., -1:]
    kept = jnp.where(keep_g[..., None], groups, 0.0).reshape(shape)
    kth = jax.lax.top_k(kept, k)[0][..., -1:]
    return scores, kept >= kth


def held_margin(logits, k, n_group, topk_group, held):
    """Per token, the least margin (logit units, inf for none) by which a
    routing decision keeps the held experts ``[first, stop)`` chosen as
    they are: inside the kept groups, a chosen held expert above the best
    expert left out, and the worst expert chosen above a held expert left
    out; and each kept group above each group left out by best logit,
    where swapping the two changes the held experts chosen.  A program
    that rounds differently may route a token whose margin is small
    either way."""
    shape = logits.shape
    per = shape[-1] // n_group
    ids = np.arange(shape[-1])
    is_held = jnp.asarray((ids >= held[0]) & (ids < held[1]))
    grouped = logits.reshape(*shape[:-1], n_group, per)
    best = grouped.max(-1)
    g_order = jnp.argsort(-best, -1)
    g_pair = jnp.take_along_axis(best, g_order, -1)

    def chosen(keep):
        kept = jnp.where(keep[..., None], grouped, -jnp.inf).reshape(shape)
        return kept, kept >= jax.lax.top_k(kept, k)[0][..., -1:]
    rank = jnp.argsort(g_order, -1)                 # each group's place
    keep = rank < topk_group
    kept, pick = chosen(keep)
    inf = jnp.inf
    g_margin = jnp.full(shape[:-1], inf)
    for i in range(topk_group):                     # a kept group out,
        for j in range(topk_group, n_group):        # a left one in
            alt = (keep & (rank != i)) | (rank == j)
            swap = ((pick != chosen(alt)[1]) & is_held).any(-1)
            g_margin = jnp.minimum(g_margin, jnp.where(
                swap, g_pair[..., i] - g_pair[..., j], inf))
    out = jnp.where(pick, -inf, kept)               # the kept, left out
    drop = (jnp.where(pick & is_held, kept, inf).min(-1) - out.max(-1))
    enter = (jnp.where(pick, kept, inf).min(-1)
             - jnp.where(is_held, out, -inf).max(-1))
    return jnp.minimum(g_margin, jnp.minimum(drop, enter))


@functools.partial(jax.jit, static_argnames=("dims", "quant"))
def moe_ffn(x, w, *, dims, quant=None):
    """The expert layer over rows x (B, S, d), residual included.
    Also returns, per token, its ``held_margin``.
    ``dims``: (eps, k, n_group, topk_group, scaling, held)."""
    eps, k, n_group, topk_group, scaling, held = dims
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["ln2"], eps)
        logits = _mm("bsd,de->bse", h, w["router"], quant)
        scores, chosen = route(logits, k, n_group, topk_group)
        weight = jnp.where(chosen, scores * scaling, 0.0)
        y = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"],
                    quant)
        for j in range(w["w_gate"].shape[0]):
            y = y + weight[..., held[0] + j, None] * _swiglu(
                h, w["w_gate"][j], w["w_up"][j], w["w_down"][j], quant)
        return x + y, held_margin(logits, k, n_group, topk_group, held)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def head(x, final_norm, lm_head, *, eps, quant=None):
    with jax.default_matmul_precision("highest"):
        return _mm("bsd,dv->bsv", _rms(x, final_norm, eps), lm_head, quant)


def forward(cfg: dict, weights, tokens: np.ndarray, quant=None):
    """Logits (B, S, V) of ``tokens`` (B, S), one layer at a time over
    blocks of ``ROW_BLOCK`` rows, and (B, S) the least ``held_margin`` of
    each position over the expert layers."""
    eps = cfg["rms_norm_eps"]
    s = tokens.shape[1]
    cos, sin = map(jnp.asarray, rope_tables(cfg, s))
    att_dims = (eps, cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                cfg["kv_lora_rank"], softmax_scale(cfg))
    moe_dims = (eps, cfg["num_experts_per_tok"], cfg["n_group"],
                cfg["topk_group"], float(cfg["routed_scaling_factor"]),
                tuple(cfg["held_experts"]))
    if cfg["norm_topk_prob"]:
        raise NotImplementedError("the reference follows norm_topk_prob "
                                  "false, as DeepSeek-V2 publishes it")
    blocks = [slice(i, i + ROW_BLOCK)
              for i in range(0, tokens.shape[0], ROW_BLOCK)]
    toks = jnp.asarray(tokens)
    xs = [jnp.take(weights("embed"), toks[b], axis=0).astype(jnp.float32)
          for b in blocks]
    margin = np.full(tokens.shape, np.inf, np.float32)
    for i in range(cfg["num_hidden_layers"]):
        w = weights(i)
        xs = [attention(x, w, cos, sin, dims=att_dims, quant=quant)
              for x in xs]
        if i < cfg["first_k_dense_replace"]:
            xs = [dense_ffn(x, w, eps=eps, quant=quant) for x in xs]
            continue
        out = [moe_ffn(x, w, dims=moe_dims, quant=quant) for x in xs]
        xs = [x for x, _ in out]
        margin = np.minimum(margin, np.concatenate([np.asarray(m)
                                                    for _, m in out]))
    fn, lm = weights("final_norm"), weights("lm_head")
    return jnp.concatenate([head(x, fn, lm, eps=eps, quant=quant)
                            for x in xs]), margin


def served_gaps(cfg: dict, weights, tokens: np.ndarray, prompt_len: int,
                control: bool = False, pad_to: int | None = None):
    """Gaps below the reference's best logit of every served token of
    ``tokens`` (B, L) whose first ``prompt_len`` columns are the prompt:
    (B, L - prompt_len) for the program, and for the control (None unless
    ``control``) the same read for the token fp8 would put first; and the
    (B, L - prompt_len) mask of the tokens served at positions routed
    within the configuration's ``check.route_tie_logit`` of a tie that
    concerns the held experts (``forward``), whose gaps are read as 0:
    either routing is right there, and the reference computes one of them.
    ``weights`` and ``pad_to`` as ``dense_lm.served_gaps`` takes them."""
    length = tokens.shape[1]
    if pad_to is not None and pad_to > length:
        tokens = np.pad(tokens, ((0, 0), (0, pad_to - length)))
    ref, margin = forward(cfg, weights, tokens)
    ref = np.asarray(ref)
    tied = margin < cfg["check"]["route_tie_logit"]
    best = ref.max(-1)
    targets = np.concatenate([tokens[:, 1:], tokens[:, -1:]], axis=1)
    sl = slice(prompt_len - 1, length - 1)
    tied = tied[:, sl]
    gap = (best - np.take_along_axis(ref, targets[..., None], -1)[..., 0])
    gap = np.where(tied, 0.0, gap[:, sl])
    ctrl = None
    if control:
        pick = np.asarray(forward(cfg, weights, tokens, quant="fp8")[0]
                          ).argmax(-1)
        ctrl = (best - np.take_along_axis(ref, pick[..., None], -1)[..., 0]
                )[:, sl]
        ctrl = np.where(tied, 0.0, ctrl)
    return gap, ctrl, tied
