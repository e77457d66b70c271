"""Multi-head latent attention (MLA, DeepSeek-V2) with YaRN rotary scaling.

Queries come through a low-rank bottleneck (``wq_a``, RMSNorm, ``wq_b``);
keys and values through a shared latent: ``wkv_a`` gives ``kv_lora_rank``
latent values, normed, plus ``qk_rope_head_dim`` rotary values that every
head shares; ``wkv_b`` expands the latent into each head's key (no rotary
part) and value.  A head's key is its expanded key beside the shared
rotary key, scored against its query's two parts.

Two paths compute the same attention:

* ``apply`` (full sequence) expands the latent into per-head keys and
  values and attends as usual;
* ``decode`` keeps only the **latent cache**: per token the normed latent
  ``c_kv`` and the roped ``k_pe``, 512 + 64 values shared by all heads.
  ``wkv_b``'s key half is absorbed into the query (``q_nope`` is mapped
  into the latent space) and its value half into the output (the weighted
  sum of latents is expanded once per head), so no per-head key or value
  is ever built.

The rotary part follows HF ``modeling_deepseek``: each rotary vector is
de-interleaved (even dimensions, then odd) before the usual rotation of
halves, and the frequencies are YaRN's blend of interpolated and
extrapolated ones; the softmax scale carries YaRN's ``mscale_all_dim``
squared.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import Yarn

from .common import COMPUTE_DTYPE, AxesTree, Params, RMSNorm, dense_init

NEG_INF = -2.3819763e38


def yarn_mscale(scale: float, mscale: float) -> float:
    """HF ``yarn_get_mscale``."""
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(dim: int, theta: float, yarn: Yarn | None) -> np.ndarray:
    """Inverse frequencies of a rotary vector of ``dim`` values: plain
    RoPE, or YaRN's blend (HF ``DeepseekV2YarnRotaryEmbedding``)."""
    extra = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if yarn is None:
        return extra
    inter = extra / yarn.factor

    def corr_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                    # share of the extrapolated frequency
    return inter * (1 - keep) + extra * keep


def rope_deinterleaved(x, positions, inv_freq: np.ndarray,
                       scale: float = 1.0):
    """Rotate ``x`` (..., s, h, dim) at ``positions`` (..., s) as HF
    DeepSeek does: even dimensions then odd ones, then ``rotate_half``;
    ``scale`` multiplies cos and sin (YaRN's ``_mscale``)."""
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., 0::2], xf[..., 1::2]
    ang = positions[..., :, None, None].astype(jnp.float32) \
        * jnp.asarray(inv_freq, jnp.float32)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    d_model: int
    n_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float = 10000.0
    yarn: Yarn | None = None
    causal: bool = True
    eps: float = 1e-6

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def softmax_scale(self) -> float:
        s = self.qk_head_dim ** -0.5
        if self.yarn is not None and self.yarn.mscale_all_dim:
            m = yarn_mscale(self.yarn.factor, self.yarn.mscale_all_dim)
            s *= m * m
        return s

    @property
    def rope_scale(self) -> float:
        """YaRN's ``_mscale`` on cos and sin."""
        y = self.yarn
        if y is None:
            return 1.0
        return yarn_mscale(y.factor, y.mscale) / yarn_mscale(
            y.factor, y.mscale_all_dim)


@dataclasses.dataclass(frozen=True)
class MLA:
    cfg: MLAConfig

    # The norm scales are read in float32 and stay out.
    compute_leaves = ("wq_a", "wq_b", "wkv_a", "wkv_b", "wo")

    def init(self, key) -> Params:
        c = self.cfg
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        return {
            "wq_a": dense_init(k1, (c.d_model, c.q_lora_rank)),
            "q_norm": RMSNorm(c.q_lora_rank).init(k1),
            "wq_b": dense_init(k2, (c.q_lora_rank, c.n_heads,
                                    c.qk_head_dim)),
            "wkv_a": dense_init(k3, (c.d_model,
                                     c.kv_lora_rank + c.qk_rope_head_dim)),
            "kv_norm": RMSNorm(c.kv_lora_rank).init(k3),
            "wkv_b": dense_init(k4, (c.kv_lora_rank, c.n_heads,
                                     c.qk_nope_head_dim + c.v_head_dim)),
            "wo": dense_init(k5, (c.n_heads, c.v_head_dim, c.d_model),
                             in_axis=0),
        }

    def axes(self) -> AxesTree:
        return {"wq_a": ("embed", None), "q_norm": {"scale": (None,)},
                "wq_b": (None, "heads", "head_dim"),
                "wkv_a": ("embed", None), "kv_norm": {"scale": (None,)},
                "wkv_b": (None, "heads", "head_dim"),
                "wo": ("heads", "head_dim", "embed")}

    # -- projections --------------------------------------------------------
    def _query(self, p, x, positions):
        """(q_nope (B,S,H,nope), q_pe (B,S,H,rope) roped)."""
        c = self.cfg
        q = jnp.einsum("bsd,dr->bsr", x, p["wq_a"].astype(x.dtype))
        q = RMSNorm(c.q_lora_rank, c.eps).apply(p["q_norm"], q)
        q = jnp.einsum("bsr,rhk->bshk", q, p["wq_b"].astype(x.dtype))
        q_nope, q_pe = jnp.split(q, [c.qk_nope_head_dim], axis=-1)
        return q_nope, self._rope(q_pe, positions)

    def _latent(self, p, x, positions):
        """(c_kv (B,S,kv_lora) normed, k_pe (B,S,rope) roped)."""
        c = self.cfg
        kv = jnp.einsum("bsd,dr->bsr", x, p["wkv_a"].astype(x.dtype))
        c_kv, k_pe = jnp.split(kv, [c.kv_lora_rank], axis=-1)
        c_kv = RMSNorm(c.kv_lora_rank, c.eps).apply(p["kv_norm"], c_kv)
        return c_kv, self._rope(k_pe[:, :, None, :], positions)[:, :, 0]

    def _rope(self, x, positions):
        c = self.cfg
        return rope_deinterleaved(
            x, positions, rope_inv_freq(c.qk_rope_head_dim, c.rope_theta,
                                        c.yarn), c.rope_scale)

    # -- full sequence ------------------------------------------------------
    def apply(self, p: Params, x, *, positions=None, prefix_len=None):
        """Training / prefill attention over full sequences, with keys and
        values expanded per head."""
        c = self.cfg
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.arange(s)[None, :].repeat(b, 0)
        q_nope, q_pe = self._query(p, x, positions)
        c_kv, k_pe = self._latent(p, x, positions)
        kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"].astype(x.dtype))
        k_nope, v = jnp.split(kv, [c.qk_nope_head_dim], axis=-1)
        logits = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope,
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe,
                               preferred_element_type=jnp.float32))
        logits = logits * c.softmax_scale
        if c.causal:
            qp, kp = positions[0][:, None], positions[0][None, :]
            ok = kp <= qp
            if prefix_len is not None:
                ok |= (kp < prefix_len) & (qp < prefix_len)
            logits = jnp.where(ok, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        out = jnp.einsum("bhqk,bkhv->bqhv", probs, v)
        return jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(x.dtype))

    # -- decode through the latent cache -----------------------------------
    def decode(self, p: Params, x, cache: dict, pos) -> tuple[jax.Array, dict]:
        """Single-token decode.  x: (B,1,D); cache {'c_kv': (B,Smax,kv_lora),
        'k_pe': (B,Smax,rope)}; pos: scalar int32 position of the token."""
        c = self.cfg
        b = x.shape[0]
        positions = jnp.full((b, 1), pos, jnp.int32)
        q_nope, q_pe = self._query(p, x, positions)
        c_new, pe_new = self._latent(p, x, positions)
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, axis=1)
        k_pe = jax.lax.dynamic_update_slice_in_dim(
            cache["k_pe"], pe_new.astype(cache["k_pe"].dtype), pos, axis=1)
        w_kb, w_vb = jnp.split(p["wkv_b"].astype(x.dtype),
                               [c.qk_nope_head_dim], axis=-1)
        q_lat = jnp.einsum("bshn,rhn->bshr", q_nope, w_kb)
        logits = (jnp.einsum("bshr,btr->bhst", q_lat, c_kv.astype(x.dtype),
                             preferred_element_type=jnp.float32)
                  + jnp.einsum("bshp,btp->bhst", q_pe, k_pe.astype(x.dtype),
                               preferred_element_type=jnp.float32))
        logits = logits * c.softmax_scale
        valid = jnp.arange(c_kv.shape[1]) <= pos
        logits = jnp.where(valid, logits, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1).astype(x.dtype)
        o_lat = jnp.einsum("bhst,btr->bshr", probs, c_kv.astype(x.dtype))
        out = jnp.einsum("bshr,rhv->bshv", o_lat, w_vb)
        y = jnp.einsum("bshv,hvd->bsd", out, p["wo"].astype(x.dtype))
        return y, {"c_kv": c_kv, "k_pe": k_pe}

    def init_cache(self, batch: int, max_len: int,
                   dtype=COMPUTE_DTYPE) -> dict:
        c = self.cfg
        return {"c_kv": jnp.zeros((batch, max_len, c.kv_lora_rank), dtype),
                "k_pe": jnp.zeros((batch, max_len, c.qk_rope_head_dim),
                                  dtype)}

    def cache_axes(self) -> dict:
        return {"c_kv": ("batch", "kv_seq", None),
                "k_pe": ("batch", "kv_seq", None)}
