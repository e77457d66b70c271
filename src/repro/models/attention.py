"""Attention: GQA/MQA, causal / sliding-window / prefix-LM / cross, RoPE,
QK-norm, logit soft-capping, decode with (optionally ring-buffered) KV cache.

Two execution paths, selected by size and backend:

* plain einsum attention (small S, decode) — XLA fuses the iota-derived
  masks, no S x S bool tensor is ever materialized explicitly;
* chunked online-softmax attention (``lax.scan`` over KV blocks) for long
  prefills — O(S_q * block) live memory, the XLA-level analogue of the
  Pallas flash kernel in ``repro.kernels.flash_attention`` (which is used
  on real TPU backends; the scan path keeps CPU dry-runs compilable).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .common import (COMPUTE_DTYPE, AxesTree, Dense, Params, RMSNorm,
                     apply_rope, dense_init, softcap)

NEG_INF = -2.3819763e38   # == float32 min-ish; matches common flash impls


@dataclasses.dataclass(frozen=True)
class AttentionConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    rope_theta: float = 10000.0
    use_rope: bool = True
    qkv_bias: bool = False
    qk_norm: bool = False
    logit_softcap: float | None = None
    window: int | None = None          # sliding-window size (None = global)
    causal: bool = True                # False: encoder (bidirectional)
    cross: bool = False                # cross-attention (enc-dec decoder)
    query_scale: float | None = None   # default 1/sqrt(head_dim)

    @property
    def groups(self) -> int:
        return self.n_heads // self.n_kv


def _mask(q_pos, k_pos, cfg: AttentionConfig, prefix_len=None):
    """Additive mask from position vectors (no S x S bool materialized
    before fusion).  q_pos: (Sq,), k_pos: (Sk,) int32."""
    q = q_pos[:, None]
    k = k_pos[None, :]
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if cfg.causal and not cfg.cross:
        ok &= k <= q
        if prefix_len is not None:   # prefix-LM: bidirectional over prefix
            ok |= (k < prefix_len) & (q < prefix_len)
    if cfg.window is not None and not cfg.cross:
        ok &= (q - k) < cfg.window
    return jnp.where(ok, 0.0, NEG_INF)


@dataclasses.dataclass(frozen=True)
class Attention:
    cfg: AttentionConfig

    # q_norm / k_norm are read in float32 and stay out.
    compute_leaves = ("wq", "wk", "wv", "wo", "bq", "bk", "bv")

    # -- params ---------------------------------------------------------------
    def init(self, key) -> Params:
        c = self.cfg
        kq, kk, kv, ko, kn = jax.random.split(key, 5)
        p = {
            "wq": dense_init(kq, (c.d_model, c.n_heads, c.head_dim)),
            "wk": dense_init(kk, (c.d_model, c.n_kv, c.head_dim)),
            "wv": dense_init(kv, (c.d_model, c.n_kv, c.head_dim)),
            "wo": dense_init(ko, (c.n_heads, c.head_dim, c.d_model),
                             in_axis=0),
        }
        if c.qkv_bias:
            p["bq"] = jnp.zeros((c.n_heads, c.head_dim))
            p["bk"] = jnp.zeros((c.n_kv, c.head_dim))
            p["bv"] = jnp.zeros((c.n_kv, c.head_dim))
        if c.qk_norm:
            p["q_norm"] = RMSNorm(c.head_dim).init(kn)
            p["k_norm"] = RMSNorm(c.head_dim).init(kn)
        return p

    def axes(self) -> AxesTree:
        c = self.cfg
        a = {
            "wq": ("embed", "heads", "head_dim"),
            "wk": ("embed", "kv_heads", "head_dim"),
            "wv": ("embed", "kv_heads", "head_dim"),
            "wo": ("heads", "head_dim", "embed"),
        }
        if c.qkv_bias:
            a.update({"bq": ("heads", "head_dim"),
                      "bk": ("kv_heads", "head_dim"),
                      "bv": ("kv_heads", "head_dim")})
        if c.qk_norm:
            a["q_norm"] = {"scale": ("head_dim",)}
            a["k_norm"] = {"scale": ("head_dim",)}
        return a

    # -- qkv -------------------------------------------------------------------
    def _qkv(self, p: Params, x, kv_x, positions, kv_positions):
        c = self.cfg
        q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"].astype(x.dtype))
        src = x if kv_x is None else kv_x
        k = jnp.einsum("bsd,dnh->bsnh", src, p["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dnh->bsnh", src, p["wv"].astype(x.dtype))
        if c.qkv_bias:
            q = q + p["bq"].astype(q.dtype)
            k = k + p["bk"].astype(k.dtype)
            v = v + p["bv"].astype(v.dtype)
        if c.qk_norm:
            qn, kn = RMSNorm(c.head_dim), RMSNorm(c.head_dim)
            q = qn.apply(p["q_norm"], q)
            k = kn.apply(p["k_norm"], k)
        if c.use_rope and not c.cross:
            q = apply_rope(q, positions, c.rope_theta)
            k = apply_rope(k, kv_positions, c.rope_theta)
        scale = c.query_scale or (1.0 / np.sqrt(c.head_dim))
        return q * jnp.asarray(scale, q.dtype), k, v

    # -- core attention ---------------------------------------------------------
    def _attend_dense(self, q, k, v, mask):
        """q: (B,Sq,Hq,hd) k/v: (B,Sk,Hkv,hd) mask: (Sq,Sk) additive."""
        c = self.cfg
        b, sq, _, hd = q.shape
        qg = q.reshape(b, sq, c.n_kv, c.groups, hd)
        logits = jnp.einsum("bskgh,btkh->bkgst", qg, k).astype(jnp.float32)
        logits = softcap(logits, c.logit_softcap) + mask
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
        return out.reshape(b, sq, c.n_heads, hd)

    def _attend_windowed(self, q, k, v, q_pos, k_pos,
                         block_q: int = 256):
        """Sliding-window attention with static KV slicing (§Perf H6).

        Each q-block attends to a fixed-width KV span (window + block_q,
        lane-aligned) gathered with a dynamic slice — masked-out blocks are
        never computed, so local layers cost O(S * window) instead of
        O(S^2) (21x less logit volume for gemma3 local layers at 32k).
        The Pallas flash kernel performs the same structural skipping on
        TPU; this is its XLA twin."""
        c = self.cfg
        b, sq, _, hd = q.shape
        sk = k.shape[1]
        pad_q = (-sq) % block_q
        if pad_q:
            q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
            q_pos = jnp.pad(q_pos, (0, pad_q), constant_values=-(10 ** 9))
        nqb = q.shape[1] // block_q
        span = c.window + block_q
        span = min(-(-span // 128) * 128, sk)       # lane-align, cap at S
        qb4 = q.reshape(b, nqb, block_q, c.n_kv, c.groups * hd)
        qpb = q_pos.reshape(nqb, block_q)

        def one(args):
            qb, qp, idx = args
            qs = idx * block_q
            ks = jnp.clip(qs + block_q - span, 0, sk - span)
            kb = jax.lax.dynamic_slice_in_dim(k, ks, span, axis=1)
            vb = jax.lax.dynamic_slice_in_dim(v, ks, span, axis=1)
            kp = jax.lax.dynamic_slice_in_dim(k_pos, ks, span, axis=0)
            qg = qb.reshape(b, block_q, c.n_kv, c.groups, hd)
            logits = jnp.einsum("bskgh,btkh->bkgst", qg, kb
                                ).astype(jnp.float32)
            logits = softcap(logits, c.logit_softcap) + _mask(qp, kp, c)
            probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
            o = jnp.einsum("bkgst,btkh->bskgh", probs, vb)
            return o.reshape(b, block_q, c.n_kv, c.groups * hd)

        out = jax.lax.map(one, (qb4.swapaxes(0, 1), qpb,
                                jnp.arange(nqb)))
        out = out.swapaxes(0, 1).reshape(b, nqb * block_q, c.n_heads, hd)
        return out[:, :sq]

    def _attend_chunked(self, q, k, v, q_pos, k_pos, prefix_len,
                        block_k: int = 512):
        """Online-softmax over KV blocks; O(Sq*d) live memory."""
        c = self.cfg
        b, sq, _, hd = q.shape
        sk = k.shape[1]
        nblk = -(-sk // block_k)
        pad = nblk * block_k - sk
        if pad:
            k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            k_pos = jnp.pad(k_pos, (0, pad), constant_values=-(10 ** 9))
        qg = q.reshape(b, sq, c.n_kv, c.groups, hd)
        kb = k.reshape(b, nblk, block_k, c.n_kv, hd)
        vb = v.reshape(b, nblk, block_k, c.n_kv, hd)
        pb = k_pos.reshape(nblk, block_k)

        def step(carry, blk):
            m, l, acc = carry
            kc, vc, pc = blk
            logits = jnp.einsum("bskgh,btkh->bkgst", qg, kc
                                ).astype(jnp.float32)
            logits = softcap(logits, c.logit_softcap)
            logits = logits + _mask(q_pos, pc, c, prefix_len)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            # §Perf H7: probabilities in compute dtype after the fp32
            # max-subtraction — halves the dominant score-tensor traffic
            # of this XLA twin (the Pallas kernel keeps them in VMEM).
            pexp = jnp.exp(logits - m_new[..., None]).astype(q.dtype)
            l_new = l * alpha + pexp.sum(axis=-1, dtype=jnp.float32)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgst,btkh->bkgsh", pexp, vc).astype(jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, c.n_kv, c.groups, sq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, c.n_kv, c.groups, sq), jnp.float32)
        a0 = jnp.zeros((b, c.n_kv, c.groups, sq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, a0),
            (kb.swapaxes(0, 1), vb.swapaxes(0, 1), pb))
        out = acc / jnp.maximum(l, 1e-37)[..., None]
        out = out.astype(q.dtype).transpose(0, 3, 1, 2, 4)
        return out.reshape(b, sq, c.n_heads, hd)

    # -- public entry points ------------------------------------------------------
    def apply(self, p: Params, x, *, positions=None, kv_x=None,
              kv_positions=None, prefix_len=None,
              chunked_threshold: int = 2048) -> jax.Array:
        """Training / prefill attention over full sequences."""
        c = self.cfg
        b, s, _ = x.shape
        if positions is None:
            positions = jnp.arange(s)[None, :].repeat(b, 0)
        if kv_positions is None:
            kv_positions = (positions if kv_x is None else
                            jnp.arange(kv_x.shape[1])[None, :].repeat(b, 0))
        q, k, v = self._qkv(p, x, kv_x, positions, kv_positions)
        from repro.parallel.context import constrain, get_ctx
        ctx = get_ctx()
        tp_size = ctx.mesh.shape[ctx.tp] if ctx.mesh is not None else 1
        cp = ctx.cp_attention and q.shape[1] % max(tp_size, 1) == 0
        if cp:
            # Context-parallel attention: query-seq over the model axis,
            # K/V replicated — head-count-agnostic TP for attention.
            q = constrain(q, ctx.dp, ctx.tp, None, None)
            k = constrain(k, ctx.dp, None, None, None)
            v = constrain(v, ctx.dp, None, None, None)
        q_pos1, k_pos1 = positions[0], kv_positions[0]
        sk = k.shape[1]
        # Windowed slicing only pays once the window is a small fraction of
        # the sequence (measured crossover ~4x; at S=4k the chunked scan is
        # cheaper, at 32k the static slice is 3x on memory+collectives).
        if (c.window is not None and not c.cross and prefix_len is None
                and sk >= 4 * (c.window + 512)):
            out = self._attend_windowed(q, k, v, q_pos1, k_pos1)
        elif sk > chunked_threshold:
            out = self._attend_chunked(q, k, v, q_pos1, k_pos1, prefix_len)
        else:
            mask = _mask(q_pos1, k_pos1, c, prefix_len)
            out = self._attend_dense(q, k, v, mask)
        if cp:
            out = constrain(out, ctx.dp, None, None, None)
        return jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(out.dtype))

    def decode(self, p: Params, x, cache: dict, pos: jax.Array,
               kv_memory=None) -> tuple[jax.Array, dict]:
        """Single-token decode.  x: (B,1,D); cache {'k','v'}: (B,Smax,Hkv,hd);
        pos: scalar int32 — absolute position of the new token.

        Sliding-window layers pass caches with Smax == window (ring buffer);
        cross-attention layers pass ``kv_memory`` (already projected memory
        is not cached here — simplicity over decode speed for the stub)."""
        c = self.cfg
        b = x.shape[0]
        positions = jnp.full((b, 1), pos, jnp.int32)
        if c.cross:
            kv_pos = jnp.arange(kv_memory.shape[1])[None].repeat(b, 0)
            q, k, v = self._qkv(p, x, kv_memory, positions, kv_pos)
            logits_mask = 0.0
            k_cache, v_cache = k, v
            k_pos = kv_pos[0]
        else:
            q, k, v = self._qkv(p, x, None, positions, positions)
            smax = cache["k"].shape[1]
            slot = pos % smax if c.window is not None else pos
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
            cache = {"k": k_cache, "v": v_cache}
            # positions stored in the cache: ring for window layers
            idx = jnp.arange(smax)
            if c.window is not None:
                wrap = (pos // smax) * smax
                k_pos = jnp.where(idx <= pos % smax, wrap + idx,
                                  wrap - smax + idx)
            else:
                k_pos = idx
            valid = (k_pos >= 0) & (k_pos <= pos)
            if c.window is not None:
                valid &= (pos - k_pos) < c.window
            logits_mask = jnp.where(valid, 0.0, NEG_INF)[None, None, None,
                                                         None, :]
        qg = q.reshape(b, 1, c.n_kv, c.groups, c.head_dim)
        logits = jnp.einsum("bskgh,btkh->bkgst", qg, k_cache
                            ).astype(jnp.float32)
        logits = softcap(logits, c.logit_softcap) + logits_mask
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        out = jnp.einsum("bkgst,btkh->bskgh", probs, v_cache)
        out = out.reshape(b, 1, c.n_heads, c.head_dim)
        y = jnp.einsum("bsnh,nhd->bsd", out, p["wo"].astype(out.dtype))
        return y, cache

    def init_cache(self, batch: int, max_len: int,
                   dtype=COMPUTE_DTYPE) -> dict:
        c = self.cfg
        n = min(max_len, c.window) if c.window is not None else max_len
        shape = (batch, n, c.n_kv, c.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def cache_axes(self) -> dict:
        kv = ("batch", "kv_seq", "kv_heads", "head_dim")
        return {"k": kv, "v": kv}
