"""Model substrate: params-as-pytrees, logical sharding axes, norms, RoPE.

No flax — modules are plain dataclasses with ``init(key) -> params`` and
``apply(params, ...)``; a parallel ``axes()`` tree carries *logical* axis
names per parameter dimension (e.g. ("embed", "mlp")), mapped to mesh axes
by :mod:`repro.parallel.sharding` at lowering time.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

Params = Any        # nested dict of jnp arrays
AxesTree = Any      # same structure, leaves = tuple[str | None, ...]

# Compute dtype policy: params live in fp32, compute runs in bf16 (matmuls
# accumulate fp32 on the MXU), logits/losses in fp32.
COMPUTE_DTYPE = jnp.bfloat16
PARAM_DTYPE = jnp.float32


def dense_init(key, shape, in_axis: int = 0, scale: float = 1.0,
               dtype=PARAM_DTYPE):
    """Truncated-normal fan-in init (variance-scaling, as in T5/MaxText)."""
    fan_in = shape[in_axis]
    std = scale / np.sqrt(fan_in)
    return (std * jax.random.truncated_normal(key, -2.0, 2.0, shape)
            ).astype(dtype)


def embed_init(key, shape, dtype=PARAM_DTYPE):
    return (jax.random.normal(key, shape) * 1.0).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RMSNorm:
    dim: int
    eps: float = 1e-6
    zero_centered: bool = False   # gemma-style (1 + g) scaling

    def init(self, key) -> Params:
        del key
        return {"scale": jnp.zeros((self.dim,), PARAM_DTYPE)
                if self.zero_centered else jnp.ones((self.dim,), PARAM_DTYPE)}

    def axes(self) -> AxesTree:
        return {"scale": ("embed",)}

    def apply(self, p: Params, x: jax.Array) -> jax.Array:
        # dtype discipline (§Perf H2): only the reduced statistic runs in
        # fp32; the full tensor stays in its compute dtype so TP
        # all-reduces / CP all-gathers around norms move bf16, not fp32.
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1,
                       keepdims=True)
        inv = jax.lax.rsqrt(var + self.eps).astype(x.dtype)
        scale = p["scale"].astype(jnp.float32)
        if self.zero_centered:
            scale = 1.0 + scale
        return x * inv * scale.astype(x.dtype)


@dataclasses.dataclass(frozen=True)
class LayerNorm:
    dim: int
    eps: float = 1e-5

    def init(self, key) -> Params:
        del key
        return {"scale": jnp.ones((self.dim,), PARAM_DTYPE),
                "bias": jnp.zeros((self.dim,), PARAM_DTYPE)}

    def axes(self) -> AxesTree:
        return {"scale": ("embed",), "bias": ("embed",)}

    def apply(self, p: Params, x: jax.Array) -> jax.Array:
        xf = x.astype(jnp.float32)
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        inv = jax.lax.rsqrt(var + self.eps)
        # stats fp32, tensor stays in compute dtype (see RMSNorm note)
        y = (x - mu.astype(x.dtype)) * inv.astype(x.dtype)
        return (y * p["scale"].astype(x.dtype)
                + p["bias"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Embed:
    vocab: int
    dim: int
    scale_by_sqrt_dim: bool = False   # gemma family scales embeddings

    def init(self, key) -> Params:
        return {"embedding": embed_init(key, (self.vocab, self.dim))}

    def axes(self) -> AxesTree:
        return {"embedding": ("vocab", "embed")}

    def apply(self, p: Params, ids: jax.Array) -> jax.Array:
        # Gather, then cast the rows taken: the same values as casting the
        # table first, without converting the whole table every call.
        x = jnp.take(p["embedding"], ids, axis=0).astype(COMPUTE_DTYPE)
        if self.scale_by_sqrt_dim:
            x = x * jnp.asarray(np.sqrt(self.dim), COMPUTE_DTYPE)
        return x

    def attend(self, p: Params, x: jax.Array) -> jax.Array:
        """Tied-embedding logits (fp32)."""
        return jnp.einsum("...d,vd->...v", x.astype(jnp.float32),
                          p["embedding"].astype(jnp.float32))


# ---------------------------------------------------------------------------
# Dense layers
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Dense:
    in_dim: int
    out_dim: int
    use_bias: bool = False
    in_axis_name: str | None = "embed"
    out_axis_name: str | None = "mlp"

    compute_leaves = ("kernel", "bias")

    def init(self, key) -> Params:
        p = {"kernel": dense_init(key, (self.in_dim, self.out_dim))}
        if self.use_bias:
            p["bias"] = jnp.zeros((self.out_dim,), PARAM_DTYPE)
        return p

    def axes(self) -> AxesTree:
        a = {"kernel": (self.in_axis_name, self.out_axis_name)}
        if self.use_bias:
            a["bias"] = (self.out_axis_name,)
        return a

    def apply(self, p: Params, x: jax.Array) -> jax.Array:
        y = jnp.einsum("...d,df->...f", x, p["kernel"].astype(x.dtype))
        if self.use_bias:
            y = y + p["bias"].astype(y.dtype)
        return y


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float = 10000.0) -> np.ndarray:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array,
               theta: float = 10000.0) -> jax.Array:
    """x: (..., seq, heads, head_dim), positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = jnp.asarray(rope_freqs(head_dim, theta))          # (hd/2,)
    angles = positions[..., :, None, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)               # (..., s, 1, hd/2)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(logits: jax.Array, cap: float | None) -> jax.Array:
    if cap is None:
        return logits
    return jnp.tanh(logits / cap) * cap


# ---------------------------------------------------------------------------
# tree utilities
# ---------------------------------------------------------------------------
def stack_layers(param_list: list[Params]) -> Params:
    """Stack per-layer param trees along a new leading 'layers' axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *param_list)


def prepend_layer_axis(axes: AxesTree) -> AxesTree:
    """Add the scanned 'layers' dimension to every axes tuple."""
    return jax.tree.map(lambda t: ("layers",) + tuple(t), axes,
                        is_leaf=lambda t: isinstance(t, tuple))


def count_params(params: Params) -> int:
    return sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
