"""Dense FFN variants: SwiGLU / GeGLU / GELU (+bias) — LLaMA/Qwen/Gemma/
Whisper styles."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from .common import AxesTree, Params, dense_init


@dataclasses.dataclass(frozen=True)
class MLPConfig:
    d_model: int
    d_ff: int
    activation: str = "silu"     # silu | gelu | gelu_tanh
    gated: bool = True           # SwiGLU/GeGLU when True
    use_bias: bool = False


def _act(name: str):
    return {"silu": jax.nn.silu, "gelu": lambda x: jax.nn.gelu(x, approximate=False),
            "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True)}[name]


@dataclasses.dataclass(frozen=True)
class MLP:
    cfg: MLPConfig

    compute_leaves = ("w_up", "w_gate", "w_down", "b_up", "b_down")

    def init(self, key) -> Params:
        c = self.cfg
        k1, k2, k3 = jax.random.split(key, 3)
        p = {"w_up": dense_init(k1, (c.d_model, c.d_ff)),
             "w_down": dense_init(k2, (c.d_ff, c.d_model))}
        if c.gated:
            p["w_gate"] = dense_init(k3, (c.d_model, c.d_ff))
        if c.use_bias:
            p["b_up"] = jnp.zeros((c.d_ff,))
            p["b_down"] = jnp.zeros((c.d_model,))
        return p

    def axes(self) -> AxesTree:
        c = self.cfg
        a = {"w_up": ("embed", "mlp"), "w_down": ("mlp", "embed")}
        if c.gated:
            a["w_gate"] = ("embed", "mlp")
        if c.use_bias:
            a.update({"b_up": ("mlp",), "b_down": ("embed",)})
        return a

    def apply(self, p: Params, x: jax.Array) -> jax.Array:
        c = self.cfg
        up = jnp.einsum("...d,df->...f", x, p["w_up"].astype(x.dtype))
        if c.use_bias:
            up = up + p["b_up"].astype(up.dtype)
        if c.gated:
            gate = jnp.einsum("...d,df->...f", x, p["w_gate"].astype(x.dtype))
            h = _act(c.activation)(gate) * up
        else:
            h = _act(c.activation)(up)
        y = jnp.einsum("...f,fd->...d", h, p["w_down"].astype(x.dtype))
        if c.use_bias:
            y = y + p["b_down"].astype(y.dtype)
        return y
