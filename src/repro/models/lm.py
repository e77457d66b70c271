"""Top-level models: decoder-only CausalLM (incl. VLM prefix-LM variant)
and EncDecLM (Whisper-style), with losses and decode steps.

Modality frontends are stubs per the assignment: ``[audio]``/``[vlm]``
configs take precomputed frame/patch embeddings as inputs
(``enc_emb`` / ``prefix_emb``); only the transformer backbone is real.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig

from .blocks import LayerStack, _norm
from .common import COMPUTE_DTYPE, AxesTree, Embed, Params, dense_init


def _final_head_axes(cfg: ArchConfig):
    return ("embed", "vocab")


@dataclasses.dataclass(frozen=True)
class CausalLM:
    cfg: ArchConfig

    @property
    def stack(self) -> LayerStack:
        return LayerStack(self.cfg, self.cfg.n_layers)

    def init(self, key) -> Params:
        c = self.cfg
        k1, k2, k3, k4 = jax.random.split(key, 4)
        emb = Embed(c.padded_vocab, c.d_model, scale_by_sqrt_dim=c.scale_embed_sqrt_d)
        p = {"embed": emb.init(k1),
             "stack": self.stack.init(k2),
             "final_norm": _norm(c).init(k3)}
        if not c.tie_embeddings:
            p["lm_head"] = {"kernel": dense_init(k4, (c.d_model, c.padded_vocab))}
        return p

    def axes(self) -> AxesTree:
        c = self.cfg
        emb = Embed(c.padded_vocab, c.d_model)
        a = {"embed": emb.axes(),
             "stack": self.stack.axes(),
             "final_norm": _norm(c).axes()}
        if not c.tie_embeddings:
            a["lm_head"] = {"kernel": _final_head_axes(c)}
        return a

    def _logits(self, p: Params, x: jax.Array) -> jax.Array:
        c = self.cfg
        if c.tie_embeddings:
            return Embed(c.padded_vocab, c.d_model).attend(p["embed"], x)
        return jnp.einsum("...d,dv->...v", x.astype(jnp.float32),
                          p["lm_head"]["kernel"].astype(jnp.float32))

    def apply(self, p: Params, tokens: jax.Array, *, prefix_emb=None,
              remat: bool = True):
        """tokens: (B, S) int32; prefix_emb: (B, P, D) for VLM prefixes.
        Returns (logits over the token positions, aux_loss)."""
        c = self.cfg
        emb = Embed(c.padded_vocab, c.d_model, scale_by_sqrt_dim=c.scale_embed_sqrt_d)
        x = emb.apply(p["embed"], tokens)
        prefix_len = None
        if prefix_emb is not None:
            x = jnp.concatenate([prefix_emb.astype(x.dtype), x], axis=1)
            prefix_len = prefix_emb.shape[1]
        x, aux = self.stack.apply(p["stack"], x, prefix_len=prefix_len,
                                  remat=remat)
        x = _norm(c).apply(p["final_norm"], x)
        if prefix_emb is not None:
            x = x[:, prefix_len:]
        return self._logits(p, x), aux

    # -- decode -------------------------------------------------------------------
    def serving_params(self, p: Params) -> Params:
        """The weights ``decode_step`` serves from: the layer stack's
        compute-dtype leaves already cast (``LayerStack.serving_params``);
        the embedding, the final norm and the float32 head as they are.
        ``decode_step`` computes the same with either tree."""
        return {**p, "stack": self.stack.serving_params(p["stack"])}

    def init_caches(self, batch: int, max_len: int):
        return self.stack.init_caches(batch, max_len)

    def cache_axes(self):
        return self.stack.cache_axes()

    def init_counts(self) -> dict:
        """Zeroed counters for ``decode_step_counted``: one int32 scalar
        per name a layer counts (an empty dict for most models)."""
        return {k: jnp.zeros((), jnp.int32) for k in self.stack.counters}

    def decode_step_counted(self, p: Params, token: jax.Array, caches,
                            pos: jax.Array, counts: dict):
        """``decode_step``, and ``counts`` with the step's counters added
        (the expert layers' routed pairs, held here and elsewhere)."""
        c = self.cfg
        emb = Embed(c.padded_vocab, c.d_model, scale_by_sqrt_dim=c.scale_embed_sqrt_d)
        x = emb.apply(p["embed"], token)
        x, caches, n = self.stack.decode(p["stack"], x, caches, pos)
        x = _norm(c).apply(p["final_norm"], x)
        return self._logits(p, x), caches, {k: counts[k] + n[k]
                                            for k in counts}

    def decode_step(self, p: Params, token: jax.Array, caches,
                    pos: jax.Array):
        """token: (B, 1) -> (logits (B,1,V) fp32, new caches)."""
        logits, caches, _ = self.decode_step_counted(p, token, caches, pos,
                                                     {})
        return logits, caches


@dataclasses.dataclass(frozen=True)
class EncDecLM:
    """Whisper-style: bidirectional encoder over stub frame embeddings,
    causal decoder with cross-attention."""
    cfg: ArchConfig

    @property
    def encoder(self) -> LayerStack:
        return LayerStack(self.cfg, self.cfg.enc_layers, causal=False)

    @property
    def decoder(self) -> LayerStack:
        return LayerStack(self.cfg, self.cfg.n_layers, with_cross=True)

    def init(self, key) -> Params:
        c = self.cfg
        k1, k2, k3, k4, k5 = jax.random.split(key, 5)
        return {"embed": Embed(c.padded_vocab, c.d_model).init(k1),
                "encoder": self.encoder.init(k2),
                "enc_norm": _norm(c).init(k3),
                "decoder": self.decoder.init(k4),
                "final_norm": _norm(c).init(k5)}

    def axes(self) -> AxesTree:
        c = self.cfg
        return {"embed": Embed(c.padded_vocab, c.d_model).axes(),
                "encoder": self.encoder.axes(),
                "enc_norm": _norm(c).axes(),
                "decoder": self.decoder.axes(),
                "final_norm": _norm(c).axes()}

    def encode(self, p: Params, enc_emb: jax.Array, remat: bool = True):
        x, _ = self.encoder.apply(p["encoder"], enc_emb.astype(COMPUTE_DTYPE),
                                  remat=remat)
        return _norm(self.cfg).apply(p["enc_norm"], x)

    def apply(self, p: Params, enc_emb: jax.Array, tokens: jax.Array,
              remat: bool = True):
        c = self.cfg
        memory = self.encode(p, enc_emb, remat=remat)
        x = Embed(c.padded_vocab, c.d_model).apply(p["embed"], tokens)
        x, aux = self.decoder.apply(p["decoder"], x, memory=memory,
                                    remat=remat)
        x = _norm(c).apply(p["final_norm"], x)
        logits = Embed(c.padded_vocab, c.d_model).attend(p["embed"], x)
        return logits, aux

    def serving_params(self, p: Params) -> Params:
        """As ``CausalLM.serving_params``, for the decoder stack that
        ``decode_step`` runs."""
        return {**p, "decoder": self.decoder.serving_params(p["decoder"])}

    def init_caches(self, batch: int, max_len: int):
        return self.decoder.init_caches(batch, max_len)

    def cache_axes(self):
        return self.decoder.cache_axes()

    def decode_step(self, p: Params, token: jax.Array, caches,
                    pos: jax.Array, memory: jax.Array):
        c = self.cfg
        x = Embed(c.padded_vocab, c.d_model).apply(p["embed"], token)
        x, caches, _ = self.decoder.decode(p["decoder"], x, caches, pos,
                                           memory=memory.astype(x.dtype))
        x = _norm(c).apply(p["final_norm"], x)
        return Embed(c.padded_vocab, c.d_model).attend(p["embed"], x), caches


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------
def lm_loss(logits: jax.Array, tokens: jax.Array, aux: jax.Array,
            z_loss: float = 1e-4):
    """Next-token cross-entropy (+ router aux + z-loss).  logits fp32."""
    targets = tokens[:, 1:]
    lg = logits[:, :-1].astype(jnp.float32)
    logz = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    nll = (logz - tgt).mean()
    zl = z_loss * jnp.square(logz).mean()
    return nll + zl + aux, {"nll": nll, "z_loss": zl, "aux": aux}


def make_model(cfg: ArchConfig):
    if cfg.arch_type == "encdec":
        return EncDecLM(cfg)
    return CausalLM(cfg)
