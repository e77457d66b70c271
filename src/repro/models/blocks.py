"""Layer composition: decoder layers (attn/MLA/SSM/RG-LRU mixers x MLP/MoE
ffns), sandwich norms, and the scanned layer stack (after the leading
dense layers a config may name).

Stacks are ``lax.scan``-over-groups: the repeating layer pattern (e.g.
gemma3's 5 local + 1 global, recurrentgemma's 2 recurrent + 1 local-attn)
forms one *group*; parameters are stacked along a leading "layers" axis and
the group body is remat-ed — one HLO body regardless of depth, which keeps
512-device dry-run compiles tractable and is the standard production trick
(MaxText does the same).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, LayerKind

from .attention import Attention, AttentionConfig
from .common import (COMPUTE_DTYPE, AxesTree, LayerNorm, Params, RMSNorm,
                     prepend_layer_axis, stack_layers)
from .mla import MLA, MLAConfig
from .mlp import MLP, MLPConfig
from .moe import HeldMoE, HeldMoEConfig, MoE, MoEConfig
from .rglru import RecurrentBlock, RGLRUConfig
from .ssm import Mamba2, SSMConfig


def _prepend_none(axes):
    return jax.tree.map(lambda t: (None,) + tuple(t), axes,
                        is_leaf=lambda t: isinstance(t, tuple))


def _norm(cfg: ArchConfig):
    if cfg.norm_type == "layer":
        return LayerNorm(cfg.d_model)
    return RMSNorm(cfg.d_model, zero_centered=cfg.zero_centered_norm)


def make_mixer(cfg: ArchConfig, kind: LayerKind, causal: bool = True,
               cross: bool = False):
    if kind.mixer == "attn":
        return Attention(AttentionConfig(
            d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
            head_dim=cfg.resolved_head_dim,
            rope_theta=kind.rope_theta or cfg.rope_theta,
            use_rope=cfg.use_rope, qkv_bias=cfg.qkv_bias,
            qk_norm=cfg.qk_norm, logit_softcap=cfg.logit_softcap,
            window=kind.window, causal=causal, cross=cross))
    if kind.mixer == "mla":
        return MLA(MLAConfig(
            d_model=cfg.d_model, n_heads=cfg.n_heads,
            q_lora_rank=cfg.q_lora_rank, kv_lora_rank=cfg.kv_lora_rank,
            qk_nope_head_dim=cfg.qk_nope_head_dim,
            qk_rope_head_dim=cfg.qk_rope_head_dim,
            v_head_dim=cfg.v_head_dim,
            rope_theta=kind.rope_theta or cfg.rope_theta, yarn=cfg.yarn,
            causal=causal))
    if kind.mixer == "ssm":
        return Mamba2(SSMConfig(d_model=cfg.d_model, d_state=cfg.ssm_state,
                                head_dim=cfg.ssm_head_dim,
                                chunk=cfg.ssm_chunk))
    if kind.mixer == "rglru":
        return RecurrentBlock(RGLRUConfig(d_model=cfg.d_model,
                                          lru_width=cfg.lru_width))
    raise ValueError(kind.mixer)


def make_ffn(cfg: ArchConfig, kind: LayerKind):
    if kind.ffn == "mlp":
        return MLP(MLPConfig(cfg.d_model, cfg.d_ff, activation=cfg.act,
                             gated=cfg.gated_mlp, use_bias=cfg.mlp_bias))
    if kind.ffn == "moe" and cfg.moe_dispatch == "held":
        return HeldMoE(HeldMoEConfig(
            cfg.d_model, cfg.moe_dff, cfg.n_experts, cfg.top_k,
            held=cfg.experts_held or (0, cfg.n_experts),
            n_shared=cfg.n_shared_experts,
            n_group=cfg.n_group, topk_group=cfg.topk_group,
            routed_scaling=cfg.routed_scaling, norm_topk=cfg.norm_topk))
    if kind.ffn == "moe":
        return MoE(MoEConfig(cfg.d_model, cfg.moe_dff, cfg.n_experts,
                             cfg.top_k, norm_topk=cfg.norm_topk,
                             dispatch=cfg.moe_dispatch))
    return None


@dataclasses.dataclass(frozen=True)
class DecoderLayer:
    cfg: ArchConfig
    kind: LayerKind
    causal: bool = True
    with_cross: bool = False     # enc-dec decoder layers

    def _mods(self):
        mixer = make_mixer(self.cfg, self.kind, causal=self.causal)
        ffn = make_ffn(self.cfg, self.kind)
        cross = (make_mixer(self.cfg, LayerKind("attn"), cross=True)
                 if self.with_cross else None)
        return mixer, ffn, cross

    def init(self, key) -> Params:
        mixer, ffn, cross = self._mods()
        keys = jax.random.split(key, 8)
        n = _norm(self.cfg)
        p = {"ln1": n.init(keys[0]), "mixer": mixer.init(keys[1])}
        if cross is not None:
            p["ln_cross"] = n.init(keys[2])
            p["cross"] = cross.init(keys[3])
        if ffn is not None:
            p["ln2"] = n.init(keys[4])
            p["ffn"] = ffn.init(keys[5])
        if self.cfg.post_norms:
            p["ln1_post"] = n.init(keys[6])
            if ffn is not None:
                p["ln2_post"] = n.init(keys[7])
        return p

    def axes(self) -> AxesTree:
        mixer, ffn, cross = self._mods()
        n = _norm(self.cfg)
        a = {"ln1": n.axes(), "mixer": mixer.axes()}
        if cross is not None:
            a["ln_cross"] = n.axes()
            a["cross"] = cross.axes()
        if ffn is not None:
            a["ln2"] = n.axes()
            a["ffn"] = ffn.axes()
        if self.cfg.post_norms:
            a["ln1_post"] = n.axes()
            if ffn is not None:
                a["ln2_post"] = n.axes()
        return a

    def serving_params(self, p: Params) -> Params:
        """``p`` with the ``compute_leaves`` of its mixer, cross-attention
        and ffn cast to ``COMPUTE_DTYPE``.  A module names as
        ``compute_leaves`` the leaves it reads only through a cast to the
        activations' dtype, so the cast copy gives the same result and
        spares the step the cast.  Norms, and the leaves of modules that
        name none, stay as they are."""
        out = dict(p)
        for key, mod in zip(("mixer", "ffn", "cross"), self._mods()):
            names = getattr(mod, "compute_leaves", ())
            if key in p and names:
                out[key] = {k: v.astype(COMPUTE_DTYPE) if k in names else v
                            for k, v in p[key].items()}
        return out

    # -- full-sequence (train / prefill) -------------------------------------
    def apply(self, p: Params, x, *, positions=None, memory=None,
              prefix_len=None):
        from repro.parallel.context import constrain, get_ctx
        ctx = get_ctx()
        tp_size = ctx.mesh.shape[ctx.tp] if ctx.mesh is not None else 1
        sp = (ctx.seq_parallel and x.shape[1] % max(tp_size, 1) == 0
              and x.shape[1] > 1)

        def _sp(t):
            # Megatron-SP (§Perf H4): the residual stream lives
            # sequence-sharded over the TP axis, so norms/residual adds
            # touch 1/tp of the tokens and GSPMD lowers the TP psum into
            # reduce-scatter + later all-gather at the next matmul.
            return constrain(t, ctx.dp, ctx.tp, None) if sp else t

        n = _norm(self.cfg)
        aux = jnp.zeros((), jnp.float32)
        x = _sp(x)
        mixer, ffn, cross = self._mods()
        h = n.apply(p["ln1"], x)
        if isinstance(mixer, (Attention, MLA)):
            h = mixer.apply(p["mixer"], h, positions=positions,
                            prefix_len=prefix_len)
        else:
            h = mixer.apply(p["mixer"], h)
        if self.cfg.post_norms:
            h = _sp(n.apply(p["ln1_post"], h))
        x = x + _sp(h)
        if self.with_cross:
            h = n.apply(p["ln_cross"], x)
            h = cross.apply(p["cross"], h, kv_x=memory)
            x = x + _sp(h)
        if ffn is not None:
            h = n.apply(p["ln2"], x)
            if isinstance(ffn, MoE):
                h, aux = ffn.apply(p["ffn"], h)
            elif isinstance(ffn, HeldMoE):
                h, _ = ffn.apply(p["ffn"], h)
            else:
                h = ffn.apply(p["ffn"], h)
            if self.cfg.post_norms:
                h = _sp(n.apply(p["ln2_post"], h))
            x = x + _sp(h)
        return x, aux

    # -- decode ----------------------------------------------------------------
    def decode(self, p: Params, x, cache, pos, *, memory=None):
        """One token through the layer: (x, new cache, counts), the counts
        a dict of the ffn's ``counters`` (empty for most layers)."""
        n = _norm(self.cfg)
        mixer, ffn, cross = self._mods()
        counts = {}
        h = n.apply(p["ln1"], x)
        if isinstance(mixer, (Attention, MLA)):
            h, cache = mixer.decode(p["mixer"], h, cache, pos)
        else:
            h, cache = mixer.decode(p["mixer"], h, cache)
        if self.cfg.post_norms:
            h = n.apply(p["ln1_post"], h)
        x = x + h
        if self.with_cross:
            h = n.apply(p["ln_cross"], x)
            h, _ = cross.decode(p["cross"], h, {}, pos, kv_memory=memory)
            x = x + h
        if ffn is not None:
            h = n.apply(p["ln2"], x)
            if isinstance(ffn, MoE):
                h, _ = ffn.apply(p["ffn"], h)
            elif isinstance(ffn, HeldMoE):
                h, counts = ffn.apply(p["ffn"], h)
            else:
                h = ffn.apply(p["ffn"], h)
            if self.cfg.post_norms:
                h = n.apply(p["ln2_post"], h)
            x = x + h
        return x, cache, counts

    @property
    def counters(self) -> tuple[str, ...]:
        return getattr(self._mods()[1], "counters", ())

    def init_cache(self, batch: int, max_len: int):
        mixer = self._mods()[0]
        if isinstance(mixer, (Attention, MLA)):
            return mixer.init_cache(batch, max_len)
        return mixer.init_cache(batch)

    def cache_axes(self):
        return self._mods()[0].cache_axes()


def add_counts(a: dict, b: dict) -> dict:
    """Counters of two steps or layers, added name by name."""
    return {k: a.get(k, 0) + b.get(k, 0) for k in {**a, **b}}


@dataclasses.dataclass(frozen=True)
class LayerStack:
    """n_layers: the config's leading dense layers (``first_k_dense``,
    each the pattern's first kind with the dense MLP as its ffn), then
    scan-groups of the repeating pattern + tail."""
    cfg: ArchConfig
    n_layers: int
    causal: bool = True
    with_cross: bool = False

    @property
    def n_lead(self) -> int:
        return min(self.cfg.first_k_dense, self.n_layers)

    @property
    def group_size(self) -> int:
        return len(self.cfg.pattern)

    @property
    def n_groups(self) -> int:
        return (self.n_layers - self.n_lead) // self.group_size

    @property
    def n_tail(self) -> int:
        return (self.n_layers - self.n_lead) % self.group_size

    def _layers(self) -> list[DecoderLayer]:
        return [DecoderLayer(self.cfg, k, causal=self.causal,
                             with_cross=self.with_cross)
                for k in self.cfg.pattern]

    def _lead(self) -> DecoderLayer:
        kind = dataclasses.replace(self.cfg.pattern[0], ffn="mlp")
        return DecoderLayer(self.cfg, kind, causal=self.causal,
                            with_cross=self.with_cross)

    @property
    def counters(self) -> tuple[str, ...]:
        """Names of the counters ``decode`` returns."""
        names = {n for layer in self._layers() for n in layer.counters}
        return tuple(sorted(names))

    # -- params ------------------------------------------------------------------
    def init(self, key) -> Params:
        layers = self._layers()
        if self.n_lead:
            key, lkey = jax.random.split(key)
        gkeys = jax.random.split(key, self.n_groups + 1)
        groups = []
        for g in range(self.n_groups):
            lkeys = jax.random.split(gkeys[g], self.group_size)
            groups.append({f"l{i}": layers[i].init(lkeys[i])
                           for i in range(self.group_size)})
        p = {"groups": stack_layers(groups) if groups else {}}
        tkeys = jax.random.split(gkeys[-1], max(self.n_tail, 1))
        p["tail"] = {f"l{i}": layers[i].init(tkeys[i])
                     for i in range(self.n_tail)}
        if self.n_lead:
            lead = self._lead()
            p["lead"] = {f"l{i}": lead.init(k) for i, k in
                         enumerate(jax.random.split(lkey, self.n_lead))}
        return p

    def axes(self) -> AxesTree:
        layers = self._layers()
        group = {f"l{i}": layers[i].axes() for i in range(self.group_size)}
        a = {"groups": prepend_layer_axis(group) if self.n_groups else {},
             "tail": {f"l{i}": layers[i].axes()
                      for i in range(self.n_tail)}}
        if self.n_lead:
            a["lead"] = {f"l{i}": self._lead().axes()
                         for i in range(self.n_lead)}
        return a

    def serving_params(self, p: Params) -> Params:
        """Every layer's ``DecoderLayer.serving_params``; a stacked leaf
        casts as one array."""
        layers = self._layers()
        groups = ({f"l{i}": layers[i].serving_params(p["groups"][f"l{i}"])
                   for i in range(self.group_size)} if self.n_groups else {})
        out = {"groups": groups,
               "tail": {f"l{i}": layers[i].serving_params(p["tail"][f"l{i}"])
                        for i in range(self.n_tail)}}
        if self.n_lead:
            out["lead"] = {k: self._lead().serving_params(v)
                           for k, v in p["lead"].items()}
        return out

    # -- forward -------------------------------------------------------------------
    def apply(self, p: Params, x, *, positions=None, memory=None,
              prefix_len=None, remat: bool = True):
        layers = self._layers()
        kw = dict(positions=positions, memory=memory, prefix_len=prefix_len)
        aux = jnp.zeros((), jnp.float32)
        for i in range(self.n_lead):
            x, a = self._lead().apply(p["lead"][f"l{i}"], x, **kw)
            aux = aux + a

        def group_fn(x, gp):
            aux = jnp.zeros((), jnp.float32)
            for i, layer in enumerate(layers):
                x, a = layer.apply(gp[f"l{i}"], x, **kw)
                aux = aux + a
            return x, aux

        body = jax.checkpoint(group_fn) if remat else group_fn

        if self.n_groups:
            def scan_fn(carry, gp):
                x, aux = carry
                x, a = body(x, gp)
                return (x, aux + a), None
            (x, aux), _ = jax.lax.scan(scan_fn, (x, aux), p["groups"])
        for i in range(self.n_tail):
            x, a = layers[i].apply(p["tail"][f"l{i}"], x, **kw)
            aux = aux + a
        return x, aux

    # -- decode ----------------------------------------------------------------------
    def decode(self, p: Params, x, caches, pos, *, memory=None):
        """One token through every layer: (x, new caches, counts), the
        counts summed over the layers (an empty dict where no layer
        counts)."""
        layers = self._layers()
        counts = {}
        new_lead = {}
        for i in range(self.n_lead):
            x, new_lead[f"l{i}"], n = self._lead().decode(
                p["lead"][f"l{i}"], x, caches["lead"][f"l{i}"], pos,
                memory=memory)
            counts = add_counts(counts, n)

        def group_fn(x, gp, gc):
            new_c, counts = {}, {}
            for i, layer in enumerate(layers):
                x, new_c[f"l{i}"], n = layer.decode(
                    gp[f"l{i}"], x, gc[f"l{i}"], pos, memory=memory)
                counts = add_counts(counts, n)
            return x, new_c, counts

        if self.n_groups:
            def scan_fn(x, inp):
                gp, gc = inp
                x, nc, n = group_fn(x, gp, gc)
                return x, (nc, n)
            x, (new_groups, n) = jax.lax.scan(
                scan_fn, x, (p["groups"], caches["groups"]))
            counts = add_counts(counts, jax.tree.map(
                lambda v: v.sum(0, dtype=v.dtype), n))
        else:
            new_groups = caches["groups"]
        new_tail = {}
        for i in range(self.n_tail):
            x, new_tail[f"l{i}"], n = layers[i].decode(
                p["tail"][f"l{i}"], x, caches["tail"][f"l{i}"], pos,
                memory=memory)
            counts = add_counts(counts, n)
        out = {"groups": new_groups, "tail": new_tail}
        if self.n_lead:
            out["lead"] = new_lead
        return x, out, counts

    def init_caches(self, batch: int, max_len: int):
        layers = self._layers()
        group_c = {f"l{i}": layers[i].init_cache(batch, max_len)
                   for i in range(self.group_size)}
        if self.n_groups:
            groups = jax.tree.map(
                lambda v: jnp.broadcast_to(v, (self.n_groups,) + v.shape),
                group_c)
        else:
            groups = {}
        tail = {f"l{i}": layers[i].init_cache(batch, max_len)
                for i in range(self.n_tail)}
        out = {"groups": groups, "tail": tail}
        if self.n_lead:
            out["lead"] = {f"l{i}": self._lead().init_cache(batch, max_len)
                           for i in range(self.n_lead)}
        return out

    def cache_axes(self):
        layers = self._layers()
        group_a = {f"l{i}": layers[i].cache_axes()
                   for i in range(self.group_size)}
        groups = (prepend_layer_axis(group_a) if self.n_groups else {})
        tail = {f"l{i}": layers[i].cache_axes() for i in range(self.n_tail)}
        out = {"groups": groups, "tail": tail}
        if self.n_lead:
            out["lead"] = {f"l{i}": self._lead().cache_axes()
                           for i in range(self.n_lead)}
        return out
