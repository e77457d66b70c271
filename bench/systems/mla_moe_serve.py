"""The system under test for a served DeepSeek-V2 (latent attention over a
latent cache, a leading dense layer, expert layers that hold some of the
experts): one client, closed loop, back-to-back ``Engine.generate``
requests, exactly as ``lm_serve`` drives a dense decoder, with the
engine's fabric scheduling every step's cache movement.

The benchmark makes the float32 master weights itself, on the chip, in one
jitted call from the seed, in the program's parameter layout; the engine
serves from the bfloat16 copy it builds once.  The check runs the plain
reference (``bench/reference/deepseek_v2.py``) on the same weights over a
seed-drawn sample of the completed requests and reads the gaps by which
the served tokens' logits lie below the reference's best, outside the
tokens served where the reference routes within ``check.route_tie_logit``
of a tie that concerns the held experts (their share is logged): their
mean, ``served_logit_gap_mean``, or their widest, as ``check`` names; it
replays every cache-transfer batch through ``bench/reference/tdm.py``; and
it counts the streams the engine shed from the fabric's tracking.
Counters: the (token, expert) pairs the window routed to the held experts
and to the others, read from the engine once.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.core import traffic
from bench.reference import deepseek_v2
from bench.systems import lm_serve

class System(lm_serve.System):

    def arch(self):
        """The program's config at the configuration's sizes, checked
        against what the file states."""
        from repro.configs import get_config
        from repro.configs.base import LayerKind, Yarn
        from repro.models.common import RMSNorm
        c = self.cfg
        rs = c["rope_scaling"]
        arch = dataclasses.replace(
            get_config(c["program_arch"]),
            n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
            n_heads=c["num_attention_heads"],
            n_kv=c["num_key_value_heads"], d_ff=c["intermediate_size"],
            vocab=c["vocab_size"], rope_theta=float(c["rope_theta"]),
            q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
            qk_nope_head_dim=c["qk_nope_head_dim"],
            qk_rope_head_dim=c["qk_rope_head_dim"],
            v_head_dim=c["v_head_dim"],
            yarn=Yarn(factor=float(rs["factor"]),
                      original_max_position=rs[
                          "original_max_position_embeddings"],
                      beta_fast=float(rs["beta_fast"]),
                      beta_slow=float(rs["beta_slow"]),
                      mscale=float(rs["mscale"]),
                      mscale_all_dim=float(rs["mscale_all_dim"])),
            first_k_dense=c["first_k_dense_replace"],
            n_experts=c["source_values"]["n_routed_experts"],
            experts_held=tuple(c["held_experts"]),
            top_k=c["num_experts_per_tok"],
            moe_dff=c["moe_intermediate_size"],
            n_shared_experts=c["n_shared_experts"], n_group=c["n_group"],
            topk_group=c["topk_group"],
            routed_scaling=float(c["routed_scaling_factor"]),
            norm_topk=c["norm_topk_prob"],
            tie_embeddings=c["tie_word_embeddings"])
        held = arch.experts_held[1] - arch.experts_held[0]
        runs = {"hidden_act": (arch.act, "silu"),
                "rms_norm_eps": (RMSNorm(1).eps, c["rms_norm_eps"]),
                "n_routed_experts": (held, c["n_routed_experts"]),
                "topk_method": ("group_limited_greedy", c["topk_method"]),
                "scoring_func": ("softmax", c["scoring_func"]),
                "moe_dispatch": (arch.moe_dispatch, "held"),
                "attention_bias": (arch.qkv_bias, c["attention_bias"]),
                "rope_scaling": ("yarn", rs["type"]),
                "moe_layer_freq": (1, c["moe_layer_freq"]),
                "layers": (arch.pattern, (LayerKind("mla", ffn="moe"),))}
        for key, (got, want) in runs.items():
            if got != want:
                raise RuntimeError(f"the program runs {key}={got!r}, the "
                                   f"configuration states {want!r}")
        return arch

    def make_params(self, model):
        """The weights, made on the chip in one jitted call from the seed
        in the program's layout: matmul weights N(0, 1/fan_in), the fan-in
        being the leaf's input axes (after any stacked layer axis)."""
        import jax
        import jax.numpy as jnp
        vocab = self.cfg["vocab_size"]
        inputs = {"wo": 2}          # (heads, v, d): two input axes
        experts = ("w_gate", "w_up", "w_down")
        shapes = jax.eval_shape(model.init, jax.random.key(0))
        leaves, tree = jax.tree_util.tree_flatten_with_path(shapes)

        def fan_in(path, shape):
            keys = [str(getattr(k, "key", k)) for k in path]
            if "groups" in keys:
                shape = shape[1:]
            name = keys[-1]
            if name in experts and "ffn" in keys and len(shape) == 3:
                shape = shape[1:]   # (held experts, in, out)
            return int(np.prod(shape[:inputs.get(name, 1)]))

        def init(key):
            out = []
            for i, (path, leaf) in enumerate(leaves):
                name = str(getattr(path[-1], "key", path[-1]))
                z = jax.random.normal(jax.random.fold_in(key, i),
                                      leaf.shape, jnp.float32)
                if name == "embedding":
                    x = z
                elif name == "scale":
                    x = 1.0 + 0.1 * z
                else:
                    x = z / np.sqrt(fan_in(path, leaf.shape))
                if name == "kernel":          # the LM head: zero padding
                    x = jnp.where(jnp.arange(leaf.shape[-1]) < vocab, x, 0.0)
                out.append(x.astype(leaf.dtype))
            return jax.tree_util.tree_unflatten(tree, out)

        return jax.jit(init)(traffic.jax_key(self.seed, 1))

    def mark(self) -> None:
        super().mark()
        self.routed0 = dict(self.engine.step_counters())

    def counters(self, win) -> dict:
        out = super().counters(win)
        got = self.engine.step_counters()
        out.update({k: v - self.routed0.get(k, 0) for k, v in got.items()})
        out["held_experts"] = (self.cfg["held_experts"][1]
                               - self.cfg["held_experts"][0])
        out["moe_layers"] = (self.cfg["num_hidden_layers"]
                             - self.cfg["first_k_dense_replace"])
        return out

    # -- the check ---------------------------------------------------------
    def weights(self, key):
        """The benchmark's own float32 weights, handed to the reference in
        its plain layout, one layer at a time."""
        return plain_weights(self.params, self.cfg, key)

    def gaps(self, control: bool = False):
        """The gaps of the sample's served tokens that the reference routes
        clear of a tie (``deepseek_v2.served_gaps``), read two ways, for
        the program and (with ``control``) the fp8 control: the widest,
        ``served_logit_gap``, and the mean, ``served_logit_gap_mean``.
        Also the number of served tokens compared, and the share of them
        routed near a tie (logged)."""
        vocab = self.cfg["vocab_size"]
        pad = max(x.shape[1] + n for x, n in self.pool)
        prog, ctrl, n_tok, n_tied = [], [], 0, 0
        for i in self.sample():
            p, n, toks, _ = self.done[i]
            prompt = self.pool[i % len(self.pool)][0]
            served = toks[:, p:]
            if toks.shape != (prompt.shape[0], p + n) or \
                    (toks[:, :p] != prompt).any() or \
                    served.min() < 0 or served.max() >= vocab:
                bad = {"served_logit_gap": float("inf"),
                       "served_logit_gap_mean": float("inf")}
                return bad, None, n_tok
            gap, c, tied = deepseek_v2.served_gaps(
                self.cfg, self.weights, toks, p, control=control, pad_to=pad)
            n_tied += int(tied.sum())
            prog.append(gap[~tied])
            if control:
                ctrl.append(c[~tied])
            n_tok += served.size
        self.log(f"reference: {n_tied} of {n_tok} served tokens routed "
                 f"within {self.cfg['check']['route_tie_logit']} logits of "
                 f"a tie that concerns the held experts")

        def read(parts):
            g = np.concatenate(parts)
            return {"served_logit_gap": float(g.max(initial=0.0)),
                    "served_logit_gap_mean": float(g.mean()) if g.size
                    else 0.0}
        return read(prog), (read(ctrl) if control else None), n_tok

    def control(self) -> dict:
        """The served-token gaps of the program, and of the fp8 control
        read at the same prompts and tokens."""
        prog, ctrl, n_tok = self.gaps(control=True)
        return {"program": prog, "control": ctrl, "tokens": n_tok}

    def release(self) -> None:
        """Streams the window shed from the fabric's tracking, kept for
        the check, then the engine let go."""
        self.shed = (self.engine.transfer_telemetry().get("shed_tenants", 0)
                     - self.tel0.get("shed_tenants", 0))
        super().release()

    def check(self) -> list:
        """The gap statistics the configuration's ``check`` limits, every
        cache-transfer batch against ``bench/reference/tdm.py``, and the
        streams shed."""
        wrong = self.circuits()
        got, _, n_tok = self.gaps()
        self.log(f"reference: {n_tok} served tokens of {len(self.sample())} "
                 f"requests compared")
        limits = self.cfg["check"]
        return [(k, v, limits[k]) for k, v in got.items() if k in limits] + [
            ("wrong_circuits", wrong, 0), ("shed_tenants", self.shed, 0)]


def plain_weights(params, cfg: dict, key):
    """The program's weights ``params`` in the reference's plain layout
    (``bench/reference/deepseek_v2.py``): ``"embed"``, ``"final_norm"``,
    ``"lm_head"`` over the configuration's vocabulary, or layer ``key``'s
    dict, sliced out of the stacked layers."""
    import jax
    p, vocab = params, cfg["vocab_size"]
    if key == "embed":
        return p["embed"]["embedding"][:vocab]
    if key == "final_norm":
        return p["final_norm"]["scale"]
    if key == "lm_head":
        return p["lm_head"]["kernel"][:, :vocab]
    lead = cfg["first_k_dense_replace"]
    if key < lead:
        layer = p["stack"]["lead"][f"l{key}"]
    else:
        layer = jax.tree.map(lambda a: a[key - lead],
                             p["stack"]["groups"]["l0"])
    mixer = layer["mixer"]
    w = {k: v for k, v in mixer.items() if k not in ("q_norm", "kv_norm")}
    w.update(q_norm=mixer["q_norm"]["scale"],
             kv_norm=mixer["kv_norm"]["scale"],
             ln1=layer["ln1"]["scale"], ln2=layer["ln2"]["scale"],
             **layer["ffn"])
    return w
