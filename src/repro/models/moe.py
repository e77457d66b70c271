"""Mixture-of-Experts with expert parallelism — the end-to-end showcase of
the paper's technique.

Token dispatch to expert owners is a bulk all-to-all over the "model" mesh
axis; exactly the communication pattern NoM schedules.  Three dispatch
implementations are selectable per config / CLI:

* ``"nom"``   — NOM-scheduled ``ppermute`` rounds (conflict-free TDM slots
                over the ICI ring; see ``repro.core.nom_collectives``),
* ``"xla"``   — opaque ``lax.all_to_all`` (the "shared bus" baseline),
* ``"einsum"``— GSPMD-auto dense one-hot dispatch (no shard_map; used for
                tiny smoke configs and as a compiler-managed reference).

Routing is top-k softmax with capacity-factor token dropping (GShard
style); tokens are bucketed *by expert* at the source so the receive side
gets contiguous per-expert blocks and runs plain per-expert GEMMs.

Sharding contract (shard_map paths): expert weights enter the body already
sharded over the EP axis (each device holds its n_experts/ep slice); the
router is replicated.  Prefill/train shards the sequence dim over the EP
axis; decode (S == 1) uses replicated dispatch — every EP rank runs its own
experts over all local tokens and contributions are psum-combined, avoiding
an all-to-all that a single token cannot feed.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.fabric import FabricCluster, NomFabric
from repro.core.nom_collectives import nom_all_to_all
from repro.core.scheduler import TransferRequest, reduce_request
from repro.core.topology import StackedTopology, make_topology
from repro.parallel.context import get_ambient_mesh

from .common import AxesTree, Params, dense_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    capacity_factor: float = 1.5
    norm_topk: bool = True          # renormalize top-k probs (Qwen-style)
    dispatch: str = "nom"           # nom | xla | einsum
    ep_axis: str = "model"
    dp_axes: tuple = ("data",)
    aux_loss_weight: float = 0.01


def bucket_by(ids: jax.Array, n_buckets: int, capacity: int):
    """Order-preserving bucket positions with capacity dropping.

    ids: (N,) int32 in [0, n_buckets). Returns (pos, keep): pos[i] is the
    slot of item i within bucket ids[i]; keep[i] False if it overflowed.
    """
    onehot = jax.nn.one_hot(ids, n_buckets, dtype=jnp.int32)   # (N, B)
    pos_all = jnp.cumsum(onehot, axis=0) - 1                   # (N, B)
    pos = jnp.take_along_axis(pos_all, ids[:, None], axis=1)[:, 0]
    keep = pos < capacity
    return pos, keep


def _expert_ffn(h, wg, wu, wd):
    """h: (E_loc, C, D); weights: (E_loc, D, F) / (E_loc, F, D)."""
    act = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wg.astype(h.dtype)))
    act = act * jnp.einsum("ecd,edf->ecf", h, wu.astype(h.dtype))
    return jnp.einsum("ecf,efd->ecd", act, wd.astype(h.dtype))


@dataclasses.dataclass(frozen=True)
class MoE:
    cfg: MoEConfig

    def init(self, key) -> Params:
        c = self.cfg
        kr, kg, ku, kd = jax.random.split(key, 4)
        return {
            "router": dense_init(kr, (c.d_model, c.n_experts)),
            "w_gate": dense_init(kg, (c.n_experts, c.d_model, c.d_ff),
                                 in_axis=1),
            "w_up": dense_init(ku, (c.n_experts, c.d_model, c.d_ff),
                               in_axis=1),
            "w_down": dense_init(kd, (c.n_experts, c.d_ff, c.d_model),
                                 in_axis=1),
        }

    def axes(self) -> AxesTree:
        return {"router": ("embed", None),
                "w_gate": ("experts", "embed", "mlp"),
                "w_up": ("experts", "embed", "mlp"),
                "w_down": ("experts", "mlp", "embed")}

    def _param_specs(self):
        c = self.cfg
        return {"router": P(None, None),
                "w_gate": P(c.ep_axis, None, None),
                "w_up": P(c.ep_axis, None, None),
                "w_down": P(c.ep_axis, None, None)}

    # -- routing ----------------------------------------------------------------
    def _route(self, router_w, x2d: jax.Array):
        """x2d: (T, D) -> (weights (T,k), experts (T,k), aux_loss)."""
        c = self.cfg
        logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                            router_w.astype(jnp.float32))
        probs = jax.nn.softmax(logits, axis=-1)
        w, e = lax.top_k(probs, c.top_k)                       # (T,k)
        if c.norm_topk:
            w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
        # GShard load-balancing auxiliary loss.
        me = probs.mean(axis=0)                                # (E,)
        ce = jnp.zeros((c.n_experts,)).at[e.reshape(-1)].add(
            jnp.ones_like(e.reshape(-1), jnp.float32))
        ce = ce / jnp.maximum(ce.sum(), 1.0)
        aux = c.n_experts * jnp.sum(me * ce) * c.aux_loss_weight
        return w.astype(x2d.dtype), e, aux

    # -- shared bucketing ----------------------------------------------------------
    def _bucketize(self, x2d, flat_e, cap):
        c = self.cfg
        t = x2d.shape[0]
        pos, keep = bucket_by(flat_e, c.n_experts, cap)
        tok = jnp.repeat(jnp.arange(t), c.top_k)
        send = jnp.zeros((c.n_experts, cap + 1, x2d.shape[1]), x2d.dtype)
        slot = jnp.where(keep, pos, cap)
        send = send.at[flat_e, slot].set(x2d[tok], mode="drop")
        return send[:, :cap], pos, keep, tok

    def _combine(self, buf, flat_e, pos, keep, tok, w, t, d, cap, dtype):
        gathered = buf[flat_e, jnp.minimum(pos, cap - 1)]       # (t*k, D)
        contrib = gathered * (w.reshape(-1, 1)
                              * keep[:, None]).astype(gathered.dtype)
        return jnp.zeros((t, d), dtype).at[tok].add(contrib)

    # -- host-side dispatch transfer planning ---------------------------------------
    def _axis_size(self, name: str) -> int:
        """Ambient-mesh axis size: 1 when no mesh is installed or the
        mesh has no such axis."""
        mesh = get_ambient_mesh()
        if mesh is None or name not in mesh.axis_names:
            return 1
        return int(mesh.shape[name])

    def _ep_size(self) -> int:
        """EP axis size from the ambient mesh (1 when none installed)."""
        return self._axis_size(self.cfg.ep_axis)

    def plan_dispatch(self, p: Params, x: jax.Array, ep: int | None = None,
                      policy: str = "arrival"):
        """Expert-dispatch transfer plan from the bucketized routing.

        Mirrors what :meth:`_ep_body` puts on the wire: the router runs
        eagerly on the host, tokens are bucketed per source EP rank with
        the same capacity rule, and every non-empty (src_rank, dst_rank)
        block becomes a :class:`TransferRequest` — dispatch direction plus
        the combine return path — scheduled through the MoE's
        :class:`~repro.core.fabric.NomFabric` session on the ``(ep,)``
        EP ring, the same discipline as reshard.  Returns
        ``(TransferPlan, ScheduleReport)`` and stores them for
        :attr:`last_dispatch_report`.

        This is the *standalone* planner (plan without running the
        model); eager :meth:`apply` calls do NOT come through here — they
        reuse the traced routing via the block-counts aux output of
        ``_ep_body`` (:meth:`_plan_from_blocks`), so the router runs
        exactly once per forward.

        The plan covers one data-parallel replica's EP ring (each dp
        replica runs an identical, independent a2a): the batch dim is
        divided by the dp axis size so per-rank token counts and the
        capacity match what each device's ``_ep_body`` actually sends.

        Requires concrete (non-traced) inputs; ``ep`` defaults to the
        ambient mesh's EP-axis size.
        """
        ep = self._ep_size() if ep is None else int(ep)
        blocks, d, itemsize = self._dispatch_blocks(p, x, ep)
        return self._plan_from_blocks(blocks, d, itemsize, policy)

    def _dispatch_blocks(self, p: Params, x: jax.Array,
                         ep: int) -> tuple[np.ndarray, int, int]:
        """Host-side routing shared by the standalone planners: run the
        router per EP rank with the body's capacity rule and return the
        ``(ep, ep)`` kept-token block matrix plus the token feature dim
        and itemsize — the wire description both :meth:`plan_dispatch`
        (device ring) and :meth:`plan_dispatch_stacked` (bank level,
        multi-stack) schedule from."""
        c = self.cfg
        if isinstance(x, jax.core.Tracer):
            raise TypeError("dispatch planning needs concrete inputs "
                            "(host-side planning cannot run under jit)")
        e_loc = max(1, c.n_experts // ep)
        dp = 1
        for ax in c.dp_axes:
            dp *= self._axis_size(ax)
        b, s, d = x.shape
        b_loc = max(1, b // dp)
        x = x[:b_loc]
        s_loc = max(1, s // ep)
        itemsize = jnp.dtype(x.dtype).itemsize
        blocks = np.zeros((ep, ep), np.int64)   # kept tokens per (src, dst)
        for r in range(ep):
            x_loc = np.asarray(x[:, r * s_loc:(r + 1) * s_loc]
                               ).reshape(-1, d)
            t_loc = x_loc.shape[0]
            _w, e, _aux = self._route(p["router"], jnp.asarray(x_loc))
            flat_e = np.asarray(e).reshape(-1)
            cap = max(1, int(c.capacity_factor * t_loc * c.top_k
                             / c.n_experts))
            _pos, keep = bucket_by(jnp.asarray(flat_e), c.n_experts, cap)
            kept = np.bincount(flat_e[np.asarray(keep)],
                               minlength=c.n_experts)
            for expert, n_tok in enumerate(kept):
                blocks[r, expert // e_loc] += int(n_tok)
        return blocks, d, itemsize

    def plan_dispatch_stacked(self, p: Params, x: jax.Array,
                              topology: StackedTopology,
                              ep: int | None = None,
                              policy: str = "arrival"):
        """Expert-dispatch plan when the EP ring spans a multi-stack NoM.

        Same host-side routing as :meth:`plan_dispatch`, but instead of
        the abstract ``(ep,)`` device ring each EP rank is homed on a
        bank of a :class:`~repro.core.topology.StackedTopology` — rank
        ``r`` on stack ``r % n_stacks`` (ranks striped across cubes, the
        expert-placement a capacity-balanced deployment uses), bank
        ``r // n_stacks`` within the stack's mesh.  Every non-empty
        (src, dst) block then becomes a bank-level request through a
        per-topology :class:`~repro.core.fabric.FabricCluster`:
        same-stack blocks ride that stack's TDM mesh, cross-stack blocks
        negotiate two-phase circuits over the SerDes links.  Returns
        ``(results, report)``; ``report.n_cross_stack`` counts the
        inter-cube share, and :attr:`last_dispatch_report` is updated."""
        ep = self._ep_size() if ep is None else int(ep)
        blocks, d, itemsize = self._dispatch_blocks(p, x, ep)
        ns = topology.n_stacks

        def home(r: int) -> tuple[int, int]:
            stack = r % ns
            return stack, (r // ns) % topology.stacks[stack].n_nodes

        reqs = []
        for r in range(ep):
            for q in range(ep):
                if r == q or not blocks[r, q]:
                    continue
                (rs, rn), (qs, qn) = home(r), home(q)
                if (rs, rn) == (qs, qn):
                    continue         # two ranks folded onto one bank
                nbytes = int(blocks[r, q]) * d * itemsize
                reqs.append(TransferRequest(
                    src=rn, dst=qn, nbytes=nbytes, tag=("dispatch", r, q),
                    src_stack=rs, dst_stack=qs))
                reqs.append(TransferRequest(
                    src=qn, dst=rn, nbytes=nbytes, tag=("combine", q, r),
                    src_stack=qs, dst_stack=rs))
        results, report = self._stacked_cluster(topology).schedule(
            reqs, policy=policy)
        object.__setattr__(self, "_last_dispatch", (results, report))
        return results, report

    def plan_combine(self, p: Params, x: jax.Array, ep: int | None = None,
                     policy: str = "arrival"):
        """Expert-output combine as compute-class reduce traffic.

        The return leg of the a2a is a *sum*: destination rank ``r`` adds
        the expert outputs coming back from every rank it dispatched
        tokens to.  :meth:`plan_dispatch` models that leg as plain
        ``("combine", q, r)`` copies; this planner instead emits one
        fan-in :func:`~repro.core.scheduler.reduce_request` per
        destination rank — sources are the ranks with a non-empty
        ``blocks[r, q]`` block, the merge happens in the destination
        bank's ALU, and no copy-then-compute round trip touches the
        processor.

        Wire model: the fan-in streams every operand through the shared
        destination port, so the request is sized to the *widest*
        incoming block (``max_q blocks[r, q] * d * itemsize``) — slot
        occupancy is set by the longest operand stream, narrower
        operands ride the same circuit windows.

        Ranks are homed identity-mapped onto a square single-stack mesh
        (rank ``r`` = bank ``r``) and scheduled through a per-``ep``
        bank-level TDM fabric (:meth:`_combine_fabric` — the
        rounds-backend :meth:`_dispatch_fabric` cannot carry reduce, by
        design).  Returns ``(results, report)`` with
        ``report.n_reduce`` counting the fan-ins, and updates
        :attr:`last_dispatch_report`.
        """
        ep = self._ep_size() if ep is None else int(ep)
        blocks, d, itemsize = self._dispatch_blocks(p, x, ep)
        reqs = []
        for r in range(ep):
            srcs = [q for q in range(ep) if q != r and blocks[r, q]]
            if not srcs:
                continue
            widest = int(max(blocks[r, q] for q in srcs)) * d * itemsize
            reqs.append(reduce_request(srcs, r, nbytes=widest,
                                       tag=("combine_reduce", r)))
        results, report = self._combine_fabric(ep).schedule(
            reqs, policy=policy)
        object.__setattr__(self, "_last_dispatch", (results, report))
        return results, report

    def _combine_fabric(self, ep: int) -> NomFabric:
        """Per-EP-size bank-level fabric for :meth:`plan_combine`: a
        square mesh just large enough to home every rank on its own
        bank, kept across forwards like :meth:`_dispatch_fabric`."""
        fabrics = getattr(self, "_reduce_fabrics", None)
        if fabrics is None:
            fabrics = {}
            object.__setattr__(self, "_reduce_fabrics", fabrics)
        if ep not in fabrics:
            side = 1
            while side * side < ep:
                side += 1
            mesh = make_topology(1, mesh=(side, side, 1), vault_span_y=1)
            fabrics[ep] = NomFabric(mesh=mesh)
        return fabrics[ep]

    def _stacked_cluster(self, topology: StackedTopology) -> FabricCluster:
        """Per-topology :class:`FabricCluster` session for
        :meth:`plan_dispatch_stacked`, kept across forwards (same
        lifetime discipline as :meth:`_dispatch_fabric`)."""
        clusters = getattr(self, "_clusters", None)
        if clusters is None:
            clusters = {}
            object.__setattr__(self, "_clusters", clusters)
        if topology not in clusters:
            clusters[topology] = FabricCluster(topology=topology)
        return clusters[topology]

    def _dispatch_fabric(self, ep: int) -> NomFabric:
        """The MoE's dispatch-planning session: one rounds-backend
        :class:`NomFabric` per EP-ring size, kept across forwards so the
        dispatch telemetry accumulates (``fabric.telemetry()``)."""
        fabrics = getattr(self, "_fabrics", None)
        if fabrics is None:
            fabrics = {}
            object.__setattr__(self, "_fabrics", fabrics)
        if ep not in fabrics:
            fabrics[ep] = NomFabric(shape=(ep,), torus=True)
        return fabrics[ep]

    def _plan_from_blocks(self, blocks: np.ndarray, d: int, itemsize: int,
                          policy: str = "arrival"):
        """Schedule the EP-ring a2a from a (ep, ep) kept-token block
        matrix — the shared back half of :meth:`plan_dispatch` and of the
        traced-routing reuse path in :meth:`apply`."""
        ep = blocks.shape[0]
        reqs = []
        for r in range(ep):
            for q in range(ep):
                if r == q or not blocks[r, q]:
                    continue
                nbytes = int(blocks[r, q]) * d * itemsize
                reqs.append(TransferRequest(src=(r,), dst=(q,), nbytes=nbytes,
                                            tag=("dispatch", r, q)))
                reqs.append(TransferRequest(src=(q,), dst=(r,), nbytes=nbytes,
                                            tag=("combine", q, r)))
        plan, report = self._dispatch_fabric(ep).schedule(reqs, policy=policy)
        object.__setattr__(self, "_last_dispatch", (plan, report))
        return plan, report

    @property
    def last_dispatch_report(self):
        """ScheduleReport of the most recent dispatch plan (None before)."""
        last = getattr(self, "_last_dispatch", None)
        return None if last is None else last[1]

    # -- expert-parallel dispatch via all-to-all (train / prefill) -----------------
    def _ep_body(self, p: Params, x: jax.Array):
        """Per-device body; weights pre-sharded: w_* (E/ep, D, F).
        x: (b_loc, s_loc, D) — sequence sharded on the EP axis.

        Besides (y, aux_loss) the body returns its *dispatch block
        counts* — kept tokens per destination EP rank, shape (1, 1, ep) —
        as a third output: the traced routing made reusable, so eager
        ``apply`` refreshes the NoM dispatch plan without re-running the
        router on host (the double-routing ROADMAP item)."""
        c = self.cfg
        ep = lax.psum(1, c.ep_axis)
        if isinstance(ep, jax.Array):
            ep = int(ep)
        e_loc = c.n_experts // ep
        b, s, d = x.shape
        t = b * s
        x2d = x.reshape(t, d)
        w, e, aux = self._route(p["router"], x2d)
        flat_e = e.reshape(-1)
        cap = max(1, int(c.capacity_factor * t * c.top_k / c.n_experts))
        send, pos, keep, tok = self._bucketize(x2d, flat_e, cap)
        # Kept tokens per destination rank — the (src=me, dst) row of the
        # block matrix plan_dispatch would compute host-side.
        blocks = jnp.zeros((ep,), jnp.int32).at[flat_e // e_loc].add(
            keep.astype(jnp.int32))
        send = send.reshape(ep, e_loc * cap, d)
        a2a = (nom_all_to_all if c.dispatch == "nom" else
               lambda v, ax: lax.all_to_all(v, ax, 0, 0))
        recv = a2a(send, c.ep_axis)
        # recv[j]: tokens from rank j, bucketed for my e_loc experts.
        # (§Perf H5 refuted: contracting directly on a (ep, e_loc, cap, d)
        # layout regressed bytes 22% — XLA fuses these transposes into the
        # surrounding ops, the explicit einsum forced worse layouts.)
        h = recv.reshape(ep, e_loc, cap, d).transpose(1, 0, 2, 3)
        h = h.reshape(e_loc, ep * cap, d)
        y = _expert_ffn(h, p["w_gate"], p["w_up"], p["w_down"])
        y = y.reshape(e_loc, ep, cap, d).transpose(1, 0, 2, 3)
        y = y.reshape(ep, e_loc * cap, d)
        back = a2a(y, c.ep_axis).reshape(c.n_experts, cap, d)
        y_tok = self._combine(back, flat_e, pos, keep, tok, w, t, d, cap,
                              x.dtype)
        axes = tuple(c.dp_axes) + (c.ep_axis,)
        return (y_tok.reshape(b, s, d), lax.pmean(aux, axes),
                blocks.reshape(1, 1, ep))

    # -- replicated dispatch (decode: S == 1, batch < devices) ----------------------
    def _ep_body_replicated(self, p: Params, x: jax.Array):
        c = self.cfg
        ep = lax.psum(1, c.ep_axis)
        if isinstance(ep, jax.Array):
            ep = int(ep)
        e_loc = c.n_experts // ep
        b, s, d = x.shape
        t = b * s
        x2d = x.reshape(t, d)
        w, e, aux = self._route(p["router"], x2d)
        flat_e = e.reshape(-1)
        cap = max(1, int(c.capacity_factor * t * c.top_k
                         / max(1, c.n_experts // 4)))
        send, pos, keep, tok = self._bucketize(x2d, flat_e, cap)
        # Process only my expert slice; other ranks handle theirs.
        eid0 = lax.axis_index(c.ep_axis) * e_loc
        h = lax.dynamic_slice_in_dim(send, eid0, e_loc, axis=0)
        y = _expert_ffn(h, p["w_gate"], p["w_up"], p["w_down"])
        buf = jnp.zeros((c.n_experts, cap, d), x.dtype)
        buf = lax.dynamic_update_slice_in_dim(buf, y, eid0, axis=0)
        y_tok = self._combine(buf, flat_e, pos, keep, tok, w, t, d, cap,
                              x.dtype)
        y_tok = lax.psum(y_tok, c.ep_axis)
        axes = tuple(c.dp_axes) + (c.ep_axis,)
        return y_tok.reshape(b, s, d), lax.pmean(aux, axes)

    # -- GSPMD dense dispatch (reference / smoke path) -------------------------------
    def _einsum_body(self, p: Params, x: jax.Array):
        c = self.cfg
        b, s, d = x.shape
        t = b * s
        x2d = x.reshape(t, d)
        w, e, aux = self._route(p["router"], x2d)
        flat_e = e.reshape(-1)
        cap = max(1, int(c.capacity_factor * t * c.top_k / c.n_experts))
        buf, pos, keep, tok = self._bucketize(x2d, flat_e, cap)
        y = _expert_ffn(buf, p["w_gate"], p["w_up"], p["w_down"])
        y_tok = self._combine(y, flat_e, pos, keep, tok, w, t, d, cap,
                              x.dtype)
        return y_tok.reshape(b, s, d), aux

    def apply(self, p: Params, x: jax.Array) -> tuple[jax.Array, jax.Array]:
        """x: (B, S, D) global. Returns (y, aux_loss).

        Eager (non-traced) expert-parallel calls also refresh the NoM
        dispatch plan / :class:`ScheduleReport` — from the *traced*
        routing: ``_ep_body`` returns its dispatch block counts as an aux
        output, so the router runs exactly once per forward (no host-side
        re-route; skipped under jit, where the counts are not concrete).
        """
        c = self.cfg
        if c.dispatch == "einsum":
            return self._einsum_body(p, x)
        decode = x.shape[1] == 1
        x_spec = (P(c.dp_axes, None, None) if decode
                  else P(c.dp_axes, c.ep_axis, None))
        if decode:
            fn = jax.shard_map(
                self._ep_body_replicated,
                in_specs=(self._param_specs(), x_spec),
                out_specs=(x_spec, P()),
                check_vma=False)
            return fn(p, x)
        fn = jax.shard_map(
            self._ep_body,
            in_specs=(self._param_specs(), x_spec),
            out_specs=(x_spec, P(), P(c.dp_axes, c.ep_axis, None)),
            check_vma=False)
        y, aux, blocks = fn(p, x)
        if not isinstance(blocks, jax.core.Tracer) and self._ep_size() > 1:
            # blocks: (dp, ep, ep); dp replicas run identical independent
            # a2a rings — plan the first, as plan_dispatch does.
            self._plan_from_blocks(np.asarray(blocks[0], np.int64),
                                   d=x.shape[-1],
                                   itemsize=jnp.dtype(x.dtype).itemsize)
        return y, aux


# ---------------------------------------------------------------------------
# Held experts: the layer one expert-parallel rank runs, dropless
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class HeldMoEConfig:
    d_model: int
    d_ff: int
    n_experts: int                  # the router's width: every expert
    top_k: int
    held: tuple[int, int]           # experts [first, stop) held here
    n_shared: int = 0               # shared experts, width n_shared * d_ff
    n_group: int = 1                # group-limited greedy routing
    topk_group: int = 1
    routed_scaling: float = 1.0
    norm_topk: bool = True

    @property
    def n_held(self) -> int:
        return self.held[1] - self.held[0]


def route(logits: jax.Array, c: HeldMoEConfig):
    """Router of HF ``MoEGate`` (``group_limited_greedy``): softmax
    scores over every expert, the top-k among the ``topk_group`` groups
    whose best score is highest (one group of all: plain top-k); weights
    renormalized (``norm_topk``) or scaled by ``routed_scaling``.
    logits: (T, E) float32 -> (weights (T, k) float32, experts (T, k))."""
    scores = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    t = scores.shape[0]
    groups = scores.reshape(t, c.n_group, -1)
    _, top_groups = lax.top_k(groups.max(-1), c.topk_group)
    keep = jax.nn.one_hot(top_groups, c.n_group, dtype=bool).any(1)
    scores = jnp.where(keep[:, :, None], groups, 0.0).reshape(t, -1)
    w, e = lax.top_k(scores, c.top_k)
    if c.norm_topk:
        w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-20)
    else:
        w = w * c.routed_scaling
    return w, e


@dataclasses.dataclass(frozen=True)
class HeldMoE:
    """An expert layer told which experts it holds (``cfg.held``).  It
    routes every token over all ``n_experts``, computes the (token, expert)
    pairs that land on its own experts with no capacity cap, leaves out the
    pairs that land on experts held elsewhere, and adds the shared experts
    for every token.  On one chip it runs without the exchange: its result
    is the part of the layer its experts give, plus the shared experts.

    ``apply`` returns ``(y, counts)``: the pairs routed to held experts and
    to the others, as int32 scalars named by ``counters``."""
    cfg: HeldMoEConfig

    counters = ("routed_held", "routed_elsewhere")
    # The expert weights, read in the activations' dtype.  The router is
    # not one: as HF ``MoEGate`` does, it takes its logits from float32
    # operands, since rounding there flips discrete routing decisions.
    compute_leaves = ("w_gate", "w_up", "w_down",
                      "shared_gate", "shared_up", "shared_down")

    def init(self, key) -> Params:
        c = self.cfg
        keys = jax.random.split(key, 7)
        p = {"router": dense_init(keys[0], (c.d_model, c.n_experts)),
             "w_gate": dense_init(keys[1], (c.n_held, c.d_model, c.d_ff),
                                  in_axis=1),
             "w_up": dense_init(keys[2], (c.n_held, c.d_model, c.d_ff),
                                in_axis=1),
             "w_down": dense_init(keys[3], (c.n_held, c.d_ff, c.d_model),
                                  in_axis=1)}
        if c.n_shared:
            f = c.n_shared * c.d_ff
            p["shared_gate"] = dense_init(keys[4], (c.d_model, f))
            p["shared_up"] = dense_init(keys[5], (c.d_model, f))
            p["shared_down"] = dense_init(keys[6], (f, c.d_model))
        return p

    def axes(self) -> AxesTree:
        a = {"router": ("embed", None),
             "w_gate": ("experts", "embed", "mlp"),
             "w_up": ("experts", "embed", "mlp"),
             "w_down": ("experts", "mlp", "embed")}
        if self.cfg.n_shared:
            a.update(shared_gate=("embed", "mlp"), shared_up=("embed", "mlp"),
                     shared_down=("mlp", "embed"))
        return a

    def routed(self, p: Params, x2d: jax.Array):
        """The held experts' part of the layer for tokens x2d (T, D):
        (y (T, D), pairs routed to held experts, pairs routed elsewhere)."""
        c = self.cfg
        t, d = x2d.shape
        logits = jnp.einsum("td,de->te", x2d.astype(jnp.float32),
                            p["router"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        w, e = route(logits, c)
        # (T, held) routing weights: a pair routed elsewhere, or not
        # routed, weighs 0.
        local = e - c.held[0]
        held = (local >= 0) & (local < c.n_held)
        weight = (jax.nn.one_hot(jnp.where(held, local, c.n_held), c.n_held,
                                 dtype=jnp.float32) * w[..., None]).sum(1)
        # Every held expert runs over every token, so no pair can be
        # dropped.  At decode sizes (T up to a few hundred) this is no
        # more matmul work than a grouped kernel's tile per expert, and
        # XLA reads each layer's expert weights in place inside the
        # layer scan.
        wt = (lambda name: p[name].astype(x2d.dtype))
        h = jax.nn.silu(jnp.einsum("td,edf->etf", x2d, wt("w_gate"))) \
            * jnp.einsum("td,edf->etf", x2d, wt("w_up"))
        ys = jnp.einsum("etf,efd->etd", h, wt("w_down"))
        y = jnp.einsum("etd,te->td", ys, weight,
                       preferred_element_type=jnp.float32).astype(x2d.dtype)
        n_held = held.sum(dtype=jnp.int32)
        return y, n_held, jnp.int32(t * c.top_k) - n_held

    def shared(self, p: Params, x: jax.Array) -> jax.Array:
        """The shared experts, on every token."""
        wt = (lambda name: p[name].astype(x.dtype))
        h = jax.nn.silu(x @ wt("shared_gate")) * (x @ wt("shared_up"))
        return h @ wt("shared_down")

    def apply(self, p: Params, x: jax.Array):
        """x: (B, S, D) -> (y, {"routed_held": n, "routed_elsewhere": m})."""
        b, s, d = x.shape
        y, n_held, n_else = self.routed(p, x.reshape(b * s, d))
        y = y.reshape(b, s, d)
        if self.cfg.n_shared:
            y = y + self.shared(p, x)
        return y, {"routed_held": n_held, "routed_elsewhere": n_else}
