"""The fused CCU prepare program: one compiled XLA program per search wave.

PR 5 vectorized the commit pipeline but left it split across three
host/device round trips per wave: the wavefront search on device, then
slot scoring and trace-back in numpy against the pulled-back (B, n)
vectors.  This module fuses all three stages into a single jit program —
the paper's claim that circuit setup happens at line rate inside the
memory controller, restated as "one program dispatch per wave":

* **wavefront fixpoint** — ``repro.core.slot_alloc.wavefront_search_batch``
  (packed uint32, the whole wave at once, its upstream reads static
  shifts of the node axis rather than gathers);
* **slot scoring** — the int32 twin of ``_best_slots_np`` over the
  availability vectors at each destination;
* **trace-back** — a ``lax.scan`` lockstep walk (one step per hop, whole
  batch at once) whose per-step outputs are assembled into forward hop
  arrays by one vectorized gather, still inside the program.

Only the chosen arrival slot is traced on device; extra-slot bundles
(``max_extra_slots``) are rare and ride the existing host trace-back
against the returned vectors.  Everything the host commit loop needs
comes back as small arrays — the (B, n) vectors stay on device unless a
caller actually asks for them.

Bit-identity to the host pipeline (and hence to serial ``allocate``) is
by construction: same tie-breaks (argmin first occurrence = ascending
scan), same x->y->z upstream priority (argmax on the candidate mask =
first free dimension), same slot arithmetic — costs are int32 here
(int64 on host), so callers must guard ``t_ready < 2**31 - 2*n_slots``
(``repro.core.slot_alloc.TdmAllocator`` does).  The property harness in
``tests/test_fused_alloc.py`` proves it across randomized topologies,
wave sizes and conflict densities; ``ref.fused_prepare_ref`` is the
numpy oracle twin.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bitvec import UINT
from repro.core.topology import PORT_LOCAL, Mesh3D

__all__ = ["FusedPrepare", "fused_prepare", "fused_prepare_program",
           "FAR32"]

# int32 "infeasible" sentinel — the host twin (`_best_slots_np`) uses
# int64 2**62; any feasible start cycle is strictly below either, so the
# argmin choice is identical whenever t_ready fits int32 (guarded by the
# caller).
FAR32 = np.int32(2 ** 31 - 1)


# ---------------------------------------------------------------------------
# Slot scoring
# ---------------------------------------------------------------------------
def _score_jnp(avail: jax.Array, dists: jax.Array, t_readys: jax.Array,
               n_slots: int) -> jax.Array:
    """Per-(request, arrival-slot) start-cycle cost on packed uint32
    availability vectors: the (B, n_slots) int32 cost matrix, cost[b, s]
    the earliest injection cycle >= t_ready that arrives at slot s
    (FAR32 when s is busy).  argmin over it is the chosen arrival slot
    (ties resolve to the lowest slot, as the serial ascending scan does).
    Oracle: ``ref.slot_score_ref``."""
    slots = jnp.arange(n_slots, dtype=jnp.int32)
    free = ((avail[:, None] >> slots[None].astype(UINT)) & 1) == 0
    s_inj = (slots[None] - dists[:, None]) % n_slots
    c = t_readys[:, None] + ((s_inj - t_readys[:, None]) % n_slots)
    return jnp.where(free, c, jnp.int32(FAR32))


# ---------------------------------------------------------------------------
# Trace-back (lax.scan lockstep walk)
# ---------------------------------------------------------------------------
def _traceback_scan(vecs, occ, jreq, jsrc, jdst, a0, *, mesh: Mesh3D,
                    n_slots: int):
    """Scan twin of ``traceback_batch`` for one job per request: per-step
    (J,) outputs, assembled into (J, max_dist+1) forward hop arrays by a
    single vectorized gather — all inside the program."""
    coords = jnp.asarray(mesh.coord_array)
    strides = jnp.asarray([1, mesh.X, mesh.X * mesh.Y], jnp.int32)
    n = mesh.n_nodes
    J = jreq.shape[0]
    rows = jnp.arange(J)
    src_c = coords[jsrc]
    sign = jnp.sign(coords[jdst] - src_c).astype(jnp.int32)        # (J, 3)
    dists = jnp.abs(coords[jdst] - src_c).sum(1)
    dims = jnp.arange(3)
    ports = jnp.where(sign < 0, 2 * dims + 1, 2 * dims)            # (J, 3)
    if mesh.max_dist == 0:
        # 1x1x1 mesh: every circuit is the zero-hop (dst, LOCAL, slot).
        hop_n = jdst[:, None].astype(jnp.int32)
        hop_p = jnp.full((J, 1), PORT_LOCAL, jnp.int32)
        hop_s = a0[:, None].astype(jnp.int32)
        return hop_n, hop_p, hop_s, jnp.ones(J, bool), dists

    def step(carry, _):
        v, j, active, ok = carry
        jp = (j - 1) % n_slots
        u = jnp.clip(v[:, None] - sign * strides[None], 0, n - 1)
        valid = (sign != 0) & (coords[v] != src_c)
        busy = vecs[jreq[:, None], u] | occ[u, ports]
        cand = valid & (((busy >> jp[:, None].astype(UINT)) & 1) == 0)
        has = cand.any(1)
        d = jnp.argmax(cand, 1)          # first free dim: x -> y -> z
        mask = active & has
        ok = ok & ~(active & ~has)
        v2 = jnp.where(mask, u[rows, d], v)
        j2 = jnp.where(mask, jp, j)
        return (v2, j2, mask & (v2 != jsrc), ok), (v2, ports[rows, d], jp)

    v0 = jdst.astype(jnp.int32)
    carry0 = (v0, a0.astype(jnp.int32), v0 != jsrc, jnp.ones(J, bool))
    (_, _, _, ok), (sv, sp, ss) = jax.lax.scan(
        step, carry0, None, length=mesh.max_dist)
    # Forward hop t (t < dist) was produced at scan step (dist-1-t); the
    # final entry (t == dist) is (dst, LOCAL, arrival_slot).
    L = mesh.max_dist + 1
    tpos = jnp.arange(L)[None, :]
    sidx = jnp.clip(dists[:, None] - 1 - tpos, 0, mesh.max_dist - 1)
    mid = tpos < dists[:, None]
    last = tpos == dists[:, None]
    hop_n = jnp.where(mid, sv[sidx, rows[:, None]],
                      jnp.where(last, jdst[:, None], 0)).astype(jnp.int32)
    hop_p = jnp.where(mid, sp[sidx, rows[:, None]],
                      jnp.where(last, PORT_LOCAL, 0)).astype(jnp.int32)
    hop_s = jnp.where(mid, ss[sidx, rows[:, None]],
                      jnp.where(last, a0[:, None], 0)).astype(jnp.int32)
    return hop_n, hop_p, hop_s, ok, dists


# ---------------------------------------------------------------------------
# The fused program
# ---------------------------------------------------------------------------
@partial(jax.jit, static_argnames=("mesh", "n_slots"))
def fused_prepare_program(occ, srcs, dsts, t_readys, *, mesh: Mesh3D,
                          n_slots: int):
    """One compiled program: wavefront + slot scoring + trace-back.

    Args:
      occ: (n, N_PORTS) uint32 device occupancy (``device_busy_masks``,
        version-keyed and reused across waves).
      srcs, dsts, t_readys: (B,) int32 per-call request buffers.

    Returns ``(ints, flags, vecs)``: ``ints`` is (B, 3 + 3*(max_dist+1))
    int32 — columns [starts, arr, dists, hop_n..., hop_p..., hop_s...];
    ``flags`` is (B, 2 + n_slots) bool — columns [denied, ok, free...];
    ``vecs`` the converged (B, n) uint32 vectors for extra-slot
    trace-backs.  :func:`fused_prepare` unpacks them into a
    :class:`FusedPrepare`.
    """
    # Local import: core.slot_alloc lazily imports this module to run
    # its fused waves, so the top level must stay one-directional.
    from repro.core.slot_alloc import wavefront_search_batch

    B = srcs.shape[0]
    rows = jnp.arange(B)
    init = jnp.zeros(B, UINT)
    vecs = wavefront_search_batch(occ, srcs, dsts, init, mesh=mesh,
                                  n_slots=n_slots)
    coords = jnp.asarray(mesh.coord_array)
    dists = jnp.abs(coords[dsts] - coords[srcs]).sum(1)
    avail = vecs[rows, dsts] | occ[dsts, PORT_LOCAL]
    cost = _score_jnp(avail, dists, t_readys, n_slots)
    arr = jnp.argmin(cost, 1).astype(jnp.int32)
    starts = cost[rows, arr]
    free = cost != jnp.int32(FAR32)
    denied = ~free.any(1)
    hop_n, hop_p, hop_s, ok, _ = _traceback_scan(
        vecs, occ, rows, srcs, dsts, arr, mesh=mesh, n_slots=n_slots)
    # Pack everything bound for the host into two arrays (one int32, one
    # bool): two device->host pulls per wave instead of nine.
    ints = jnp.concatenate(
        [starts[:, None], arr[:, None], dists[:, None].astype(jnp.int32),
         hop_n, hop_p, hop_s], axis=1)
    flags = jnp.concatenate([denied[:, None], ok[:, None], free], axis=1)
    return ints, flags, vecs


@dataclasses.dataclass
class FusedPrepare:
    """Host-side view of one fused wave: small numpy arrays, trimmed to
    the true batch size; the (B, n) vectors stay on device until
    :meth:`vecs_np` is called (extra-slot bundles only)."""
    starts: np.ndarray        # (B,) int32 chosen start cycles
    arr: np.ndarray           # (B,) int32 chosen arrival slots
    denied: np.ndarray        # (B,) bool — no free arrival slot
    free: np.ndarray          # (B, n_slots) bool
    hop_n: np.ndarray         # (B, max_dist+1) int32 forward hop nodes
    hop_p: np.ndarray         # (B, max_dist+1) int32 forward hop ports
    hop_s: np.ndarray         # (B, max_dist+1) int32 forward hop slots
    ok: np.ndarray            # (B,) bool — trace-back reached the source
    dists: np.ndarray         # (B,) int32 manhattan distances
    _vecs_dev: jax.Array = dataclasses.field(repr=False, default=None)
    _batch: int = 0

    def vecs_np(self) -> np.ndarray:
        """(B, n) uint32 converged busy vectors (device pull, lazy)."""
        return np.asarray(self._vecs_dev)[:self._batch]


def _pow2_pad(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p


def fused_prepare_start(occ, srcs, dsts, t_readys, *, mesh: Mesh3D,
                        n_slots: int):
    """Dispatch the fused program for one wave without blocking.

    JAX dispatch is asynchronous: the returned token holds in-flight
    device arrays.  Pass it to :func:`fused_prepare_wait` to pull the
    outputs.
    """
    B = len(srcs)
    pad = _pow2_pad(B)
    s = np.zeros(pad, np.int32)
    d = np.zeros(pad, np.int32)
    t = np.zeros(pad, np.int32)
    s[:B] = srcs
    d[:B] = dsts
    t[:B] = t_readys
    outs = fused_prepare_program(
        jnp.asarray(occ), s, d, t, mesh=mesh, n_slots=n_slots)
    return outs, B, mesh


def fused_prepare_wait(token) -> FusedPrepare:
    """Block on a :func:`fused_prepare_start` token and unpack it."""
    (ints, flags, vecs), B, mesh = token
    ints = np.asarray(ints)[:B]
    flags = np.asarray(flags)[:B]
    L = mesh.max_dist + 1
    return FusedPrepare(
        starts=ints[:, 0], arr=ints[:, 1],
        denied=flags[:, 0], free=flags[:, 2:],
        hop_n=ints[:, 3:3 + L], hop_p=ints[:, 3 + L:3 + 2 * L],
        hop_s=ints[:, 3 + 2 * L:3 + 3 * L], ok=flags[:, 1],
        dists=ints[:, 2], _vecs_dev=vecs, _batch=B)


def fused_prepare(occ, srcs, dsts, t_readys, *, mesh: Mesh3D,
                  n_slots: int) -> FusedPrepare:
    """Run the fused program for one wave and pull the host-side outputs.

    ``srcs``/``dsts``/``t_readys`` are host arrays of any int dtype (the
    batch is padded to a power of two so jit retraces stay rare);
    ``t_readys`` must fit int32 — callers guard.  ``occ`` may be a
    device array (``SlotTable.device_busy_masks``) or host uint32 masks.
    """
    return fused_prepare_wait(fused_prepare_start(
        occ, srcs, dsts, t_readys, mesh=mesh, n_slots=n_slots))
