"""Pure-host oracles of the fused prepare program.

``fused_prepare_ref`` and ``slot_score_ref`` are the numpy twins of
``fused.fused_prepare`` and its slot scoring; their search is the core
library's scalar host twin (``_wavefront_host``: one topological pass
over each request's shortest-path box), independent of the batch search
it checks.  The differential harness (``tests/test_fused_alloc.py``)
holds the compiled program bit-identical to these.
"""
from __future__ import annotations

import numpy as np

from repro.core.slot_alloc import (_best_slots_np, _wavefront_host,
                                   traceback_batch)
from repro.core.topology import PORT_LOCAL, Mesh3D

from .fused import FAR32, FusedPrepare


def slot_score_ref(avail: np.ndarray, dists: np.ndarray,
                   t_readys: np.ndarray, n_slots: int) -> np.ndarray:
    """numpy twin of the fused program's slot scoring on packed uint32
    availability vectors: the (B, n_slots) int32 cost matrix."""
    slots = np.arange(n_slots, dtype=np.int64)
    free = ((avail.astype(np.int64)[:, None] >> slots[None]) & 1) == 0
    s_inj = (slots[None] - dists[:, None]) % n_slots
    c = t_readys[:, None] + ((s_inj - t_readys[:, None]) % n_slots)
    return np.where(free, c, np.int64(FAR32)).astype(np.int32)


def fused_prepare_ref(occ: np.ndarray, srcs, dsts, t_readys, *,
                      mesh: Mesh3D, n_slots: int) -> FusedPrepare:
    """Host oracle of ``fused.fused_prepare``: scalar topological
    wavefront, int64 slot choice, lockstep numpy trace-back."""
    srcs = np.asarray(srcs, np.int64)
    dsts = np.asarray(dsts, np.int64)
    t_readys = np.asarray(t_readys, np.int64)
    B = len(srcs)
    occ = np.asarray(occ, np.uint32)
    vecs = np.stack([_wavefront_host(occ, mesh, n_slots, int(s), int(d), 0)
                     for s, d in zip(srcs, dsts)]) if B else \
        np.zeros((0, mesh.n_nodes), np.uint32)
    coords = mesh.coord_array
    dists = np.abs(coords[srcs] - coords[dsts]).sum(1)
    avail = vecs[np.arange(B), dsts] | occ[dsts, PORT_LOCAL]
    starts, arr, free, denied = _best_slots_np(avail, dists, t_readys,
                                               n_slots)
    starts = np.where(denied, np.int64(FAR32), starts)  # int32-safe sentinel
    hop_n, hop_p, hop_s, _, ok = traceback_batch(
        vecs, np.arange(B), occ, mesh, n_slots, srcs, dsts, arr)
    L = mesh.max_dist + 1
    hn = np.zeros((B, L), np.int32)
    hp = np.zeros((B, L), np.int32)
    hs = np.zeros((B, L), np.int32)
    hn[:, :hop_n.shape[1]] = hop_n
    hp[:, :hop_p.shape[1]] = hop_p
    hs[:, :hop_s.shape[1]] = hop_s
    return FusedPrepare(
        starts=starts.astype(np.int32), arr=arr.astype(np.int32),
        denied=denied, free=free, hop_n=hn, hop_p=hp, hop_s=hs, ok=ok,
        dists=dists.astype(np.int32), _vecs_dev=None, _batch=B)
