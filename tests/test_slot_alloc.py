"""TDM slot-allocation invariants (the paper's Section 2.1 guarantees)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.scheduler import schedule_transfers
from repro.core.slot_alloc import (CopyRequest, TdmAllocator,
                                   TdmAllocatorLight, _wavefront_host,
                                   wavefront_search, wavefront_search_batch)
from repro.core.topology import Mesh3D, PORT_LOCAL

MESH = Mesh3D(8, 8, 4)
N_SLOTS = 16


def _random_stream(seed: int, n: int, with_extras: bool = True):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        s, d = rng.integers(MESH.n_nodes, size=2)
        while s == d:
            d = rng.integers(MESH.n_nodes)
        reqs.append(CopyRequest(
            int(s), int(d), int(rng.integers(64, 4096)),
            max_extra_slots=int(rng.integers(0, 4)) if with_extras else 0))
    return reqs


def test_basic_circuit_structure():
    alloc = TdmAllocator(MESH, N_SLOTS)
    src, dst = MESH.node_id(0, 0, 0), MESH.node_id(5, 3, 2)
    c = alloc.allocate(src, dst, 4096, cycle=0).circuit
    assert c is not None
    dist = MESH.manhattan(src, dst)
    assert len(c.hops) == dist + 1
    assert c.hops[0][0] == src
    assert c.hops[-1] == (dst, PORT_LOCAL, c.hops[-1][2])
    # Guarantee (2): increasingly-numbered slots along the path.
    slots = [h[2] for h in c.hops]
    for a, b in zip(slots, slots[1:]):
        assert (a + 1) % N_SLOTS == b
    # 3-cycle setup: injection cannot precede t+3 (paper Section 2.2).
    assert c.start_cycle >= 3
    assert c.start_cycle % N_SLOTS == slots[0]


@settings(max_examples=25, deadline=None)
@given(st.integers(0, MESH.n_nodes - 1), st.integers(0, MESH.n_nodes - 1),
       st.integers(0, 10))
def test_no_double_booking_property(src, dst, n_extra):
    """Guarantee (1): no slot of a link is shared by two circuits — the
    SlotTable asserts on double-booking, so allocating a random request
    stream must never trip it."""
    if src == dst:
        return
    alloc = TdmAllocator(MESH, N_SLOTS)
    rng = np.random.default_rng(src * 1000 + dst)
    alloc.allocate(src, dst, 512, cycle=0, max_extra_slots=n_extra % 4)
    for i in range(10):
        s, d = rng.integers(MESH.n_nodes, size=2)
        if s != d:
            alloc.allocate(int(s), int(d), 512, cycle=i * 2,
                           max_extra_slots=i % 3)


def test_saturation_and_rejection():
    alloc = TdmAllocator(MESH, N_SLOTS)
    src, dst = 0, 1
    got = 0
    for i in range(N_SLOTS + 4):
        if alloc.allocate(src, dst, 8 * N_SLOTS * 100, cycle=i).circuit:
            got += 1
    # one-hop pair: exactly n_slots circuits fit, further requests fail
    assert got == N_SLOTS


def test_nom_light_same_layer_matches_full():
    full = TdmAllocator(MESH, N_SLOTS)
    light = TdmAllocatorLight(MESH, N_SLOTS)
    src, dst = MESH.node_id(1, 1, 2), MESH.node_id(6, 4, 2)
    cf = full.allocate(src, dst, 1024, 0).circuit
    cl = light.allocate(src, dst, 1024, 0).circuit
    assert cf.start_cycle == cl.start_cycle
    assert len(cf.hops) == len(cl.hops)


def test_nom_light_uses_bus_across_layers():
    light = TdmAllocatorLight(MESH, N_SLOTS)
    src, dst = MESH.node_id(1, 1, 0), MESH.node_id(4, 2, 3)
    c = light.allocate(src, dst, 1024, 0).circuit
    assert c.uses_bus and c.bus_column >= 0
    # vertical bus: one slot regardless of layer count (single-cycle
    # multi-hop, Section 2.3) => distance = XY hops + 1
    assert c.distance == abs(4 - 1) + abs(2 - 1) + 1


def test_bus_contention_serializes():
    light = TdmAllocatorLight(MESH, N_SLOTS)
    col_src = MESH.node_id(2, 2, 0)
    # saturate the (2,2) column's bus with long transfers
    starts = []
    for i in range(N_SLOTS):
        c = light.allocate(col_src, MESH.node_id(2, 2, 3),
                           8 * N_SLOTS * 64, cycle=0).circuit
        if c is None:
            break
        starts.append(c.start_cycle)
    assert len(set(starts)) == len(starts)  # all distinct slots
    # bus fully reserved now
    res = light.allocate(col_src, MESH.node_id(2, 2, 1), 64, cycle=0)
    assert res.circuit is None


# --- concurrent batched scheduler -------------------------------------------
@pytest.mark.parametrize("cls", [TdmAllocator, TdmAllocatorLight])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_batched_equals_serial_on_identical_stream(cls, seed):
    """allocate_batch must be bit-identical to servicing the same stream
    through allocate() one request at a time (same circuits, same hops,
    same final table state) — losers of a stale search round are retried
    against fresh state, so no divergence is possible."""
    reqs = _random_stream(seed, 48)
    serial, batched = cls(MESH, N_SLOTS), cls(MESH, N_SLOTS)
    want = [serial.allocate(r.src, r.dst, r.nbytes, 0, r.max_extra_slots)
            for r in reqs]
    got = batched.allocate_batch(reqs, cycle=0)
    for i, (w, g) in enumerate(zip(want, got)):
        assert (w.circuit is None) == (g.circuit is None), i
        if w.circuit is not None:
            assert w.circuit.start_cycle == g.circuit.start_cycle, i
            assert w.circuit.hops == g.circuit.hops, i
            assert w.circuit.n_windows == g.circuit.n_windows, i
    np.testing.assert_array_equal(serial.table.expiry, batched.table.expiry)
    np.testing.assert_array_equal(serial.table.bus_expiry,
                                  batched.table.bus_expiry)
    rep = batched.last_report
    assert rep.n_committed + rep.n_denied == len(reqs)
    # the whole point: far fewer vectorized passes than requests
    assert rep.search_rounds < len(reqs)


@pytest.mark.parametrize("cls", [TdmAllocator, TdmAllocatorLight])
def test_batched_circuits_are_slot_disjoint(cls):
    """Invariant: no two circuits committed for one window share a
    (router, port, slot) — checked from the circuits themselves, not the
    table bookkeeping."""
    alloc = cls(MESH, N_SLOTS)
    results = alloc.allocate_batch(_random_stream(7, 64), cycle=0)
    claimed: set[tuple[int, int, int]] = set()
    committed = 0
    for res in results:
        if res.circuit is None:
            continue
        committed += 1
        for hop in res.circuit.hops:
            assert hop not in claimed, hop
            claimed.add(hop)
    assert committed > 1   # the schedule is actually concurrent


def test_batched_scheduler_unified_entry_reports_concurrency():
    alloc = TdmAllocator(MESH, N_SLOTS)
    results, report = schedule_transfers(_random_stream(3, 32),
                                         allocator=alloc, cycle=0)
    assert report.backend == "tdm"
    assert report.n_scheduled == sum(r.circuit is not None for r in results)
    assert report.max_inflight > 1       # concurrent circuits per window
    assert report.search_rounds < report.n_requests


def test_batch_respects_per_request_cycle_anchor():
    alloc = TdmAllocator(MESH, N_SLOTS)
    reqs = [CopyRequest(0, 5, 256), CopyRequest(8, 13, 256, cycle=40)]
    r0, r1 = alloc.allocate_batch(reqs, cycle=0)
    assert r0.circuit.start_cycle >= 3
    assert r1.circuit.start_cycle >= 43   # anchored request injects later


def test_anchored_request_reserved_through_streaming_interval():
    """Regression: a cycle-anchored request must hold its slots for its
    actual streaming interval (anchored at its own window, as serial
    allocate would), not the batch window — otherwise a later allocation
    can double-book the still-live circuit."""
    alloc = TdmAllocator(MESH, N_SLOTS)
    (_r0, r1) = alloc.allocate_batch(
        [CopyRequest(3, 9, 64), CopyRequest(0, 5, 2048, cycle=80)], cycle=0)
    serial = TdmAllocator(MESH, N_SLOTS)
    want = serial.allocate(0, 5, 2048, cycle=80).circuit
    c = r1.circuit
    w_res = 83 // N_SLOTS
    for node, port, slot in c.hops:
        assert alloc.table.expiry[node, port, slot] == w_res + c.n_windows
    assert want.n_windows == c.n_windows
    # a copy requested while the circuit is still streaming must not be
    # granted any of its hops (the reserve() assert would also trip)
    mid = (w_res + c.n_windows - 1) * N_SLOTS
    res = alloc.allocate(0, 5, 64, cycle=mid)
    if res.circuit is not None:
        assert not set(res.circuit.hops) & set(c.hops)


def test_memsim_inflight_cap_binds():
    from repro.memsim import SimParams, WorkloadSpec, generate, simulate
    reqs = generate(WorkloadSpec("fileCopy60", n_requests=400, seed=2))
    free = simulate(reqs, SimParams(config="nom", window=64))
    capped = simulate(reqs, SimParams(config="nom", window=64,
                                      nom_max_inflight=2))
    assert free.extra["nom_inflight_max"] > 2
    assert capped.extra["nom_inflight_max"] <= 2


def test_memsim_reports_concurrent_inflight_circuits():
    """The headline property end-to-end: on the TSV-conflict workload the
    simulator must keep more than one NoM circuit in flight per TDM window
    (and the allocator's own asserts guarantee slot-disjointness)."""
    from repro.memsim import SimParams, WorkloadSpec, generate, simulate
    reqs = generate(WorkloadSpec("fileCopy60", n_requests=800, seed=2))
    r = simulate(reqs, SimParams(config="nom", window=64))
    assert r.extra["nom_inflight_avg"] > 1.0, r.extra
    assert r.extra["nom_inflight_max"] >= 2


def test_windows_expire_and_slots_recycle():
    alloc = TdmAllocator(MESH, N_SLOTS)
    c1 = alloc.allocate(0, 3, 64, cycle=0).circuit   # short: few windows
    much_later = (c1.n_windows + 2) * N_SLOTS
    c2 = alloc.allocate(0, 3, 64, cycle=much_later).circuit
    assert c2 is not None
    assert c2.hops[0][2] in range(N_SLOTS)


# --- the wavefront search against its scalar host twin ----------------------
SEARCH_MESHES = {"8x8x4/16": (Mesh3D(8, 8, 4), 16),
                 "8x8x4/32": (Mesh3D(8, 8, 4), 32),
                 "4x4x2/8": (Mesh3D(4, 4, 2), 8),
                 "1x4x2/8": (Mesh3D(1, 4, 2), 8),
                 "1x1x1/4": (Mesh3D(1, 1, 1, vault_span_y=1), 4)}


def _search_requests(mesh: Mesh3D, rng, n_random: int = 48):
    """Edge cases first, then random pairs: src == dst, corner to corner
    both ways, flat-id neighbours that are not mesh neighbours (where a
    roll of the node axis wraps), mesh neighbours across each axis, and
    the fused path's padding rows (src = dst = 0)."""
    X, Y, Z, n = mesh.X, mesh.Y, mesh.Z, mesh.n_nodes
    nid = mesh.node_id
    pairs = [(0, 0), (n - 1, n - 1), (n // 2, n // 2), (0, n - 1), (n - 1, 0),
             (nid(X - 1, 0, 0), nid(0, Y - 1, Z - 1)),
             (nid(0, Y - 1, Z - 1), nid(X - 1, 0, 0))]
    for d, size in enumerate((X, Y, Z)):
        if size > 1:
            lo, hi = [0, 0, 0], [0, 0, 0]
            hi[d] = size - 1
            pairs += [(nid(*hi), nid(*hi) + 1), (nid(*hi) + 1, nid(*hi)),
                      (nid(*lo), nid(*hi)), (nid(*hi), nid(*lo))]
            step = [0, 0, 0]
            step[d] = 1
            pairs += [(nid(*lo), nid(*step)), (nid(*step), nid(*lo))]
    pairs = [(s, d) for s, d in pairs if s < n and d < n]
    rand = rng.integers(n, size=(n_random, 2))
    pairs += [tuple(p) for p in rand] + [(0, 0)] * 4
    srcs, dsts = (np.asarray(c, np.int32) for c in zip(*pairs))
    inits = rng.integers(0, 2 ** 32, size=len(srcs), dtype=np.uint64)
    inits[::2] = 0
    return srcs, dsts, inits.astype(np.uint32)


@pytest.mark.parametrize("density", [0.05, 0.4, 1.0])
@pytest.mark.parametrize("mesh_pick", list(SEARCH_MESHES))
def test_wavefront_search_batch_matches_host_twin(mesh_pick, density):
    """The batch search and its batch-of-one call equal the scalar
    topological twin bit for bit, on occupancies from nearly free to
    saturated, with non-zero (and over-wide) init vectors."""
    mesh, n_slots = SEARCH_MESHES[mesh_pick]
    rng = np.random.default_rng(int(density * 100) + mesh.n_nodes)
    bits = rng.random((mesh.n_nodes, 7, n_slots)) < density
    occ = (bits * (np.uint64(1) << np.arange(n_slots, dtype=np.uint64))) \
        .sum(-1).astype(np.uint32)
    srcs, dsts, inits = _search_requests(mesh, rng)
    got = np.asarray(jax.jit(partial(
        wavefront_search_batch, mesh=mesh, n_slots=n_slots))(
            occ, srcs, dsts, inits))
    want = np.stack([_wavefront_host(occ, mesh, n_slots, int(s), int(d),
                                     int(iv))
                     for s, d, iv in zip(srcs, dsts, inits)])
    np.testing.assert_array_equal(got, want)
    b = 1                                     # a random non-zero init
    one = wavefront_search(jnp.asarray(occ), jnp.int32(srcs[b]),
                           jnp.int32(dsts[b]), jnp.uint32(inits[b]),
                           mesh=mesh, n_slots=n_slots)
    np.testing.assert_array_equal(np.asarray(one), want[b])


@pytest.mark.parametrize("mesh_pick", list(SEARCH_MESHES))
def test_wavefront_search_batch_lowers_without_gather(mesh_pick):
    """Every upstream read is a static shift: the lowered search holds no
    gather, which on a TPU costs an element-wise pass per read."""
    mesh, n_slots = SEARCH_MESHES[mesh_pick]
    B = 64
    ids = jax.ShapeDtypeStruct((B,), jnp.int32)
    text = jax.jit(partial(wavefront_search_batch, mesh=mesh,
                           n_slots=n_slots)).lower(
        jax.ShapeDtypeStruct((mesh.n_nodes, 7), jnp.uint32), ids, ids,
        jax.ShapeDtypeStruct((B,), jnp.uint32)).as_text()
    assert "while" in text or mesh.max_dist == 0
    assert "gather" not in text
