"""Multi-stack NoM: two-level topology, per-stack CCU authorities, and
cross-stack circuits (the ``docs/multistack.md`` contract).

Covers: degenerate single-stack mesh geometries, StackedTopology
addressing and link routing, the single-stack bit-identity of
FabricCluster, the structural invariants of committed cross-stack
circuits, the two-phase-commit rollback guarantee (a far-side conflict
leaks no near-side slot-table state), persistent rounds-backend link
reservations across flushes, the repaired ``shard_owners`` ownership
mapping, and the stack-aware serving placement (lease pinning,
``BankPool.migrate``, ``Engine.migrate_tenant``)."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.checkpoint.reshard import cross_stack_reshard_plan, shard_owners
from repro.core.fabric import FabricCluster, NomFabric
from repro.core.scheduler import ScheduleReport, TransferRequest
from repro.core.slot_alloc import CopyRequest, TdmAllocator
from repro.core.topology import (Mesh3D, PORT_LOCAL, StackedTopology,
                                 make_topology)
from repro.serving.engine import Engine
from repro.serving.placement import BankPool, LeafSpec

MESH = Mesh3D(4, 4, 2)
N_SLOTS = 16


def _copy_stream(seed: int, n: int, n_nodes: int, nbytes=256):
    rng = np.random.default_rng(seed)
    reqs = []
    for _ in range(n):
        s, d = rng.integers(n_nodes, size=2)
        while s == d:
            d = rng.integers(n_nodes)
        reqs.append(TransferRequest(src=int(s), dst=int(d), nbytes=nbytes))
    return reqs


# --------------------------------------------------------------------------
# Satellite: degenerate Mesh3D geometries
# --------------------------------------------------------------------------
def test_mesh_degenerate_x1_allocates():
    m = Mesh3D(1, 4, 2, vault_span_y=2)
    alloc = TdmAllocator(m, N_SLOTS)
    res = alloc.allocate(m.node_id(0, 0, 0), m.node_id(0, 3, 1), 512, cycle=0)
    c = res.circuit
    assert c is not None
    slots = [h[2] for h in c.hops]
    for a, b in zip(slots, slots[1:]):
        assert (a + 1) % N_SLOTS == b


def test_mesh_degenerate_z1_allocates():
    m = Mesh3D(4, 4, 1, vault_span_y=2)
    alloc = TdmAllocator(m, N_SLOTS)
    res = alloc.allocate(m.node_id(0, 0, 0), m.node_id(3, 3, 0), 512, cycle=0)
    assert res.circuit is not None
    assert res.circuit.hops[-1][1] == PORT_LOCAL


def test_mesh_invalid_geometry_raises_cleanly():
    with pytest.raises(ValueError, match="vault_span_y"):
        Mesh3D(4, 3, 2, vault_span_y=2)     # Y not divisible by span
    with pytest.raises(ValueError):
        Mesh3D(0, 4, 2)
    with pytest.raises(ValueError):
        Mesh3D(4, 4, -1)
    with pytest.raises(ValueError):
        Mesh3D(4, 4, 2, vault_span_y=0)


# --------------------------------------------------------------------------
# StackedTopology: addressing + link graph
# --------------------------------------------------------------------------
def test_make_topology_single_stack_is_bare_mesh():
    m = make_topology(1, mesh=(4, 4, 2))
    assert isinstance(m, Mesh3D) and m == MESH
    assert isinstance(make_topology(2, mesh=MESH), StackedTopology)


def test_stacked_validation():
    with pytest.raises(ValueError):
        StackedTopology(0, MESH)
    with pytest.raises(ValueError):
        StackedTopology(2, MESH, link="star")
    with pytest.raises(ValueError):
        StackedTopology(3, MESH, meshes=(MESH, MESH))
    with pytest.raises(ValueError):
        StackedTopology(2, MESH, link_bytes=0)


@settings(max_examples=30)
@given(st.integers(0, 3 * MESH.n_nodes - 1))
def test_addressing_roundtrip(gid):
    topo = StackedTopology(3, MESH)
    stack, node = topo.locate(gid)
    assert topo.global_id(stack, node) == gid
    assert topo.stack_of(gid) == stack
    assert 0 <= node < topo.stacks[stack].n_nodes


def test_link_graph_ring_and_full():
    ring = StackedTopology(4, MESH, link="ring")
    assert len(ring.links) == 4 and ring.n_channels == 8
    # Shortest ring direction, wrap included; ties go +1.
    assert ring.stack_route(0, 3) == [(0, 3)]
    assert ring.stack_route(0, 2) == [(0, 1), (1, 2)]
    assert ring.route_channels(0, 1) == [ring.channel(0, 1)]
    # Non-adjacent stacks have no direct channel under "ring".
    with pytest.raises(ValueError):
        ring.channel(0, 2)
    full = StackedTopology(4, MESH, link="full")
    assert len(full.links) == 6
    assert full.stack_route(0, 2) == [(0, 2)]
    per_hop = 1 + full.link_latency
    assert full.route_cycles(0, 2) == per_hop
    assert ring.route_cycles(0, 2) == 2 * per_hop
    # Directed channels are distinct per direction.
    assert ring.channel(0, 1) != ring.channel(1, 0)
    assert ring.is_cross(0, ring.global_id(1, 0))
    assert not ring.is_cross(0, 1)


# --------------------------------------------------------------------------
# FabricCluster: n_stacks=1 bit-identity
# --------------------------------------------------------------------------
def test_single_stack_cluster_bit_identical():
    reqs = _copy_stream(3, 24, MESH.n_nodes)
    reqs.append(TransferRequest(src=5, dst=5, nbytes=2048, op="init"))
    fab = NomFabric(mesh=MESH, n_slots=N_SLOTS)
    clu = FabricCluster(topology=StackedTopology(1, MESH), n_slots=N_SLOTS)
    for _ in range(2):                      # session behavior, not one-shot
        res_f, rep_f = fab.schedule(reqs)
        res_c, rep_c = clu.schedule(reqs)
        assert rep_f == rep_c
        for a, b in zip(res_f, res_c):
            assert a.circuit == b.circuit
            assert a.searched_cycle == b.searched_cycle
    assert clu.fabrics[0].clock == fab.clock
    assert rep_c.n_cross_stack == 0


# --------------------------------------------------------------------------
# Cross-stack circuits: structure
# --------------------------------------------------------------------------
def test_cross_stack_circuit_invariants():
    topo = StackedTopology(2, MESH, link_latency=5, link_bytes=4)
    clu = FabricCluster(topology=topo, n_slots=N_SLOTS)
    src, dst = (0, MESH.node_id(2, 3, 1)), (1, MESH.node_id(3, 1, 1))
    nbytes = 96
    c = clu.segmented.allocate(src, dst, nbytes, cycle=0)
    assert c is not None and c.cross_stack
    n = N_SLOTS
    # Near leg: increasing slots source -> bridge, arriving at slot a.
    slots = [h[2] for h in c.near_hops]
    for a, b in zip(slots, slots[1:]):
        assert (a + 1) % n == b
    a = slots[-1]
    assert c.near_hops[-1][0] == topo.bridge_of(0)
    # SerDes leg: first channel slot (a+1)%n, each hop advances 1+latency.
    chans = topo.route_channels(0, 1)
    assert [ch for ch, _s in c.link_slots] == chans
    s = (a + 1) % n
    for (_ch, sl), lat in zip(c.link_slots,
                              (topo.links[ch // 2].latency for ch in chans)):
        assert sl == s
        s = (s + 1 + lat) % n
    # Far leg: injection pinned at (a + T) % n, increasing to the sink.
    T = topo.route_cycles(0, 1)
    far_slots = [h[2] for h in c.far_hops]
    assert far_slots[0] == (a + T) % n
    for x, y in zip(far_slots, far_slots[1:]):
        assert (x + 1) % n == y
    assert c.far_hops[0][0] == topo.bridge_of(1)
    assert c.far_hops[-1][1] == PORT_LOCAL
    # Streaming rate: the bottleneck width sets the window count.
    bw = clu.segmented.bottleneck_bytes(0, 1)
    assert bw == 4 and c.n_windows == -(-nbytes // bw)
    assert c.distance == len(c.near_hops) - 1 + T + len(c.far_hops) - 1


def test_same_stack_requests_never_take_cluster_path():
    topo = StackedTopology(2, MESH)
    clu = FabricCluster(topology=topo, n_slots=N_SLOTS)
    reqs = [TransferRequest(src=(0, 1), dst=(0, 9), nbytes=256),
            TransferRequest(src=(1, 4), dst=(1, 20), nbytes=256)]
    _res, rep = clu.schedule(reqs)
    assert rep.n_scheduled == 2
    assert rep.n_cross_stack == 0 and clu.cross_requests == 0
    assert clu.segmented.link_windows == 0


def test_cross_stack_init_rejected():
    clu = FabricCluster(topology=StackedTopology(2, MESH))
    with pytest.raises(ValueError, match="init"):
        clu.schedule([TransferRequest(src=(0, 3), dst=(1, 3), nbytes=64,
                                      op="init")])


# --------------------------------------------------------------------------
# Two-phase commit: far-side conflict rolls back near-side state
# --------------------------------------------------------------------------
def _saturate(alloc):
    """Mark every port slot of a stack busy far into the future."""
    ports = alloc.table._ports
    ports.expiry[:] = 1 << 40
    ports._recompute(ports.window)


def test_far_conflict_rolls_back_near_reservations():
    topo = StackedTopology(2, MESH)
    clu = FabricCluster(topology=topo, n_slots=N_SLOTS)
    seg = clu.segmented
    _saturate(seg.allocators[1])
    near = seg.allocators[0].table._ports
    near_before = near.expiry.copy()
    links_before = seg.links.expiry.copy()
    c = seg.allocate((0, 10), (1, 21), 512, cycle=0)
    assert c is None
    assert seg.rollbacks >= 1 and seg.denied == 1
    np.testing.assert_array_equal(near.expiry, near_before)
    np.testing.assert_array_equal(seg.links.expiry, links_before)


@pytest.fixture(scope="module")
def far_batches_compiled():
    """Compile the prepare program at every padded batch size that the
    property's far-stack traffic reaches (1-40 requests: waves above
    eight pad to 16, 32 or 64), so that Hypothesis's deadline times the
    property and not the first XLA compile at each size."""
    for n in (16, 32, 40):
        clu = FabricCluster(topology=StackedTopology(2, MESH),
                            n_slots=N_SLOTS)
        clu.fabrics[1].schedule(
            _copy_stream(n, n, MESH.n_nodes, nbytes=512), cycle=0)
        clu.segmented.allocate((0, 0), (1, 1), 512, cycle=0)


@settings(max_examples=15)
@given(st.integers(0, 2**31 - 1), st.integers(1, 40))
@example(seed=108, n_far_circuits=14)
def test_two_phase_commit_leaks_nothing(far_batches_compiled, seed,
                                        n_far_circuits):
    """Property: whatever local traffic congests the far stack, a denied
    cross-stack request leaves near-side and link slot tables exactly as
    it found them, and a committed one reserves on both sides."""
    rng = np.random.default_rng(seed)
    topo = StackedTopology(2, MESH)
    clu = FabricCluster(topology=topo, n_slots=N_SLOTS)
    seg = clu.segmented
    # Congest stack 1 with its own local circuits (through its own CCU).
    local = _copy_stream(seed % 997, n_far_circuits, MESH.n_nodes, nbytes=512)
    clu.fabrics[1].schedule(local, cycle=0)
    near = seg.allocators[0].table._ports
    far = seg.allocators[1].table._ports
    near_before = near.expiry.copy()
    links_before = seg.links.expiry.copy()
    far_before = far.expiry.copy()
    s = int(rng.integers(MESH.n_nodes))
    d = int(rng.integers(MESH.n_nodes))
    c = seg.allocate((0, s), (1, d), int(rng.integers(16, 2048)), cycle=0)
    if c is None:
        np.testing.assert_array_equal(near.expiry, near_before)
        np.testing.assert_array_equal(seg.links.expiry, links_before)
        np.testing.assert_array_equal(far.expiry, far_before)
    else:
        assert (near.expiry != near_before).sum() == len(c.near_hops)
        assert (seg.links.expiry != links_before).sum() == len(c.link_slots)
        assert (far.expiry != far_before).sum() == len(c.far_hops)


# --------------------------------------------------------------------------
# Satellite: rounds-backend link reservations persist across flushes
# --------------------------------------------------------------------------
def test_rounds_busy_persists_across_anchored_flushes():
    mk = lambda: NomFabric(shape=(8,), torus=True)
    reqs = [TransferRequest(src=(i,), dst=((i + 1) % 8,), nbytes=4096)
            for i in range(8)]
    # Two flushes re-anchored at the same cycle share the session's link
    # reservations: the second batch must pack AROUND the first.
    fab = mk()
    plan1, _ = fab.schedule(reqs, cycle=0)
    plan2, _ = fab.schedule(reqs, cycle=0)
    fresh_plan, _ = mk().schedule(reqs, cycle=0)
    assert plan1.n_rounds == fresh_plan.n_rounds
    starts = lambda p: sorted(p.starts)
    assert starts(plan2) != starts(fresh_plan)   # contention is visible
    # Sequential (un-anchored) batches advance the clock past the drain,
    # so each plan is bit-identical to a fresh session's.
    seq = mk()
    p1, _ = seq.schedule(reqs)
    p2, _ = seq.schedule(reqs)
    assert starts(p1) == starts(p2) == starts(fresh_plan)


# --------------------------------------------------------------------------
# ScheduleReport: the cross-stack counter merges
# --------------------------------------------------------------------------
def test_report_merge_accumulates_cross_stack():
    a = ScheduleReport(backend="tdm", n_requests=2, n_scheduled=2,
                       n_windows=1, max_inflight=1, avg_inflight=1.0,
                       n_cross_stack=1)
    b = ScheduleReport(backend="tdm", n_requests=3, n_scheduled=3,
                       n_windows=1, max_inflight=1, avg_inflight=1.0,
                       n_cross_stack=2)
    assert a.merge(b).n_cross_stack == 3


# --------------------------------------------------------------------------
# Satellite: shard_owners implements its documented mapping
# --------------------------------------------------------------------------
def test_shard_owners_partitions_exactly():
    owners = shard_owners((8, 6), ("x", None), (4, 2), ("x", "y"))
    assert len(owners) == 8
    assert owners[(0, 0)] == ((0, 2), (0, 6))
    assert owners[(3, 1)] == ((6, 8), (0, 6))
    # Sharded dim: the 4 x-slices tile [0, 8) exactly; replicated dim is
    # the full extent everywhere.
    xs = sorted({r[0] for r in owners.values()})
    assert xs == [(0, 2), (2, 4), (4, 6), (6, 8)]
    assert all(r[1] == (0, 6) for r in owners.values())


def test_shard_owners_validates():
    with pytest.raises(ValueError, match="unknown mesh axis"):
        shard_owners((8,), ("q",), (4,), ("x",))
    with pytest.raises(ValueError, match="divisible"):
        shard_owners((9,), ("x",), (4,), ("x",))
    with pytest.raises(ValueError, match="reused"):
        shard_owners((8, 8), ("x", "x"), (4,), ("x",))
    with pytest.raises(ValueError):
        shard_owners((8,), ("x", None), (4,), ("x",))   # rank mismatch


def test_cross_stack_reshard_plan_moves_between_stacks():
    topo = make_topology(3, mesh=(4, 4, 2))
    res, rep = cross_stack_reshard_plan(
        {f"p{i}": 256 for i in range(9)}, topo, (0, 1, 2), (0,))
    assert rep.n_cross_stack > 0
    assert rep.n_scheduled == rep.n_requests    # uncontended: all commit
    with pytest.raises(ValueError):
        cross_stack_reshard_plan({"p": 1}, topo, (0,), (5,))


# --------------------------------------------------------------------------
# Stack-aware serving placement
# --------------------------------------------------------------------------
def _leaves(n=3):
    return [LeafSpec(f"l{i}", step_bytes=64, lease_bytes=256, ring_slots=4)
            for i in range(n)]


def test_pool_lease_pins_to_stacks():
    pool = BankPool(make_topology(3, mesh=(4, 4, 2)))
    for ls in pool.lease("a", _leaves(), stacks={1}):
        assert pool.stack_of(ls.home) == 1
        assert pool.stack_of(ls.staging) == 1   # staging never crosses
    assert pool.stack_load() == {1: 3}
    with pytest.raises(ValueError):
        pool.lease("b", _leaves(), stacks={7})


def test_pool_migrate_moves_only_off_stack_leases():
    pool = BankPool(make_topology(2, mesh=(4, 4, 2)))
    held = pool.lease("a", _leaves(4))
    on_dst = [ls for ls in held if pool.stack_of(ls.home) == 1]
    old, fresh = pool.migrate("a", 1)
    assert len(old) == len(fresh) == 4 - len(on_dst)
    assert all(pool.stack_of(ls.home) == 1 for ls in pool.leases("a"))
    # Kept leases stayed put; vacated homes are free; no old/fresh overlap
    # (a teardown scrub must never hit a live home).
    assert {ls.home for ls in on_dst} <= {ls.home for ls in pool.leases("a")}
    assert not {ls.home for ls in old} & {ls.home for ls in fresh}
    assert all(ls.home not in pool._owner for ls in old)
    assert pool.migrate("a", 1) == ([], [])     # idempotent


def test_pool_migrate_rolls_back_on_exhaustion():
    pool = BankPool(make_topology(2, mesh=(2, 2, 2)))
    pool.lease("big", [LeafSpec(f"x{i}", 8) for i in range(4)], stacks={1})
    pool.lease("t", [LeafSpec("y", 8)], stacks={0})
    snap = (dict(pool._owner), {k: list(v) for k, v in pool._leased.items()})
    assert pool.migrate("t", 1) == ([], [])
    assert dict(pool._owner) == snap[0]
    assert {k: list(v) for k, v in pool._leased.items()} == snap[1]


def test_partition_groups_never_span_stacks():
    pool = BankPool(make_topology(2, mesh=(4, 4, 2)), policy="partition")
    pool.lease("t0", _leaves(), stacks={0})
    pool.lease("t1", _leaves(), stacks={1})
    g0 = {c for c, t in pool._col_owner.items() if t == "t0"}
    g1 = {c for c, t in pool._col_owner.items() if t == "t1"}
    assert g0 and g1 and not g0 & g1
    assert all(pool._group_stack(g) == 0 for g in g0)
    assert all(pool._group_stack(g) == 1 for g in g1)


class _CacheStub:
    def init_caches(self, batch, max_len):
        return {"kv": jnp.zeros((batch, max_len, 8), jnp.int8),
                "state": jnp.zeros((batch, 16), jnp.int8)}

    def decode_step(self, params, token, caches, pos):
        return jnp.zeros((token.shape[0], 1, 7)), caches


def test_engine_migrate_tenant_cross_stack():
    eng = Engine(model=_CacheStub(), cfg=None, max_len=16,
                 cache_mesh=make_topology(2, mesh=(4, 4, 2)), ring_slots=4)
    assert isinstance(eng.fabric, FabricCluster)
    eng.open_tenant("t0", 2)
    eng.migrate_tenant("t0", 0)                 # pin everything to stack 0
    rep = eng.migrate_tenant("t0", 1)
    assert rep is not None
    assert rep.n_cross_stack >= 1               # the COPY leg crosses
    assert rep.n_init >= 1                      # vacated homes are scrubbed
    assert all(eng.pool.stack_of(ls.home) == 1
               for ls in eng.pool.leases("t0"))
    # The tenant keeps streaming after the move; telemetry counts it.
    assert eng.schedule_tick(["t0"]) is not None
    tel = eng.transfer_telemetry()
    assert tel["migrations"] >= 1 and tel["cross_stack"] >= 1
    assert eng.migrate_tenant("t0", 1) is None  # already there
    eng.close_tenant("t0")
    with pytest.raises(ValueError):
        eng.migrate_tenant("t0", 0)


def test_engine_single_stack_unchanged():
    eng = Engine(model=_CacheStub(), cfg=None, max_len=16,
                 cache_mesh=Mesh3D(2, 2, 2), ring_slots=4)
    assert isinstance(eng.fabric, NomFabric)
    eng.open_tenant("a", 1)
    assert eng.migrate_tenant("a", 0) is None   # one stack: no-op
    rep = eng.schedule_tick(["a"])
    assert rep.n_cross_stack == 0
    eng.close_tenant("a")
