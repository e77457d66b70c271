"""Differential harness for the fused CCU prepare program (PR 8).

The allocator's fused backend runs wavefront search + slot choice +
trace-back as ONE compiled program per wave and commits through a
struct-of-arrays pipeline.  Correctness story, in three layers:

* **program vs oracle** — ``fused_prepare`` (the compiled program) is
  bit-identical to ``ref.fused_prepare_ref`` (scalar host twin) on random
  occupancies, on meshes from one node to the paper's 8x8x4 at 16 and
  32 slots: starts, arrival slots, denials, hop arrays, distances;
* **pipeline vs serial** — ``allocate_batch`` under ``backend="fused"``
  is bit-identical to feeding the same stream through serial
  ``allocate`` one request at a time: circuits (every field), the final
  slot-table expiry state, and commit/deny counts — swept over
  randomized topologies, wave sizes, conflict densities, and copy/init
  op mixes (hypothesis-shim driven);
* **telemetry** — ``fused_waves`` / ``host_waves`` track which backend
  served each prepare round, agree between ``BatchReport``,
  ``ScheduleReport`` and fabric/memsim telemetry, and the host/fused
  reports agree with *each other* on every shared counter.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.slot_alloc import CopyRequest, TdmAllocator
from repro.core.topology import Mesh3D
from repro.kernels.slot_alloc import fused as fused_mod
from repro.kernels.slot_alloc import ref as ref_mod

# Small topology/slot grid: every (mesh, n_slots, padded wave) combo is
# one XLA compile, so the sweep reuses these rather than free-ranging.
MESHES = {
    "tall": Mesh3D(4, 4, 2, vault_span_y=1),
    "wide": Mesh3D(8, 8, 4),
}
N_SLOTS = {"tall": 8, "wide": 16}
# The program-vs-oracle meshes: the two above, the paper's mesh at 32
# slots (the widest busy vector), a one-column mesh and a single node.
ORACLE_MESHES = {
    "tall": (MESHES["tall"], N_SLOTS["tall"]),
    "wide": (MESHES["wide"], N_SLOTS["wide"]),
    "8x8x4/32": (Mesh3D(8, 8, 4), 32),
    "1x4x2/8": (Mesh3D(1, 4, 2), 8),
    "1x1x1/4": (Mesh3D(1, 1, 1, vault_span_y=1), 4),
}


def _stream(rng, mesh, n, *, contended=False, init_frac=0.0,
            extra_frac=0.0):
    """Random request stream; ``contended=True`` funnels endpoints through
    a single mesh column so within-wave conflicts actually happen."""
    reqs = []
    for _ in range(n):
        if contended:
            s = mesh.node_id(0, int(rng.integers(mesh.Y)), 0)
            d = mesh.node_id(mesh.X - 1, int(rng.integers(mesh.Y)),
                             int(rng.integers(mesh.Z)))
        else:
            s, d = (int(v) for v in rng.integers(mesh.n_nodes, size=2))
        while s == d:
            d = int(rng.integers(mesh.n_nodes))
        op = "init" if rng.random() < init_frac else "copy"
        extra = int(rng.integers(1, 3)) if (op == "copy" and
                                            rng.random() < extra_frac) else 0
        nbytes = int(rng.integers(1, 4096))
        reqs.append(CopyRequest(s, d if op == "copy" else s, nbytes,
                                max_extra_slots=extra, op=op))
    return reqs


def _circuit_key(c):
    if c is None:
        return None
    return (c.src, c.dst, c.start_cycle, c.n_windows, tuple(c.hops),
            c.slots_per_window, c.uses_bus, c.bus_column, c.distance)


def _assert_stream_identical(mesh, n_slots, reqs, *, wave=None,
                             cycle=0) -> TdmAllocator:
    """The batch under the fused backend == the serial allocate stream:
    circuits, final expiry table, commit/deny counts.  Returns the fused
    allocator (callers inspect its report)."""
    a_f = TdmAllocator(mesh, n_slots, backend="fused")
    a_s = TdmAllocator(mesh, n_slots)
    if wave is not None:
        a_f.search_wave = wave
        a_s.search_wave = wave
    res_f = a_f.allocate_batch(reqs, cycle=cycle)
    res_s = [a_s.allocate_batch([r], cycle=cycle)[0] for r in reqs]
    for i, (f, s) in enumerate(zip(res_f, res_s)):
        assert _circuit_key(f.circuit) == _circuit_key(s.circuit), (
            f"request {i} diverged: fused={f.circuit} serial={s.circuit}")
    np.testing.assert_array_equal(a_f.table.expiry, a_s.table.expiry)
    np.testing.assert_array_equal(a_f.table.bus_expiry, a_s.table.bus_expiry)
    rep = a_f.last_report
    n_committed = sum(r.circuit is not None for r in res_s)
    assert rep.n_committed == n_committed
    assert rep.n_denied == len(reqs) - n_committed
    return a_f


# --- program vs host oracle --------------------------------------------------
@pytest.mark.parametrize("mesh_pick", list(ORACLE_MESHES))
@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31), st.sampled_from([0.05, 0.3, 0.6, 1.0]))
def test_fused_prepare_matches_ref_oracle(mesh_pick, seed, density):
    """The compiled program's outputs are bit-identical to the numpy
    oracle on random occupancies, from nearly free to saturated, and
    random request rows: denials from saturated destinations included,
    and rows whose source is their destination (an INIT's shape)."""
    mesh, n = ORACLE_MESHES[mesh_pick]
    rng = np.random.default_rng(seed)
    bits = rng.random((mesh.n_nodes, 7, n)) < density
    occ = (bits * (np.uint64(1) << np.arange(n, dtype=np.uint64))) \
        .sum(-1).astype(np.uint32)
    B = 32
    srcs = rng.integers(mesh.n_nodes, size=B).astype(np.int64)
    dsts = rng.integers(mesh.n_nodes, size=B).astype(np.int64)
    same = rng.random(B) < 0.25
    dsts[same] = srcs[same]
    t_readys = rng.integers(3, 50, size=B).astype(np.int64)
    got = fused_mod.fused_prepare(occ, srcs, dsts, t_readys, mesh=mesh,
                                  n_slots=n)
    want = ref_mod.fused_prepare_ref(occ, srcs, dsts, t_readys, mesh=mesh,
                                     n_slots=n)
    np.testing.assert_array_equal(got.denied, want.denied)
    np.testing.assert_array_equal(got.dists, want.dists)
    np.testing.assert_array_equal(got.starts, want.starts)
    np.testing.assert_array_equal(got.arr[~got.denied], want.arr[~want.denied])
    np.testing.assert_array_equal(got.ok, want.ok)
    live = ~got.denied & got.ok
    np.testing.assert_array_equal(got.hop_n[live], want.hop_n[live])
    np.testing.assert_array_equal(got.hop_p[live], want.hop_p[live])
    np.testing.assert_array_equal(got.hop_s[live], want.hop_s[live])


def test_slot_score_ref_matches_jnp():
    rng = np.random.default_rng(7)
    n = 16
    avail = rng.integers(0, 2**n, size=24, dtype=np.uint32)
    dists = rng.integers(0, 12, size=24)
    t = rng.integers(0, 40, size=24)
    import jax.numpy as jnp
    got = np.asarray(fused_mod._score_jnp(
        jnp.asarray(avail), jnp.asarray(dists, jnp.int32),
        jnp.asarray(t, jnp.int32), n))
    np.testing.assert_array_equal(got, ref_mod.slot_score_ref(avail, dists,
                                                              t, n))


# --- pipeline vs serial: the property sweep ----------------------------------
@settings(max_examples=6, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 1), st.integers(0, 2))
def test_fused_batch_matches_serial_random(seed, mesh_pick, density):
    """Random streams over both topologies at three conflict densities
    (uniform, contended column, contended + op/extra mix)."""
    name = ("tall", "wide")[mesh_pick]
    mesh, n = MESHES[name], N_SLOTS[name]
    rng = np.random.default_rng(seed)
    if density == 0:
        reqs = _stream(rng, mesh, 96)
    elif density == 1:
        reqs = _stream(rng, mesh, 96, contended=True)
    else:
        reqs = _stream(rng, mesh, 96, contended=True, init_frac=0.2,
                       extra_frac=0.2)
    _assert_stream_identical(mesh, n, reqs)


@settings(max_examples=4, deadline=None)
@given(st.integers(0, 2**31), st.integers(0, 2))
def test_fused_batch_matches_serial_wave_sizes(seed, wave_pick):
    """Bit-identity holds for every search-wave size (the wave only
    changes snapshot staleness, which the conflict path must absorb)."""
    wave = (5, 16, 64)[wave_pick]
    mesh, n = MESHES["tall"], N_SLOTS["tall"]
    rng = np.random.default_rng(seed)
    reqs = _stream(rng, mesh, 40, contended=seed % 2 == 0)
    _assert_stream_identical(mesh, n, reqs, wave=wave)


def test_fused_conflicts_actually_exercised():
    """The contended sweep isn't vacuous: a contended wide-mesh stream
    drives stale-snapshot conflicts through the fused commit, and the
    result still matches serial."""
    mesh, n = MESHES["wide"], N_SLOTS["wide"]
    reqs = _stream(np.random.default_rng(1), mesh, 128, contended=True)
    a_f = _assert_stream_identical(mesh, n, reqs)
    assert a_f.last_report.conflicts > 0
    assert a_f.last_report.fused_waves > 0


def test_fused_denials_match_serial():
    """A saturated mesh denies the same requests under both paths."""
    mesh = MESHES["tall"]
    reqs = _stream(np.random.default_rng(5), mesh, 160, contended=True)
    a_f = _assert_stream_identical(mesh, 4, reqs)
    assert a_f.last_report.n_denied > 0


# --- telemetry ----------------------------------------------------------------
def test_backend_reports_agree_and_split_waves():
    """host and fused backends produce the same BatchReport (the wave
    structure is shared, only who serves each round differs) and the
    fused/host wave counters partition search_rounds."""
    mesh, n = MESHES["wide"], N_SLOTS["wide"]
    reqs = _stream(np.random.default_rng(11), mesh, 192)
    a_h = TdmAllocator(mesh, n, backend="host")
    a_f = TdmAllocator(mesh, n, backend="fused")
    a_h.allocate_batch(reqs, cycle=0)
    a_f.allocate_batch(reqs, cycle=0)
    rh, rf = a_h.last_report, a_f.last_report
    for field in ("n_requests", "n_committed", "n_denied", "search_rounds",
                  "conflicts", "n_searched"):
        assert getattr(rh, field) == getattr(rf, field), field
    assert rh.fused_waves == 0
    assert rh.host_waves == rh.search_rounds
    assert rf.fused_waves + rf.host_waves == rf.search_rounds
    assert rf.fused_waves >= 3                  # full waves went compiled
    np.testing.assert_array_equal(a_h.table.expiry, a_f.table.expiry)


def test_fabric_telemetry_carries_backend_split():
    from repro.core.fabric import NomFabric
    from repro.core.scheduler import TransferRequest
    mesh, n = MESHES["wide"], N_SLOTS["wide"]
    reqs = [TransferRequest(src=r.src, dst=r.dst, nbytes=r.nbytes)
            for r in _stream(np.random.default_rng(13), mesh, 128)]
    fab = NomFabric(mesh=mesh, n_slots=n, alloc_backend="auto")
    _res, rep = fab.schedule(reqs)
    assert rep.fused_waves + rep.host_waves == rep.search_rounds
    assert rep.fused_waves > 0
    tel = fab.telemetry()
    assert tel["fused_waves"] == rep.fused_waves
    assert tel["host_waves"] == rep.host_waves
    host = NomFabric(mesh=mesh, n_slots=n, alloc_backend="host")
    host.schedule(reqs)
    assert host.telemetry()["fused_waves"] == 0
    assert host.telemetry()["host_waves"] > 0


def test_allocator_rejects_unknown_backend():
    with pytest.raises(ValueError, match="backend"):
        TdmAllocator(MESHES["tall"], 8, backend="gpu")
