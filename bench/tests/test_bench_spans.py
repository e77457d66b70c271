"""The program-span reduction (``bench/core/spans.py``) on small recorded
traces: span sums and self times, the device clock put on the host's, gaps
named by program spans, and every reading of ``bench/core/trace.py`` left
as it was."""
import pathlib

import pytest

from bench.core import harness, spans, trace

DATA = pathlib.Path(__file__).parent / "data"
SPAN_TRACE = DATA / "cpu_spans.xplane.pb"
ORIGINAL = [(DATA / "cpu_trace.xplane.pb", ("probe.step",)),
            (DATA / "tpu_trace.xplane.pb", ("probe.step",)),
            (SPAN_TRACE, ("probe.step",))]
# Research sleeps this long with no device operation running.
RESEARCH_S = 0.004
SPAN_METRICS = ("research_ms.ccu", "fused_wait_ms.ccu", "commit_ms.ccu",
                "control_plane_ms.serve", "ttft_ms.serve",
                "ttft_per_token_ms.serve")


def record(directory) -> None:
    """The recording of ``cpu_spans.xplane.pb``: three ``probe.step``
    calls inside ``bench.window``, each nesting ``nom.schedule`` >
    ``nom.alloc.batch`` > a jitted probe's launch in
    ``nom.alloc.fused.dispatch``, its wait in ``nom.alloc.fused.wait``,
    then a host-only ``nom.alloc.research`` (run with
    ``JAX_PLATFORMS=cpu python -m bench.tests.test_bench_spans <dir>``)."""
    import time

    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    @jax.jit
    def bench_probe(x):
        return (x @ x).sum()

    x = jnp.ones((256, 256))
    bench_probe(x).block_until_ready()
    with jax.profiler.trace(str(directory), profiler_options=trace.options()):
        with TraceAnnotation(trace.WINDOW_SPAN):
            for _ in range(3):
                with TraceAnnotation("probe.step"), \
                        TraceAnnotation("nom.schedule"), \
                        TraceAnnotation("nom.alloc.batch"):
                    with TraceAnnotation("nom.alloc.fused.dispatch"):
                        y = bench_probe(x)
                    with TraceAnnotation("nom.alloc.fused.wait"):
                        y.block_until_ready()
                    with TraceAnnotation("nom.alloc.research"):
                        time.sleep(RESEARCH_S)


def _reduced():
    return spans.reduce(str(SPAN_TRACE), span_names=("probe.step",))


def test_program_spans_are_read_with_their_counts_and_sums():
    r = _reduced()
    table = r.span_table()
    for name in ("probe.step", "nom.schedule", "nom.alloc.batch",
                 "nom.alloc.fused.dispatch", "nom.alloc.fused.wait",
                 "nom.alloc.research"):
        assert table[name][0] == 3, name
    assert {s.name for s in r.program_spans} == {
        "nom.schedule", "nom.alloc.batch", "nom.alloc.fused.dispatch",
        "nom.alloc.fused.wait", "nom.alloc.research"}
    total, own, n = r.span_ms("nom.alloc.research")
    assert n == 3 and total >= 3 * RESEARCH_S * 1e3
    assert own == total           # a leaf: its self time is all of it
    # The spans nest: each level lies inside the one above it.
    assert r.span_ms("nom.alloc.batch")[0] <= r.span_ms("nom.schedule")[0] \
        <= r.span_ms("probe.step")[0] <= r.window_s * 1e3


def test_self_time_is_the_span_less_its_children_on_its_thread():
    r = _reduced()
    batches = r.in_window("nom.alloc.batch")
    for b in batches:
        kids = [s for s in r.host_spans if s.thread == b.thread
                and s is not b and b.start <= s.start and s.end <= b.end]
        assert {k.name for k in kids} == {"nom.alloc.fused.dispatch",
                                          "nom.alloc.fused.wait",
                                          "nom.alloc.research"}
        assert b.self_ns == pytest.approx(
            (b.end - b.start) - sum(k.end - k.start for k in kids))
        assert 0 <= b.self_ns < b.end - b.start
    # The self times of the spans inside the calls add up to the calls.
    step_ms = r.span_ms("probe.step")[0]
    inside = sum(r.span_ms(name)[1] for name in r.span_table()
                 if name != trace.WINDOW_SPAN)
    assert inside == pytest.approx(step_ms, rel=1e-9)


def test_self_times_of_a_hand_made_tree():
    t = (0, 0)
    a = spans.Span("a", 0, 100, t, {})
    b = spans.Span("b", 10, 40, t, {})
    c = spans.Span("c", 15, 25, t, {})
    d = spans.Span("d", 50, 90, t, {})
    e = spans.Span("e", 20, 30, (0, 1), {})      # another thread
    spans._self_times([d, c, e, a, b])
    assert [s.self_ns for s in (a, b, c, d, e)] == [30, 20, 10, 40, 10]


def test_gaps_are_named_by_the_innermost_program_span():
    r = _reduced()
    gaps = r.idle_gaps()
    assert r.shifts == [0.0]
    # The three sleeps are the longest gaps; nothing runs on the device
    # while the host re-searches.
    assert [name for name, _ in gaps[:3]] == ["nom.alloc.research"] * 3
    assert all(s >= RESEARCH_S for _, s in gaps[:3])
    names = {name for name, _ in gaps}
    assert names <= {"nom.alloc.research", "nom.alloc.fused.wait",
                     "nom.alloc.fused.dispatch", "nom.alloc.batch",
                     "nom.schedule", "probe.step", trace.BETWEEN_CALLS}


def test_cpu_launches_link_each_execution_by_run_id():
    r = _reduced()
    probes = [e for e in r.executions[0] if e.launch is not None]
    assert len(probes) >= 3
    for e in probes:
        assert e.launch <= e.end


def test_tpu_shift_puts_each_execution_between_enqueue_and_callback():
    r = spans.reduce(str(DATA / "tpu_trace.xplane.pb"),
                     span_names=("probe.step",))
    (shift,) = r.shifts
    # Bounded by the trace itself: every execution starts after its
    # enqueue (runs 4-6: the latest lead is 1.549 ms) and ends before its
    # completion callback (the earliest 2.049 ms after it).
    assert 1.549e6 <= shift <= 2.049e6
    (bounds,) = r.shift_bounds_ms()
    assert bounds["shift_ms"] == bounds["lower_ms"] == shift * 1e-6
    assert bounds["upper_ms"] == pytest.approx(2.049442)
    assert bounds["refused"] == 0
    runs = r.executions[0]
    assert [e.run_id for e in runs] == [4, 5, 6]
    for e in runs:
        assert e.launch <= e.start + shift
        assert e.end + shift <= e.callback
    # On the shifted clock, the window's one busy stretch is the third
    # call's execution, inside its ``probe.step``.
    busy = r.aligned_busy(0)
    steps = r.in_window("probe.step")
    assert busy and all(any(s.start <= a and b <= s.end for s in steps)
                        for a, b in busy)


def test_one_stray_pairing_does_not_set_the_shift():
    # Four executions agree on a shift in 100-150; one stray launch leads
    # its execution by 400, past every callback's tail.
    runs = [spans.Execution(i, 1000 * i, 1000 * i + 200,
                            1000 * i + lead, 1000 * i + 350)
            for i, lead in enumerate((80, 100, 90, 400, 60))]
    r = spans.Traced(window=(0, 10000), devices=[], spans=[],
                     host_spans=[], executions=[runs],
                     shifts=[spans._shift(runs)])
    assert r.shifts == [100]
    (bounds,) = r.shift_bounds_ms()
    assert bounds == pytest.approx({"shift_ms": 1e-4, "lower_ms": 4e-4,
                                    "upper_ms": 1.5e-4, "refused": 1})
    # Without the stray pairing the bounds meet, and the shift is the
    # largest lead.
    del runs[3]
    assert spans._shift(runs) == 100
    assert spans._shift([]) == 0.0


@pytest.mark.parametrize("path,names", ORIGINAL,
                         ids=[p.name for p, _ in ORIGINAL])
def test_every_existing_reading_is_unchanged(path, names):
    old = trace.reduce(str(path), span_names=names)
    new = spans.reduce(str(path), span_names=names)
    assert new.window == old.window and new.spans == old.spans
    assert new.busy_s == old.busy_s
    assert new.idle_shares() == old.idle_shares()
    assert new.window_s == old.window_s
    assert new.collective_s() == old.collective_s()
    assert new.top_ops() == old.top_ops()
    for jit_name in ("bench_probe", "_decode_one", "fused_prepare_program"):
        assert new.program(jit_name) == old.program(jit_name)


def test_first_tokens_reads_the_last_prefill_launch():
    # A hand-made request: the generate span opens at 0, its prefill
    # launches runs 1 and 2 (two ticks), the device ends run 2 at 900 on
    # its own clock, 100 behind the host's.
    t = (0, 0)
    g = spans.Span("engine.generate", 0, 5000, t, {"tenant": "r0"})
    p = spans.Span("engine.prefill", 10, 1500, t,
                   {"tenant": "r0", "prompt_len": 2})
    ticks = [spans.Span("engine.schedule_tick", 200 + 300 * i,
                        300 + 300 * i, t, {}) for i in range(2)]
    runs = [spans.Execution(1, 150, 500, 100, None),
            spans.Execution(2, 600, 900, 700, None),
            spans.Execution(3, 1800, 2000, 1600, None)]
    dev = trace.Device("/device:TPU:0", [], [])
    r = spans.Traced(window=(0, 10000), devices=[dev], spans=[],
                     host_spans=[g, p] + ticks, executions=[runs],
                     shifts=[100.0])
    (first,) = r.first_tokens()
    assert first == {"tenant": "r0", "prompt_len": 2,
                     "ttft_s": pytest.approx(1000e-9)}
    ctx = _context(r)
    assert harness.load_reader("ttft_ms.serve")(ctx) == pytest.approx(1e-3)
    assert harness.load_reader("ttft_per_token_ms.serve")(ctx) == \
        pytest.approx(5e-4)


def _context(reduced):
    return harness.ReadContext(trace=reduced, counters={"batches": 3},
                               work={}, window=None,
                               device_kind="cpu", chips=1)


@pytest.mark.parametrize("metric", SPAN_METRICS)
def test_span_metrics_read_nothing_without_program_spans(metric):
    # The benchmark's own reduction carries no program span: each reader
    # returns None rather than raising.
    plain = trace.reduce(str(SPAN_TRACE), span_names=("probe.step",))
    assert harness.load_reader(metric)(_context(plain)) is None
    # Nor does a trace recorded before the program had spans.
    old = spans.reduce(str(DATA / "cpu_trace.xplane.pb"),
                       span_names=("probe.step",))
    assert harness.load_reader(metric)(_context(old)) is None


def test_span_metrics_read_the_allocator_spans():
    r = _reduced()
    ctx = _context(r)
    read = {m: harness.load_reader(m)(ctx) for m in SPAN_METRICS}
    assert read["research_ms.ccu"] == pytest.approx(
        r.span_ms("nom.alloc.research")[0] / 3)
    assert read["research_ms.ccu"] >= RESEARCH_S * 1e3
    assert read["fused_wait_ms.ccu"] == pytest.approx(
        r.span_ms("nom.alloc.fused.wait")[0] / 3)
    assert read["commit_ms.ccu"] == 0.0          # no commit span recorded
    assert read["control_plane_ms.serve"] is None
    assert read["ttft_ms.serve"] is None
    assert read["ttft_per_token_ms.serve"] is None


if __name__ == "__main__":
    import sys
    record(sys.argv[1])
