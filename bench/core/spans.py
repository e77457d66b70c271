"""The program's own host spans, and each device's clock put on the host's,
over the reduction of :mod:`bench.core.trace`.

The program opens ``jax.profiler.TraceAnnotation`` spans at its layer
boundaries: ``nom.*`` in the fabric session and its allocator, ``engine.*``
in the serving ``Engine`` (listed in ``docs/fabric.md`` and
``docs/serving.md``).  :func:`reduce` reads them beside the benchmark's
spans, each with the trace line (thread) it was recorded on, so that a
span's self time, its duration less that of its children on the same
thread, can be read.

A TPU trace's device timeline is not the host's: on the recorded one each
execution starts about 1.5 ms before the host enqueued it.  Each device
gets the shift that puts its executions on the host clock: the smallest
under which every execution starts no earlier than the host's launch of
the same ``run_id`` (``DoEnqueueProgram``) and ends no later than its
completion callback (``CompleteCallbacks``).  Where no shift meets every
pairing (the largest lead of a launch exceeds the least tail of a
callback), the shift is the smallest that the fewest pairings refuse, so
that one stray pairing cannot set it, and :meth:`Traced.shift_bounds_ms`
reports both bounds and the pairings refused.  A CPU trace runs its
operations on host threads and keeps shift 0.  The idle gaps are found on
the shifted timeline and named by the innermost span open at their
midpoint, the program's spans included; every other reading of
:class:`bench.core.trace.Reduced` keeps the unshifted timeline and reads
what it read before.
"""
from __future__ import annotations

import bisect
import dataclasses

from bench.core import trace

PROGRAM_PREFIXES = ("nom.", "engine.")
# Host events that launch one execution of a compiled program, with its
# run id: the TPU runtime's enqueue, the CPU client's execute.
LAUNCHES = ("DoEnqueueProgram", "PjRtCpuExecutable::ExecuteHelper")
CALLBACK = "CompleteCallbacks"
GENERATE, PREFILL = "engine.generate", "engine.prefill"


@dataclasses.dataclass
class Span:
    name: str
    start: float            # ns, host clock
    end: float
    thread: tuple           # (plane, line) the span was recorded on
    args: dict              # the span's keyword arguments (``tenant``)
    self_ns: float = 0.0    # the duration less its children's


@dataclasses.dataclass
class Execution:
    run_id: int
    start: float            # ns, the device's own clock
    end: float
    launch: float | None    # the host launch of the same run id
    callback: float | None  # the host's completion callback (TPU)


@dataclasses.dataclass
class Traced(trace.Reduced):
    host_spans: list = dataclasses.field(default_factory=list)  # [Span]
    executions: list = dataclasses.field(default_factory=list)  # per device
    shifts: list = dataclasses.field(default_factory=list)  # ns a device

    @property
    def program_spans(self) -> list:
        return [s for s in self.host_spans
                if s.name.startswith(PROGRAM_PREFIXES)]

    def in_window(self, name: str) -> list:
        """The spans named ``name`` that lie wholly inside the window."""
        lo, hi = self.window
        return [s for s in self.host_spans
                if s.name == name and s.start >= lo and s.end <= hi]

    def span_ms(self, name: str) -> tuple[float, float, int]:
        """(total ms, self ms, count) of the spans ``name`` in the
        window."""
        spans = self.in_window(name)
        return (1e-6 * sum(s.end - s.start for s in spans),
                1e-6 * sum(s.self_ns for s in spans), len(spans))

    def span_table(self) -> dict:
        """Every span name of the window: [count, total ms, self ms]."""
        out = {}
        for name in sorted({s.name for s in self.host_spans}):
            total, own, n = self.span_ms(name)
            if n:
                out[name] = [n, total, own]
        return out

    def aligned_busy(self, i: int):
        """The busy intervals of device ``i`` on the host's clock."""
        lo, hi = self.window
        shift = self.shifts[i]
        return trace._merge(trace._clip(
            [(a + shift, b + shift) for _, a, b in self.devices[i].ops],
            lo, hi))

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest idle gaps of the first device on the host's
        clock, each named by the innermost span, the benchmark's or the
        program's, open at its midpoint."""
        lo, hi = self.window
        busy = self.aligned_busy(0)
        edges = [lo] + [x for ab in busy for x in ab] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) / 2
            inside = [(s.end - s.start, s.name) for s in self.host_spans
                      if s.name != trace.WINDOW_SPAN
                      and s.start <= mid < s.end]
            out.append([min(inside)[1] if inside else trace.BETWEEN_CALLS,
                        (b - a) * 1e-9])
        return out

    def first_tokens(self) -> list:
        """For each ``engine.generate`` of the window: its tenant, its
        prompt length (its ``engine.prefill``'s ``prompt_len``) and its
        time to first token in seconds, from the start of the request to
        the end, on the host's clock, of the first device's execution of
        the last program launched inside its prefill."""
        dev = self.executions[0]
        by_run = {e.run_id: e for e in dev}
        launches = sorted((e.launch, e.run_id) for e in dev
                          if e.launch is not None)
        out = []
        for g in self.in_window(GENERATE):
            prefill = [s for s in self.host_spans if s.name == PREFILL
                       and s.thread == g.thread
                       and g.start <= s.start and s.end <= g.end]
            if not prefill:
                continue
            p = prefill[0]
            inside = [run for t, run in launches if p.start <= t <= p.end]
            if not inside:
                continue
            end = by_run[inside[-1]].end + self.shifts[0]
            plen = p.args.get("prompt_len")
            out.append({"tenant": g.args.get("tenant"),
                        "prompt_len": None if plen is None else int(plen),
                        "ttft_s": (end - g.start) * 1e-9})
        return out

    def shift_bounds_ms(self) -> list:
        """Per device: the shift, the least shift that every launch allows
        (the largest lead of a launch over its execution's start), the
        largest that every completion callback allows (the least tail of a
        callback after its execution's end), in ms (None where no pairing
        gives one), and the pairings the shift refuses (0 unless the two
        bounds cross)."""
        out = []
        for shift, execs in zip(self.shifts, self.executions):
            leads, tails = _bounds(execs)
            out.append({
                "shift_ms": 1e-6 * shift,
                "lower_ms": 1e-6 * max(leads) if leads else None,
                "upper_ms": 1e-6 * min(tails) if tails else None,
                "refused": sum(x > shift for x in leads)
                + sum(x < shift for x in tails)})
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps(),
                "shift_ms": self.shift_bounds_ms()}


def _self_times(spans: list) -> None:
    """Fill each span's ``self_ns``: its duration less its direct
    children's on the same thread."""
    by_thread: dict = {}
    for s in spans:
        by_thread.setdefault(s.thread, []).append(s)
    for group in by_thread.values():
        group.sort(key=lambda s: (s.start, -s.end))
        stack: list = []
        for s in group:
            s.self_ns = s.end - s.start
            while stack and stack[-1].end <= s.start:
                stack.pop()
            if stack and s.end <= stack[-1].end:
                stack[-1].self_ns -= s.end - s.start
            stack.append(s)


def _bounds(execs: list) -> tuple[list, list]:
    """Each pairing's bound on the shift: the lead of a launch over its
    execution's start (the shift is at least that), and the tail of a
    completion callback after its execution's end (at most that)."""
    return ([e.launch - e.start for e in execs if e.launch is not None],
            [e.callback - e.end for e in execs if e.callback is not None])


def _shift(execs: list) -> float:
    """The smallest shift that the fewest pairings refuse: the largest
    lead when it lies within every callback's tail, else the lead at which
    the leads above it and the tails below it are fewest (0 when no
    execution has a launch)."""
    leads, tails = _bounds(execs)
    if not leads:
        return 0.0
    leads.sort()
    tails.sort()
    return min(leads, key=lambda s: (
        len(leads) - bisect.bisect_right(leads, s)
        + bisect.bisect_left(tails, s), s))


def reduce(path: str, n_devices: int | None = None,
           span_names: tuple = ()) -> Traced:
    """Read ``path`` as :func:`bench.core.trace.reduce` does, and add the
    host spans with their threads (the benchmark's ``span_names``, the
    window's and every program span), the device executions with their
    host launches, and each device's shift onto the host's clock."""
    from jax.profiler import ProfileData
    base = trace.reduce(path, n_devices=n_devices, span_names=span_names)
    on_cpu = base.devices[0].name == "cpu"
    pd = ProfileData.from_file(path)
    host, launches, callbacks = [], {}, {}
    device_runs: dict = {}      # device name -> {run_id: [start, end]}
    for pi, plane in enumerate(pd.planes):
        if plane.name.startswith("/device:") and not on_cpu:
            runs = device_runs.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name != "XLA Modules":
                    continue
                for e in line.events:
                    rid = trace._stats(e).get("run_id")
                    runs[rid] = [e.start_ns, e.start_ns + e.duration_ns]
            continue
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for e in line.events:
                name = e.name
                end = e.start_ns + e.duration_ns
                if name == trace.WINDOW_SPAN or name in span_names \
                        or name.startswith(PROGRAM_PREFIXES):
                    args = (trace._stats(e)
                            if name.startswith(PROGRAM_PREFIXES) else {})
                    host.append(Span(name, e.start_ns, end, (pi, li), args))
                elif name in LAUNCHES or name == CALLBACK:
                    st = trace._stats(e)
                    key = (st.get("device_ordinal"), st.get("run_id"))
                    (callbacks if name == CALLBACK else launches)[key] = \
                        e.start_ns
                elif on_cpu:
                    st = trace._stats(e)
                    if "hlo_op" in st and e.duration_ns > 0:
                        run = device_runs.setdefault("cpu", {}).setdefault(
                            st.get("run_id"), [e.start_ns, end])
                        run[0] = min(run[0], e.start_ns)
                        run[1] = max(run[1], end)
    _self_times(host)
    executions = []
    for d in base.devices:
        ordinal = None if on_cpu else trace._device_index(d.name)
        execs = []
        for rid, (a, b) in sorted(device_runs.get(d.name, {}).items(),
                                  key=lambda kv: kv[1][0]):
            launch = launches.get((ordinal, rid), launches.get((None, rid)))
            execs.append(Execution(rid, a, b, launch,
                                   callbacks.get((ordinal, rid))))
        executions.append(execs)
    return Traced(window=base.window, devices=base.devices, spans=base.spans,
                  host_spans=host, executions=executions,
                  shifts=[0.0 if on_cpu else _shift(x) for x in executions])
