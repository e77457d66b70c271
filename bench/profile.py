#!/usr/bin/env python3
"""Run one cell once with the profiler on, and read the program's own spans.

    python3 bench/profile.py --workload <name> --seed <n>

The cell is set up as ``bench/run.py`` sets it up, and its window is
traced as a ``--trace 1`` run traces it (``TRACE_SECONDS``), but
the trace is reduced by ``bench/core/spans.py``.  The last line of
standard output is one JSON object: ``metrics``, the cell's per-layer
metrics and those of ``bench/span_metrics.json`` that name the cell (each
read by its file in ``bench/metrics/``); ``counters``; ``spans``, every
span name of the window with its count, total and self milliseconds;
``breakdown``, the costliest device operations, the idle gaps on the
host's clock named by the innermost span, and each device's clock shift
with its two bounds; and for a serving cell ``first_tokens``, each
request's tenant, prompt length and time to first token.  The trace is
kept under ``bench_out/profile/``.  Nothing is compared with the
reference here: ``bench/run.py`` decides ``correct``.
"""
import argparse
import json
import os
import shutil
import sys
import time

T_PROCESS = time.perf_counter()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.core import harness, spans, trace  # noqa: E402

OUT = harness.ROOT / "bench_out" / "profile"
SPAN_METRICS = harness.ROOT / "bench" / "span_metrics.json"


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None) -> int:
    import jax
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    try:
        devices = harness.tpu_devices(cell.chips)
    except harness.NoChip as e:
        _log(str(e))
        return 2
    harness.enable_cache()
    system = harness.load_system(cell.config["kind"])(cell, args.seed,
                                                      devices, log=_log)
    system.setup()
    _log(f"setup: {time.perf_counter() - T_PROCESS!r} s")
    shutil.rmtree(OUT, ignore_errors=True)
    system.mark()
    with jax.profiler.trace(str(OUT), profiler_options=trace.options()):
        with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
            win = harness.measure(system, harness.TRACE_SECONDS,
                                  system.span)
    _log(f"window: {win.units} x {system.unit} in {win.seconds!r} s")
    counters = system.counters(win)
    reduced = spans.reduce(trace.find_xplane(OUT), n_devices=len(devices),
                           span_names=(system.span,))
    device = harness.device_info(devices)
    ctx = harness.ReadContext(trace=reduced, counters=counters,
                              work=system.work(win), window=win,
                              device_kind=device["kind"], chips=len(devices))
    extra = json.loads(SPAN_METRICS.read_text())
    metrics = {}
    for m in cell.per_layer + [m for m in extra
                               if cell.name in m["workloads"]]:
        value = harness.load_reader(m["name"], cell.root)(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"metrics": metrics, "counters": counters,
            "window": {"units": win.units, "seconds": win.seconds,
                       "busy_s": reduced.busy_s,
                       "window_s": reduced.window_s},
            "spans": reduced.span_table(), "breakdown": reduced.breakdown()}
    firsts = reduced.first_tokens()
    if firsts:
        line["first_tokens"] = firsts
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
