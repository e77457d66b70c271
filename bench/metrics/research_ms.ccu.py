"""Host time of the conflict re-search per batch: the summed durations of
the program's ``nom.alloc.research`` spans (one per conflict) in the traced
window, over the batches the window scheduled.  Nothing to read in a trace
without the program's spans."""


def read(run):
    if not getattr(run.trace, "program_spans", None):
        return None
    total, _, _ = run.trace.span_ms("nom.alloc.research")
    return total / run.counters["batches"]
