"""Host time blocked on the fused prepare program's outputs per batch: the
summed durations of the program's ``nom.alloc.fused.wait`` spans in the
traced window, over the batches the window scheduled.  Nothing to read in
a trace without the program's spans."""


def read(run):
    if not getattr(run.trace, "program_spans", None):
        return None
    total, _, count = run.trace.span_ms("nom.alloc.fused.wait")
    return total / run.counters["batches"] if count else None
