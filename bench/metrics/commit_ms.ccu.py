"""Host time of the allocator's commit per batch: the self time (children
left out, so the dispatch, the wait and the re-search are not counted) of
the program's ``nom.alloc.fused.commit``, ``nom.alloc.fused.emit`` and
``nom.alloc.wave`` spans in the traced window, over the batches the window
scheduled.  ``nom.alloc.wave``'s self time is a wave's state assembly and
its commit loop.  Nothing to read in a trace without the program's
spans."""

SPANS = ("nom.alloc.fused.commit", "nom.alloc.fused.emit",
         "nom.alloc.wave")


def read(run):
    if not getattr(run.trace, "program_spans", None):
        return None
    own = sum(run.trace.span_ms(name)[1] for name in SPANS)
    return own / run.counters["batches"]
