"""Host time of the serving control plane per model step: the mean
duration of the program's ``engine.schedule_tick`` spans (one per model
step: the step's cache-movement batch through the fabric) in the traced
window.  Nothing to read in a trace without the program's spans."""


def read(run):
    if not getattr(run.trace, "program_spans", None):
        return None
    total, _, count = run.trace.span_ms("engine.schedule_tick")
    return total / count if count else None
