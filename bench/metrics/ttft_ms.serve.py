"""Time to first token: the median, over the requests of the traced
window, of the time from the start of a request's ``engine.generate``
span to the end, on the host's clock, of the device execution of the last
program launched inside its ``engine.prefill`` (the first token's
``argmax``), linked by run id.  The serve mix cycles through its shapes
in one fixed order, and a 5 s window holds the first four whole requests:
prompts of 18-40 tokens of the mix's 16-128.  ``ttft_per_token_ms.serve``
reads the same requests per prompt token.  Nothing to read in a trace
without the program's spans."""
import statistics


def read(run):
    if not getattr(run.trace, "program_spans", None):
        return None
    firsts = run.trace.first_tokens()
    if not firsts:
        return None
    return 1e3 * statistics.median(f["ttft_s"] for f in firsts)
