"""Time to first token per prompt token: the summed times to first token
of the traced window's requests (as ``ttft_ms.serve`` reads each) over
their summed prompt lengths (each ``engine.prefill``'s ``prompt_len``).
Unlike the median, it does not rest on which of the mix's prompt lengths
fit in the window.  Nothing to read in a trace without the program's
spans."""


def read(run):
    if not getattr(run.trace, "program_spans", None):
        return None
    firsts = [f for f in run.trace.first_tokens()
              if f["prompt_len"]]
    if not firsts:
        return None
    return 1e3 * sum(f["ttft_s"] for f in firsts) \
        / sum(f["prompt_len"] for f in firsts)
