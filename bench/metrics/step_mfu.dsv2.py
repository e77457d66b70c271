"""Model FLOP/s utilization of the whole served DeepSeek-V2 step: the
model FLOPs of every step the traced window ran (``work_dsv2``: the held
experts at the share of routed pairs an even router sends here), over the
window's seconds, over the chip's peak bf16 FLOP/s."""
from bench.core import work_dsv2


def read(run):
    w = run.work
    if not w["contexts"]:
        return None
    flops = sum(work_dsv2.decode_flops(w["config"], w["rows"], c)
                for c in w["contexts"])
    return 100.0 * flops / run.trace.window_s / run.peaks["bf16_flops"]
