"""Share of its roofline the served DeepSeek-V2 decode step reaches: the
least time of a step, averaged over every step the traced window ran (the
larger of its FLOPs over peak FLOP/s and its bytes over peak HBM
bandwidth, counted from the configuration's shapes by
``bench/core/work_dsv2.py``, every held expert read once a step), over the
mean device time of the jitted ``_decode_one`` executions."""
from bench.core import work_dsv2


def read(run):
    seconds, count = run.trace.program("_decode_one")
    w = run.work
    if not count or not w["contexts"]:
        return None
    least = sum(work_dsv2.decode_least_seconds(w["config"], w["rows"], c,
                                               run.peaks)
                for c in w["contexts"])
    return 100.0 * (least / len(w["contexts"])) / (seconds / count)
