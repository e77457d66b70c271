"""Device time of one served DeepSeek-V2 decode step: the device
durations of the jitted ``_decode_one`` executions in the traced window,
over their count."""


def read(run):
    seconds, count = run.trace.program("_decode_one")
    return 1e3 * seconds / count if count else None
