"""Model step: device time of each prefill call of the traced window (the
prefill program and the cache scatter beside it), from the profiler
trace (ms). Reported in batch cells, where it moves output tokens per
second."""


def read(run):
    cs = run.whole_calls("prefill")
    return sum(c.device_s for c in cs) / len(cs) * 1e3 if cs else None
