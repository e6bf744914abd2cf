"""Model step: device time of each decode call of the traced window (the
decode program and any helper it dispatched), from the profiler trace
(ms)."""


def read(run):
    cs = run.whole_calls("decode_step")
    return sum(c.device_s for c in cs) / len(cs) * 1e3 if cs else None
