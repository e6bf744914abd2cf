"""Device: share of the traced window in which no operation ran on the
chip, from the profiler trace (%)."""
from bench import trace_reduce


def read(run):
    if run.trace is None:
        return None
    lo, hi = run.trace_lo, run.trace_hi
    return 100.0 * (1.0 - trace_reduce.busy_seconds(run.trace, lo, hi)
                    / (hi - lo))
