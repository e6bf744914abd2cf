"""95th percentile of every gap between consecutive output tokens of a
request, over every gap that ends in the window, timed where the client
receives the tokens (ms)."""
from bench import load


def read(run):
    gaps = [(t - r.times[j - 1]) * 1e3
            for r, j, t in run.tokens_between(run.w0, run.w1) if j > 0]
    return load.p95(gaps) if gaps else None
