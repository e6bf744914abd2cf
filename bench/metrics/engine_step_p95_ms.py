"""Scheduler: 95th percentile of the ``engine_step`` spans of the decode
steps of the traced window (ms): the engine's side of a gap between
tokens, an admitting step's prefill included. Program spans, host
clock."""
from bench import engine_steps, load


def read(run):
    d = [(s["t1"] - s["t0"]) * 1e3
         for s, _ in engine_steps.decode_steps(run)]
    return load.p95(d) if d else None
