"""Scheduler: the host's own time per decode step of the traced window
(ms): each ``engine_step`` span less its ``logits_wait`` spans, the time
the host spent blocked on the device. Mostly time the chip waits through
(admission planning, sampling, booking tokens). Program spans, host
clock."""
from bench import engine_steps


def read(run):
    host = [(s["t1"] - s["t0"] - w) * 1e3
            for s, w in engine_steps.decode_steps(run) if w is not None]
    return sum(host) / len(host) if host else None
