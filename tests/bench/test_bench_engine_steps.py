"""The readers of the engine's own spans (``engine_host_ms``,
``engine_step_p95_ms``) on hand-made spans of a traced run."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import harness  # noqa: E402


def span(name, t0, t1, **args):
    return {"name": name, "t0": t0, "t1": t1, "args": args}


def step(i, t0, t1, waits=(), decode=True):
    """An engine step over [t0, t1] that ran a decode step (or not),
    with a ``logits_wait`` child of each length in ``waits``."""
    out = [span("engine_step", t0, t1, step=i)]
    if decode:
        out.append(span("decode_step", t0, t0 + 0.001, step=i, live=2))
    at = t0 + 0.001
    for w in waits:
        out.append(span("logits_wait", at, at + w, step=i))
        at += w
    return out


def run(spans, t0=10.0, t1=11.0):
    return harness.Run(seconds=51.0, w0=0.0, w1=51.0, setup_s=1.0,
                       records=[], batch=32, work={}, peaks={},
                       t0=t0, t1=t1, spans=spans)


def read(name, r):
    return harness.read_metric(REPO, name)(r)


def window_spans():
    return (step(0, 9.95, 10.05, [0.07])           # cut by the window start
            + step(1, 10.0, 10.1, [0.02, 0.06])    # admission + decode waits
            + [span("sample", 10.081, 10.09, step=1)]
            + step(2, 10.2, 10.25, [0.04])
            + step(3, 10.3, 10.31, decode=False)   # nothing to decode
            + [span("intake", 10.31, 10.32)]
            + step(4, 10.95, 11.05, [0.09]))       # cut by the window end


def test_host_time_is_each_step_less_its_waits():
    assert read("engine_host_ms", run(window_spans())) == pytest.approx(
        ((100 - 80) + (50 - 40)) / 2)


def test_step_tail_is_the_p95_of_the_decode_steps():
    # numpy's linear p95 of 100 and 50 ms
    assert read("engine_step_p95_ms", run(window_spans())) == \
        pytest.approx(50 + 0.95 * 50)


@pytest.mark.parametrize("name", ["engine_host_ms", "engine_step_p95_ms"])
def test_nothing_to_read(name):
    assert read(name, run([], t0=0.0, t1=0.0)) is None      # untraced
    idle = step(1, 10.1, 10.2, decode=False) + step(2, 10.3, 10.4,
                                                    decode=False)
    assert read(name, run(idle)) is None                    # no decode step
    cut = step(1, 9.9, 10.2, [0.1]) + step(2, 10.9, 11.1, [0.1])
    assert read(name, run(cut)) is None                     # all cut


def test_a_program_without_wait_spans_reads_no_host_time():
    spans = step(1, 10.0, 10.1) + step(2, 10.2, 10.3)
    assert read("engine_host_ms", run(spans)) is None
    assert read("engine_step_p95_ms", run(spans)) == pytest.approx(100.0)
