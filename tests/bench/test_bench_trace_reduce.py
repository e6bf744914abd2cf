"""bench/trace_reduce.py on hand-made events, and on a trace excerpt
recorded on the chip (``bench/testdata/``) against numbers read from it
by hand."""
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import trace_reduce as tr  # noqa: E402
from bench.trace_reduce import Event, Trace  # noqa: E402

DEV = "/device:TPU:0"


def ev(name, start, dur, **stats):
    return Event(name, start, dur, stats)


def made():
    """Host: mark 0, decode span [1,2), prefill span [5,6), mark 12.
    Device: decode module [2,4) (ops [2,3) and [3.5,4)), prefill module
    [6,9) (one op), a small helper module [9,9.5) dispatched after the
    prefill, then decode again [10,11)."""
    host = [ev(tr.MARK, 0, 0), ev("engine_step", 0.5, 4),
            ev("decode_step", 1, 1), ev("prefill", 5, 1),
            ev("decode_step", 9.6, 0.2), ev(tr.MARK, 12, 0)]
    mods = [ev("jit__lambda", 2, 2, program_id=1),
            ev("jit__lambda", 6, 3, program_id=2),
            ev("jit_scatter", 9, 0.5, program_id=3),
            ev("jit__lambda", 10, 1, program_id=1)]
    ops = [ev("fusion.1", 2, 1), ev("fusion.2", 3.5, 0.5),
           ev("convolution.3", 6, 3), ev("scatter.4", 9, 0.5),
           ev("fusion.1", 10, 1)]
    return Trace(host, {DEV: mods}, {DEV: ops})


def test_window_busy_and_gaps():
    t = made()
    lo, hi = tr.window(t)
    assert (lo, hi) == (0, 12)
    assert tr.busy(t, lo, hi)[DEV] == [(2, 3), (3.5, 4), (6, 9.5), (10, 11)]
    assert tr.busy_seconds(t, lo, hi) == pytest.approx(6.0)
    assert tr.gaps(tr.busy(t, lo, hi)[DEV], lo, hi) == [
        (0, 2), (3, 3.5), (4, 6), (9.5, 10), (11, 12)]


def test_idle_gaps_are_labelled_by_the_open_host_span():
    t = made()
    g = tr.idle_gaps(t, 0, 12, {"engine_step", "decode_step", "prefill"},
                     n=3)
    # longest first: [0,2) mid 1 -> decode_step (inside engine_step);
    # [4,6) mid 5 -> prefill; [11,12) mid 11.5 -> no span
    assert g == [("decode_step", 2), ("prefill", 2), ("no_span", 1)]


def test_role_times_count_only_each_roles_own_executable():
    t = made()
    rt = tr.role_times(t, 0, 12)
    # decode: busy ops inside its two runs = 1 + 0.5 and 1
    assert rt["decode_step"] == (pytest.approx(2.5), 2)
    # prefill: one call, its run of 3 s and the 0.5 s scatter it
    # dispatched; the engine_step span is no role
    assert rt["prefill"] == (pytest.approx(3.5), 1)
    mods = tr.module_times(tr.calls(t, 0, 12))
    assert mods == {"decode_step": {"jit__lambda": pytest.approx(2.5)},
                    "prefill": {"jit__lambda": pytest.approx(3.0),
                                "jit_scatter": pytest.approx(0.5)}}


def test_calls_share_in_the_window():
    t = made()
    cs = tr.calls(t, 3, 10.5)
    # decode [2,4) is half in (ops [3,3.5) idle), prefill and scatter
    # wholly, the last decode half
    assert [(c.role, c.first) for c in cs] == [
        ("decode_step", 2), ("prefill", 6), ("decode_step", 10)]
    assert [c.in_window_s for c in cs] == [pytest.approx(0.5),
                                           pytest.approx(3.5),
                                           pytest.approx(0.5)]
    assert [c.device_s for c in cs] == [pytest.approx(1.5),
                                        pytest.approx(3.5),
                                        pytest.approx(1.0)]
    assert tr.role_times(t, 3, 10.5)["decode_step"] == (pytest.approx(1.0),
                                                        1)


def test_top_ops():
    t = made()
    assert tr.top_ops(t, 0, 12, n=2) == [("convolution.3", 3),
                                         ("fusion.1", 2)]


# An excerpt (300 ms) of a trace recorded on one TPU v5e in the
# smollm-360m-dense.batch cell (its host annotations, enqueue events and
# device modules and operations in 300 ms, as a text-format XSpace).
# Read by hand from the file: the decode executable
# (jit__lambda(11040352332900818740)) runs three times, for 48.838415,
# 48.937553 and 48.835458 ms, the last starting 280.534275 ms in and so
# running past the excerpt's end at 300 ms; one prefill run
# (jit__lambda(2992976870799253333)) of 122.555043 ms, dispatched in a
# `prefill` span with bucket=256 and n=1; then jit_scatter_rows for
# 20.088541 ms, enqueued from the same span. Each decode call also
# dispatches a jit_dynamic_slice of about 0.02 ms.
EXCERPT = os.path.join(REPO, "bench", "testdata",
                       "dense_batch_decode_prefill.pbtxt.gz")
LO, HI = 0.66, 0.96


@pytest.fixture(scope="module")
def chip():
    return tr.read(EXCERPT)


def test_excerpt_roles_match_the_module_durations(chip):
    rt = tr.role_times(chip, LO, HI)
    dec_s, dec_n = rt["decode_step"]
    pre_s, pre_n = rt["prefill"]
    assert dec_n == 3 and pre_n == 1
    # device time inside a run is the union of its operations: at most
    # the module's duration, and nearly all of it
    modules = (48.838415 + 48.937553 + 48.835458) * 1e-3
    assert 0.995 * modules <= dec_s <= modules + 3 * 0.03e-3
    # the prefill call: its program and the cache scatter it dispatched
    prefill = (122.555043 + 20.088541) * 1e-3
    assert 0.995 * prefill <= pre_s <= prefill + 0.1e-3
    calls = tr.calls(chip, LO, HI)
    assert [c.span.stats.get("live") for c in calls
            if c.role == "decode_step"] == [32, 32, 32]
    mods = tr.module_times(calls)["prefill"]
    assert mods["jit_scatter_rows"] == pytest.approx(20.088541e-3,
                                                     rel=2e-3)


def test_excerpt_busy_time_and_spans(chip):
    # the big runs inside the window: two whole decodes, the last one's
    # 300 - 280.534275 ms, the prefill and the scatter
    big = (48.838415 + 48.937553 + (300 - 280.534275) + 122.555043
           + 20.088541) * 1e-3
    busy = tr.busy_seconds(chip, LO, HI)
    assert 0.99 * big <= busy <= HI - LO
    pre = [e for e in chip.host if e.name == "prefill"]
    assert [(e.stats["bucket"], e.stats["n"]) for e in pre] == [(256, 1)]
    gaps = tr.idle_gaps(chip, LO, HI, {"engine_step", "admit",
                                       "decode_step", "prefill"})
    assert len(gaps) == 10
    assert sum(s for _, s in gaps) <= HI - LO - busy + 1e-9
    ops = tr.top_ops(chip, LO, HI)
    assert len(ops) == 10 and all(len(n) <= tr.NAME_CHARS for n, _ in ops)
