"""Reduce a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read: device busy intervals, time per executable role, the
operations that took most time, and idle gaps labelled by what the host
was doing.

A device plane (``/device:TPU:n``) carries the lines ``XLA Modules`` (one
event per executable run, with its ``run_id``) and ``XLA Ops`` (one per
operation). On the host plane, each Python thread has a line (``python``
or ``python3``) holding the ``jax.profiler.TraceAnnotation`` events the
benchmark's tracer opens for each of the program's spans; the runtime's
``DoEnqueueProgram`` events carry the ``run_id`` of the program they put
on the device queue, on the host clock.

Executables of the serving path are compiled from anonymous functions,
so their module names do not say what they are. A run belongs to the
last role span started before the run was enqueued (or, without an
enqueue event, before it started): a call of that span's role, with
every executable it dispatched, helpers included.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]

ROLE_SPANS = ("decode_step", "prefill")
MARK = "bench_trace_mark"
NAME_CHARS = 120


@dataclasses.dataclass
class Event:
    name: str
    start: float             # seconds on the trace's clock
    dur: float
    stats: Dict[str, object]

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    host: List[Event]                 # host annotations of Python threads
    modules: Dict[str, List[Event]]   # device plane -> executable runs
    ops: Dict[str, List[Event]]       # device plane -> operations
    enqueued: Dict[object, float] = dataclasses.field(
        default_factory=dict)         # run_id -> host time it was enqueued


def _events(line) -> List[Event]:
    out = []
    for e in line.events:
        out.append(Event(e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9,
                         {k: v for k, v in e.stats}))
    return out


def read_xspace(pd) -> Trace:
    """A ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    host: List[Event] = []
    modules: Dict[str, List[Event]] = {}
    ops: Dict[str, List[Event]] = {}
    enqueued: Dict[object, float] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                if line.name.startswith("python"):
                    host.extend(_events(line))
                    continue
                for e in line.events:
                    if e.name == "DoEnqueueProgram":
                        st = dict(e.stats)
                        if "run_id" in st:
                            enqueued[st["run_id"]] = e.start_ns * 1e-9
        elif plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules[plane.name] = _events(line)
                elif line.name == "XLA Ops":
                    ops[plane.name] = _events(line)
    host.sort(key=lambda e: e.start)
    return Trace(host, modules, ops, enqueued)


def read(path: str) -> Trace:
    """An ``.xplane.pb``, or a text-format XSpace (``.pbtxt``, gzipped
    ``.pbtxt.gz``), as the test excerpt under ``bench/testdata/`` is."""
    import gzip

    from jax.profiler import ProfileData
    if path.endswith(".pbtxt.gz"):
        with gzip.open(path, "rt") as f:
            return read_xspace(ProfileData.from_text_proto(f.read()))
    if path.endswith(".pbtxt"):
        with open(path) as f:
            return read_xspace(ProfileData.from_text_proto(f.read()))
    return read_xspace(ProfileData.from_file(path))


def window(tr: Trace) -> Interval:
    """The traced window: from the first to the last benchmark mark."""
    marks = [e.start for e in tr.host if e.name == MARK]
    if len(marks) < 2:
        raise ValueError("trace holds fewer than two window marks")
    return min(marks), max(marks)


def _clip(evs: Iterable[Event], lo: float, hi: float) -> List[Interval]:
    out = []
    for e in evs:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append((s, t))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for s, t in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return [(s, t) for s, t in out]


def busy(tr: Trace, lo: float, hi: float) -> Dict[str, List[Interval]]:
    """Per device plane: the union of operation intervals in [lo, hi]
    (executable runs where a plane records no operations)."""
    planes = set(tr.ops) | set(tr.modules)
    return {p: union(_clip(tr.ops.get(p) or tr.modules.get(p, []), lo, hi))
            for p in sorted(planes)}


def busy_seconds(tr: Trace, lo: float, hi: float) -> float:
    """Seconds with an operation running, averaged over device planes."""
    per = busy(tr, lo, hi)
    if not per:
        return 0.0
    return sum(sum(t - s for s, t in iv) for iv in per.values()) / len(per)


def gaps(intervals: Sequence[Interval], lo: float,
         hi: float) -> List[Interval]:
    out, cur = [], lo
    for s, t in intervals:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, t)
    if hi > cur:
        out.append((cur, hi))
    return out


def host_label(tr: Trace, at: float, names: Iterable[str]) -> str:
    """The innermost (latest started) of the spans named ``names`` that
    is open at ``at``."""
    names = set(names)
    best: Optional[Event] = None
    for e in tr.host:
        if e.start > at:
            break
        if e.end >= at and e.name in names:
            best = e
    return best.name if best is not None else "no_span"


def idle_gaps(tr: Trace, lo: float, hi: float, names: Iterable[str],
              n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of the first device plane, each with
    the program span (one of ``names``) open at its middle."""
    per = busy(tr, lo, hi)
    if not per:
        return []
    g = gaps(next(iter(per.values())), lo, hi)
    g.sort(key=lambda iv: iv[0] - iv[1])
    names = set(names)
    return [(host_label(tr, (s + t) / 2, names), t - s) for s, t in g[:n]]


@dataclasses.dataclass
class Call:
    """One role span and the executables it put on the device."""
    role: str
    span: Event                 # on the trace's host clock, with its args
    first: float                # start of its first run on the device
    device_s: float = 0.0       # busy inside its runs, per device plane
    in_window_s: float = 0.0    # ... of which inside the window
    modules: Dict[str, float] = dataclasses.field(
        default_factory=lambda: collections.defaultdict(float))


def _dispatcher(starts: List[float], tr: Trace, m: Event) -> Optional[int]:
    """Index of the role span that put run ``m`` on the device: the last
    one started before the run was enqueued (the runtime enqueues just
    after the span's dispatch returns), or, where the trace has no
    enqueue event for the run, before the run started."""
    at = tr.enqueued.get(m.stats.get("run_id"), m.start)
    i = bisect.bisect_right(starts, at) - 1
    return i if i >= 0 else None


def calls(tr: Trace, lo: float, hi: float,
          roles: Sequence[str] = ROLE_SPANS) -> List[Call]:
    """Every role span with a run on the device in [lo, hi], with the
    device time of all the executables it dispatched: the role's own and
    the helpers beside it (a prefill's cache scatter). A run's time is the
    union of its operations; times are averaged over device planes."""
    spans = [e for e in tr.host if e.name in roles]
    starts = [e.start for e in spans]
    found: Dict[int, Call] = {}
    planes = list(tr.modules) or [""]
    for plane, evs in tr.modules.items():
        ops = sorted(tr.ops.get(plane, []), key=lambda e: e.start)
        op_starts = [o.start for o in ops]
        for m in evs:
            if m.end < lo or m.start > hi:
                continue
            i = _dispatcher(starts, tr, m)
            if i is None:
                continue
            a = bisect.bisect_left(op_starts, m.start)
            b = bisect.bisect_left(op_starts, m.end)
            inner = (union(_clip(ops[a:b], m.start, m.end)) if ops
                     else [(m.start, m.end)])
            c = found.setdefault(i, Call(spans[i].name, spans[i], m.start))
            c.first = min(c.first, m.start)
            busy = sum(t - s for s, t in inner) / len(planes)
            c.device_s += busy
            c.in_window_s += sum(min(t, hi) - max(s, lo) for s, t in inner
                                 if t > lo and s < hi) / len(planes)
            c.modules[_base(m.name)] += busy
    return [found[i] for i in sorted(found)]


def _base(name: str) -> str:
    """A module name without a trailing ``(<id>)``."""
    i = name.find("(")
    return name[:i] if i > 0 else name


def role_times(tr: Trace, lo: float, hi: float,
               roles: Sequence[str] = ROLE_SPANS
               ) -> Dict[str, Tuple[float, int]]:
    """Per role: (device seconds of its calls, number of calls), over the
    calls whose first run starts in [lo, hi], each counted whole."""
    out = {r: (0.0, 0) for r in roles}
    for c in calls(tr, lo, hi, roles):
        if lo <= c.first <= hi:
            s, n = out[c.role]
            out[c.role] = (s + c.device_s, n + 1)
    return out


def module_times(cs: Iterable[Call]) -> Dict[str, Dict[str, float]]:
    """Per role, device seconds per executable (module name without its
    id): the role's own program and each helper dispatched beside it."""
    out: Dict[str, Dict[str, float]] = collections.defaultdict(
        lambda: collections.defaultdict(float))
    for c in cs:
        for name, t in c.modules.items():
            out[c.role][name] += t
    return {r: dict(d) for r, d in out.items()}


def top_ops(tr: Trace, lo: float, hi: float,
            n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` operations with most device self time (their duration
    less that of the operations nested in them, as a loop's body is in
    the loop) in [lo, hi], summed over runs and averaged over device
    planes. Names are the profiler's, cut to ``NAME_CHARS``."""
    tot: Dict[str, float] = collections.defaultdict(float)
    for evs in tr.ops.values():
        stack: List[List] = []            # [end, name, self time]
        for e in sorted(evs, key=lambda e: (e.start, -e.dur)):
            while stack and stack[-1][0] <= e.start:
                _, name, self_t = stack.pop()
                tot[name] += self_t
            s, t = max(e.start, lo), min(e.end, hi)
            d = max(0.0, t - s)
            if stack and e.end <= stack[-1][0]:
                stack[-1][2] -= d
            stack.append([e.end, e.name[:NAME_CHARS], d])
        for _, name, self_t in stack:
            tot[name] += self_t
    k = max(1, len(tr.ops))
    return sorted(((name, s / k) for name, s in tot.items() if s > 0),
                  key=lambda x: -x[1])[:n]
