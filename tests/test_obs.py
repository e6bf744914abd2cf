"""Observability layer (DESIGN.md §6): tracing spans + Chrome-trace
export, the typed metrics registry with bounded reservoirs, and the
flight recorder.

The contracts under test:

* disabled tracing is a shared no-op singleton (zero allocation on the
  hot path — asserted by identity);
* an enabled trace is valid Chrome-trace JSON (schema-checked with the
  same validator the CI chaos drill uses) and thread-safe under the
  front door's engine thread;
* the event ORDER on the engine thread is deterministic under a seeded
  ``FaultPlan`` (timestamps vary, sequence does not);
* metric reservoirs are bounded (the pre-v2 per-request lists grew
  forever) and the percentile helpers are exact on 0 and 1 samples;
* a typed poison failure auto-dumps a flight-recorder artifact that
  identifies the poisoned rid and the rung it failed at.
"""
import gc
import glob
import json
import threading
import urllib.request

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.dist import faultinject as FI
from repro.models import transformer as T
from repro.obs import flightrec, metrics, trace
from repro.serve import admission as adm
from repro.serve.engine import ContinuousBatcher, Request, ServeConfig
from repro.serve.frontdoor import FrontDoor

CFG = get_config("llama-mini").replace(
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=256)
SCFG = ServeConfig(batch=4, max_len=64)


@pytest.fixture(scope="module")
def params():
    p, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    return p


def make_requests(n=6, n_new=5, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, n_new=n_new,
                    tokens=rng.integers(0, CFG.vocab_size, size=(7,),
                                        dtype=np.int32))
            for i in range(n)]


# ---------------------------------------------------------------------------
# trace: disabled fast path
# ---------------------------------------------------------------------------

def test_disabled_span_is_the_shared_singleton():
    """While tracing is off every span() call returns the SAME no-op
    object — the disabled hot path allocates nothing."""
    assert not trace.enabled()
    s1 = trace.span("decode_step", step=1)
    s2 = trace.span("anything_else")
    assert s1 is trace.NULL_SPAN and s2 is trace.NULL_SPAN
    with s1:
        pass                                  # context protocol still works
    # instants/counters/async events are no-ops, not errors
    trace.instant("x")
    trace.counter("x", v=1)
    trace.async_begin("x", 1)
    trace.async_end("x", 1)
    assert trace.current() is None


def test_enabled_spans_are_real_and_disable_restores():
    t = trace.enable()
    try:
        assert trace.span("s") is not trace.NULL_SPAN
        with trace.span("s", k=1):
            pass
        assert any(e["name"] == "s" for e in t.events)
    finally:
        assert trace.disable() is t
    assert trace.span("s") is trace.NULL_SPAN


# ---------------------------------------------------------------------------
# trace: Chrome-trace schema
# ---------------------------------------------------------------------------

def test_chrome_trace_export_is_schema_valid(tmp_path):
    out = tmp_path / "t.json"
    with trace.tracing(out=str(out)) as t:
        with trace.span("outer", a=1):
            with trace.span("inner"):
                pass
        trace.instant("blip", why="test")
        trace.counter("serve", queue_depth=3)
        trace.async_begin("request", 7, n_new=5)
        trace.async_end("request", 7, status="done")
    obj = json.loads(out.read_text())
    assert trace.validate_chrome_trace(obj) == []
    assert obj["displayTimeUnit"] == "ms"
    assert obj["otherData"]["schema"] == trace.SCHEMA
    evs = obj["traceEvents"]
    names = [e["name"] for e in evs]
    assert {"outer", "inner", "blip", "serve", "request"} <= set(names)
    # nesting: inner closed before outer, both X spans, inner within outer
    inner = next(e for e in evs if e["name"] == "inner")
    outer = next(e for e in evs if e["name"] == "outer")
    assert inner["ph"] == outer["ph"] == "X"
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    # the emitting thread got an M metadata name event
    assert any(e["ph"] == "M" and e["name"] == "thread_name" for e in evs)
    assert t.dropped == 0


def test_validator_flags_malformed_events():
    assert trace.validate_chrome_trace({}) != []
    bad = {"traceEvents": [{"name": "x", "ph": "Z", "pid": 1, "tid": 1},
                           {"name": "", "ph": "i", "pid": 1, "tid": 1,
                            "ts": 0.0},
                           {"name": "x", "ph": "X", "pid": 1, "tid": 1,
                            "ts": 0.0, "dur": -5},
                           {"name": "x", "ph": "b", "pid": 1, "tid": 1,
                            "ts": 0.0}]}
    errs = trace.validate_chrome_trace(bad)
    assert len(errs) == 4


def test_tracer_bounds_memory_and_counts_drops():
    t = trace.Tracer(max_events=4)
    trace.enable(t)
    try:
        for i in range(10):
            with trace.span("s", i=i):
                pass
    finally:
        trace.disable()
    assert len(t.events) <= 4
    assert t.dropped > 0
    assert t.to_chrome()["otherData"]["dropped_events"] == t.dropped


def test_trace_is_thread_safe_under_concurrent_emitters():
    t = trace.enable()
    try:
        barrier = threading.Barrier(4)     # distinct tids: all live at once

        def emit(k):
            barrier.wait()
            for i in range(50):
                with trace.span(f"w{k}", i=i):
                    pass
        threads = [threading.Thread(target=emit, args=(k,), name=f"w{k}")
                   for k in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    finally:
        trace.disable()
    assert trace.validate_chrome_trace(t.to_chrome()) == []
    spans = [e for e in t.events if e["ph"] == "X"]
    assert len(spans) == 200
    # every emitting thread self-registered a name metadata event
    meta = {e["args"]["name"] for e in t.events if e["ph"] == "M"}
    assert {f"w{k}" for k in range(4)} <= meta
    # seq is strictly monotonic in insertion order (the determinism key)
    seqs = [e["seq"] for e in t.events if e["seq"] >= 0]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


# ---------------------------------------------------------------------------
# trace: deterministic ordering under a seeded FaultPlan
# ---------------------------------------------------------------------------

def _traced_run(params, plan_json):
    faults = FI.FaultPlan.from_json(plan_json) if plan_json else None
    with trace.tracing() as t:
        cb = ContinuousBatcher(
            params, CFG, SCFG,
            admission=adm.AdmissionConfig(max_retries=1), faults=faults)
        for r in make_requests():
            cb.submit(r)
        res = cb.run_until_drained()
    # the comparable fingerprint: names + the deterministic args, in
    # seq order (timestamps/durations differ run to run by design)
    evs = sorted((e for e in t.events if e["seq"] >= 0),
                 key=lambda e: e["seq"])
    sig = [(e["name"], e["ph"], json.dumps(e.get("args", {}),
                                           sort_keys=True)) for e in evs]
    return sig, res.status


def test_event_order_is_deterministic_under_seeded_faultplan(params):
    plan = json.dumps({"seed": 11, "nan_decode_step": 2,
                       "poison_rids": [3]})
    sig1, st1 = _traced_run(params, plan)
    sig2, st2 = _traced_run(params, plan)
    assert st1 == st2
    assert sig1 == sig2
    # and the faulted trace differs from the clean one (the spans see
    # the injected quarantine path)
    sig0, _ = _traced_run(params, "")
    assert sig0 != sig1


def test_frontdoor_engine_thread_traces_cleanly(params):
    with trace.tracing() as t:
        cb = ContinuousBatcher(params, CFG, SCFG)
        door = FrontDoor(cb).start()
        streams = [door.submit(r.tokens, r.n_new, rid=r.rid)
                   for r in make_requests(4)]
        assert all(s is not None for s in streams)
        res = door.drain(timeout=60.0)
        door.close()
    assert res.status == "drained"
    assert trace.validate_chrome_trace(t.to_chrome()) == []
    meta = {e["args"]["name"] for e in t.events if e["ph"] == "M"}
    assert "serve-engine" in meta


def test_engine_step_children_lie_inside_their_engine_step(params):
    """Every child the host spends an engine step in names that step by
    ``step`` and lies inside the ``engine_step`` span of the same
    index; the program's own counts ride on the role spans."""
    reqs = make_requests()
    with trace.tracing() as t:
        cb = ContinuousBatcher(params, CFG, SCFG)
        for r in reqs:
            cb.submit(r)
        res = cb.run_until_drained()
    assert res.status == "drained"
    spans = [e for e in t.events if e["ph"] == "X"]
    steps = {e["args"]["step"]: e for e in spans
             if e["name"] == "engine_step"}
    children = [e for e in spans
                if e["name"] in ("logits_wait", "sample", "emit")]
    decoded = [e["args"]["step"] for e in spans
               if e["name"] == "decode_step"]
    # one decode and one admission of each per step that ran them
    assert len(children) == 3 * (len(decoded) + cb.stats["admissions"])
    for e in children:
        outer = steps[e["args"]["step"]]
        assert outer["ts"] <= e["ts"] + 1e-3
        assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    for name in ("admit", "prefill", "decode_step"):
        assert all(e["args"]["step"] in steps for e in spans
                   if e["name"] == name)
    prefills = [e for e in spans if e["name"] == "prefill"]
    assert sum(e["args"]["real_tokens"] for e in prefills) == sum(
        len(r.tokens) for r in reqs)
    # each request decodes n_new - 1 tokens, the j-th attending its
    # prompt and the j tokens before it
    assert sum(e["args"]["ctx_tokens"] for e in spans
               if e["name"] == "decode_step") == sum(
        len(r.tokens) + j for r in reqs for j in range(1, r.n_new))


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records what each
    annotation was opened with, and whether it was entered and left."""
    opened = []

    def __init__(self, name, **args):
        self.row = [name, args, 0, 0]
        _Recorder.opened.append(self.row)

    def __enter__(self):
        self.row[2] += 1
        return self

    def __exit__(self, *exc):
        self.row[3] += 1
        return False


def test_annotating_tracer_opens_one_annotation_per_span(monkeypatch):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    monkeypatch.setattr(_Recorder, "opened", [])
    t = trace.Tracer(annotate=True)
    with t.span("engine_step", step=3):
        with t.span("decode_step", step=3, live=2, level=0, rows=[1, 2],
                    frac=0.5, tag="x"):
            pass
    assert _Recorder.opened == [
        ["engine_step", {"step": 3}, 1, 1],
        ["decode_step", {"step": 3, "live": 2, "level": 0, "frac": 0.5,
                         "tag": "x"}, 1, 1]]
    assert [e["name"] for e in t.events if e["ph"] == "X"] == [
        "decode_step", "engine_step"]
    monkeypatch.setattr(_Recorder, "opened", [])
    plain = trace.Tracer()
    with plain.span("decode_step", step=1):
        pass
    assert _Recorder.opened == [] and len(plain.events) == 2


def test_gc_hook_is_registered_only_while_tracing():
    assert trace._on_gc not in gc.callbacks
    t = trace.enable()
    try:
        assert gc.callbacks.count(trace._on_gc) == 1
        trace.enable(t)                    # re-installing adds no second
        assert gc.callbacks.count(trace._on_gc) == 1
        gc.collect()
    finally:
        trace.disable()
    assert trace._on_gc not in gc.callbacks
    collected = [e for e in t.events if e["name"] == trace.GC_SPAN]
    assert collected and all(
        e["ph"] == "X" and e["seq"] == -1
        and e["args"]["generation"] == trace.GC_GENERATION
        for e in collected)
    with trace.tracing():
        assert trace._on_gc in gc.callbacks
    assert trace._on_gc not in gc.callbacks


def test_a_collection_inside_an_append_does_not_deadlock():
    """A collection can start while ``_append`` holds the tracer's lock
    (the append allocates); the ``gc`` span it closes then appends on
    the same thread, which must not wait on itself."""
    t = trace.enable()
    try:
        def collect_inside_append():
            with t._lock:
                trace._on_gc("start", {"generation": trace.GC_GENERATION})
                trace._on_gc("stop", {"generation": trace.GC_GENERATION})
        th = threading.Thread(target=collect_inside_append, daemon=True)
        th.start()
        th.join(timeout=10.0)
        assert not th.is_alive()
    finally:
        trace.disable()
    assert {e["name"] for e in t.events if e["ph"] == "X"} == {
        trace.GC_SPAN}


def test_serve_device_trace_holds_the_programs_spans(tmp_path):
    """``--device-trace-dir`` alone puts the program's spans on the
    profiler's host plane, on the device trace's own clock."""
    from jax.profiler import ProfileData

    from repro.serve import api
    d = tmp_path / "prof"
    api.serve(api.ServeOptions(
        arch="llama-mini", requests=2, n_new=3, prompt_len=8, batch=2,
        max_len=32, trace_out=str(tmp_path / "t.json"),
        device_trace_dir=str(d)))
    paths = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)
    assert len(paths) == 1
    found = {}
    for plane in ProfileData.from_file(paths[0]).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in ("engine_step", "decode_step"):
                        found.setdefault(e.name, dict(e.stats))
    assert set(found) == {"engine_step", "decode_step"}
    assert "step" in found["engine_step"] and "live" in found["decode_step"]
    assert not trace.enabled()


# ---------------------------------------------------------------------------
# metrics: bounded reservoirs, exact edge cases
# ---------------------------------------------------------------------------

def test_histogram_zero_and_one_sample_are_exact():
    h = metrics.Histogram("h")
    assert h.summary() == {"p50": 0.0, "p95": 0.0, "mean": 0.0, "n": 0,
                           "min": 0.0, "max": 0.0}
    assert h.percentile(50) == 0.0
    h.observe(42.0)
    s = h.summary()
    assert s["p50"] == s["p95"] == s["mean"] == 42.0
    assert s["n"] == 1 and s["min"] == s["max"] == 42.0


def test_histogram_is_bounded_with_exact_aggregates():
    h = metrics.Histogram("ttft_ms", capacity=64)
    for i in range(10_000):
        h.observe(float(i))
    assert len(h.samples) == 64            # memory stays O(capacity)
    assert h.n == 10_000                   # ...but n/sum/min/max are exact
    assert h.sum == sum(range(10_000))
    assert h.min == 0.0 and h.max == 9999.0
    # uniform reservoir: p50 lands around the true median
    assert 2000 < h.percentile(50) < 8000


def test_histogram_reservoir_is_deterministic_per_name():
    def fill(name):
        h = metrics.Histogram(name, capacity=16)
        for i in range(1000):
            h.observe(float(i))
        return list(h.samples)
    assert fill("a") == fill("a")          # same name+stream → same state
    assert fill("a") != fill("b")          # name seeds the RNG


def test_servemetrics_memory_is_bounded():
    """Regression: ttft/queue-wait used to be unbounded per-request
    lists; now 100k observations hold at the reservoir capacity."""
    m = adm.ServeMetrics()
    for _ in range(100_000):
        m.observe_ttft(0.01)
    assert len(m._ttft.samples) <= metrics.DEFAULT_RESERVOIR
    snap = m.snapshot(0, 0)
    assert snap["ttft"]["n"] == 100_000
    assert snap["ttft"]["p50_ms"] == pytest.approx(10.0)


def test_snapshot_v2_schema_with_legacy_aliases():
    m = adm.ServeMetrics()
    m.bump("submitted", 3)
    m.observe_ttft(0.002)
    m.step_at_level(1)
    snap = m.snapshot(queue_depth=2, rank_level=1,
                      engine_stats={"prefill_retraces": 4})
    json.dumps(snap)                       # JSON-serializable as-is
    assert snap["schema"] == metrics.SCHEMA
    # v2 blocks: typed counters (engine stats folded in), gauges, hists
    assert snap["counters"]["submitted"] == 3
    assert snap["counters"]["prefill_retraces"] == 4
    assert snap["gauges"]["queue_depth"] == 2
    assert snap["histograms"]["ttft_ms"]["n"] == 1
    assert snap["rank_residency"] == {"1": 1}
    # deprecated aliases: every pre-v2 top-level key still present
    assert snap["submitted"] == 3
    assert snap["queue_depth"] == 2 and snap["rank_level"] == 1
    assert snap["ttft"] == {"p50_ms": 2.0, "p95_ms": 2.0, "mean_ms": 2.0,
                            "n": 1}
    assert snap["queue_wait"]["n"] == 0
    assert snap["engine"] == {"prefill_retraces": 4}


def test_prometheus_text_exposition():
    r = metrics.MetricsRegistry()
    r.counter("steps").inc(7)
    r.gauge("queue_depth").set(3)
    r.histogram("ttft_ms").observe(5.0)
    text = metrics.prometheus_text(r.snapshot(), labels={"replica": "0"})
    assert '# TYPE repro_steps_total counter' in text
    assert 'repro_steps_total{replica="0"} 7' in text
    assert 'repro_queue_depth{replica="0"} 3' in text
    assert 'repro_ttft_ms{quantile="0.5",replica="0"} 5.0' in text
    assert 'repro_ttft_ms_count{replica="0"} 1' in text


def test_metrics_exporter_and_server(tmp_path):
    r = metrics.MetricsRegistry()
    r.counter("steps").inc(2)
    path = tmp_path / "m.json"
    exp = metrics.MetricsExporter(str(path), r.snapshot, interval_s=60.0)
    exp.start()
    exp.stop()                             # final write even if no tick
    snap = json.loads(path.read_text())
    assert snap["schema"] == metrics.SCHEMA
    assert snap["counters"]["steps"] == 2
    srv = metrics.MetricsServer(lambda: [r.snapshot()], port=0).start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/metrics", timeout=10).read()
    finally:
        srv.stop()
    assert b'repro_steps_total{replica="0"} 2' in body


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flightrec_ring_is_bounded_and_dump_validates(tmp_path):
    fr = flightrec.FlightRecorder(dump_dir=str(tmp_path), max_events=8,
                                  max_timings=4)
    for i in range(50):
        fr.note("tick", i=i)
        fr.step_timing(i, 1.5, live=2)
    assert len(fr.events) == 8 and len(fr.step_timings) == 4
    path = fr.dump("stalled", {"queue_depth": 3})
    obj = json.loads(open(path).read())
    assert flightrec.validate_dump(obj) == []
    assert obj["reason"] == "stalled"
    assert obj["context"]["queue_depth"] == 3
    assert [e["i"] for e in obj["events"]] == list(range(42, 50))
    # validator catches a corrupted artifact
    obj["schema"] = "nope"
    obj["events"] = obj["events"][::-1]
    assert len(flightrec.validate_dump(obj)) == 2


def test_flightrec_without_dump_dir_records_but_never_writes():
    fr = flightrec.FlightRecorder()
    fr.note("x")
    assert fr.dump("stalled") is None and fr.dumps == []
    assert len(fr.events) == 1


def test_poison_failure_autodumps_identifying_rid_and_rung(
        params, tmp_path):
    """The acceptance artifact: an injected persistent poison fails
    typed AND leaves a dump from which the poisoned rid, the rung it
    failed at and the armed plan (seed included) are all recoverable."""
    plan = FI.FaultPlan.from_json(
        json.dumps({"seed": 5, "poison_rids": [2]}))
    cb = ContinuousBatcher(
        params, CFG, SCFG,
        admission=adm.AdmissionConfig(max_retries=1), faults=plan,
        flight=flightrec.FlightRecorder(dump_dir=str(tmp_path)))
    for r in make_requests():
        cb.submit(r)
    res = cb.run_until_drained()
    assert res.status == "drained"
    assert [r.rid for r in res.failed] == [2]
    assert len(cb.flight.dumps) == 1
    obj = json.loads(open(cb.flight.dumps[0]).read())
    assert flightrec.validate_dump(obj) == []
    assert obj["reason"] == "failed_poison"
    assert obj["context"]["rid"] == 2
    assert obj["context"]["rank_level"] == 0
    assert obj["context"]["fault_plan"]["seed"] == 5
    assert obj["context"]["fault_plan"]["poison_rids"] == [2]
    assert any(e["kind"] == "poison" and 2 in e["rids"]
               for e in obj["events"])
    assert obj["step_timings"]           # last-N step wall times present


def test_nondrained_drain_autodumps(params, tmp_path):
    plan = FI.FaultPlan.from_json(json.dumps({"wedge_from_step": 0,
                                              "wedge_s": 0.0}))
    cb = ContinuousBatcher(
        params, CFG, SCFG, faults=plan,
        flight=flightrec.FlightRecorder(dump_dir=str(tmp_path)))
    for r in make_requests(2):
        cb.submit(r)
    res = cb.run_until_drained(watchdog_s=0.2)
    assert res.status == "stalled"
    assert len(cb.flight.dumps) == 1
    obj = json.loads(open(cb.flight.dumps[0]).read())
    assert obj["reason"] == "stalled"
    assert sorted(obj["context"]["undrained_rids"]) == [0, 1]


def test_device_trace_raises_when_the_profiler_cannot_start(monkeypatch,
                                                            tmp_path):
    import jax

    def refuse(logdir):
        raise RuntimeError("profiler unavailable")
    monkeypatch.setattr(jax.profiler, "start_trace", refuse)
    with pytest.raises(RuntimeError, match="profiler unavailable"):
        with trace.device_trace(str(tmp_path)):
            pass
    with trace.device_trace(None) as off:    # no logdir: no capture asked
        assert off is None
