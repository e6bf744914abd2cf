"""The benchmark harness: one cell, one seed, one process.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in the file that entry names, its
traffic mix in ``bench/traffic/<traffic>.json``, each metric in
``bench/metrics/<metric>.py`` (a ``read(run)`` that returns a number, or
``None`` when there is nothing to read), and the plain reference in the
file the configuration's ``reference`` names. Adding a cell, a mix, a
metric or a configuration of another architecture adds files and
entries; no file here changes.

A configuration file gives ``name``, ``arch`` (the program's registered
architecture it overrides), ``reference``, ``model`` (the published
config's keys), ``serving`` (``param_dtype``, ``compute_dtype``),
``engine`` (``batch``, ``max_len``, ``kv_block``), ``compression`` (or
null), ``weights`` (``rule`` "seed", or "fixed" with a ``weight_seed``)
and ``correct`` (``sample`` and each limit). Its reference module is all
the harness knows of the architecture (``bench/reference/llama.py`` is
one):

* ``program_config(model) -> dict``: the ``ModelConfig`` fields the
  ``model`` block sets;
* ``sizes(cfg) -> dict``: what the reference's functions take as sizes;
* ``from_program(params, cfg) -> dict``: the program's parameter tree in
  the reference's layout;
* ``token_gaps(params, sizes, tokens, served, quant)`` and, for a
  compressed configuration, ``factor_misfits(dense, served, sizes,
  tokens, n)`` and ``linear_counts(ref_params, cfg) -> (served,
  dense)``: what decides ``correct``; ``int8_quant``, the control;
* ``work(cfg, params) -> dict``: what serving costs, for the per-layer
  readers (``linear_flops_per_token``, ``decode_param_bytes(live)``,
  per-layer ``windows``, ``n_heads``, ``n_kv_heads``, ``head_dim``,
  ``vocab_size``, ``kv_dtype``);
* ``TINY_MODEL``: the ``model`` block of its CPU-test copy
  (``bench/tiny.py``).

A run: check for the chip, build the engine (weights made from the seed
on the device, or a D-Rank artifact compressed once per checkout and
booted from), warm every prefill bucket the mix can reach and the
decode step through the front door, measure ``--seconds`` of the mix,
free the engine, and then check what the window served against the
plain float32 reference (:func:`judge`). The last line of standard output is the JSON result.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import gc
import hashlib
import importlib.util
import json
import os
import shutil
import sys
import time
from typing import Any, Dict, List, Optional

import numpy as np

from bench import load

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RAMP_S = 8.0              # load before the window opens
TRACE_AT_S = 2.0          # traced runs: profile from this far into the window
TRACE_S = 3.0             # ... for this long
DRAIN_S = 120.0           # after the window: wait this long for requests


class Failure(Exception):
    """A run that must exit non-zero and print no result."""


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    mix: Dict
    metrics: List[Dict]      # BENCHMARK.json entries this run reports


def find_cell(root: str, name: str, traced: bool) -> Cell:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Failure(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(root, "bench", "traffic",
                           f"{w['traffic']}.json")) as f:
        mix = json.load(f)
    kind = "per_layer" if traced else "end_to_end"
    metrics = [m for m in bench[kind]
               if name in m.get("workloads", [name])]
    return Cell(name, int(w["chips"]), config, mix, metrics)


def load_module(root: str, rel: str):
    """The Python file ``rel`` (relative to ``root``) as a module."""
    path = os.path.join(root, rel)
    name = "bench_" + "".join(c if c.isalnum() else "_" for c in rel)
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_metric(root: str, name: str):
    return load_module(root, os.path.join("bench", "metrics",
                                          f"{name}.py")).read


# ---------------------------------------------------------------------------
# the model
# ---------------------------------------------------------------------------
def model_config(config: Dict, ref):
    """The program's ``ModelConfig`` for a configuration file, as its
    reference module ``ref`` maps the ``model`` block, registered under
    the configuration's name."""
    from repro import configs
    over = ref.program_config(config["model"])
    over["dtype"] = config["serving"]["compute_dtype"]
    over["param_dtype"] = config["serving"]["param_dtype"]
    cfg = configs.get_config(config["arch"]).replace(**over)
    configs.register(config["name"], cfg)
    return cfg


def param_shapes(cfg):
    import jax

    from repro.models import transformer as T
    return jax.eval_shape(lambda: T.init_model(cfg, jax.random.PRNGKey(0))[0])


def weight_seed(config: Dict, seed: int) -> int:
    w = config["weights"]
    return int(w["weight_seed"]) if w["rule"] == "fixed" else int(seed)


def artifact_dir(root: str, config: Dict) -> str:
    """Where a compressed configuration's artifact is kept in this
    checkout: under the compile-cache root, keyed by the program's source
    and what the configuration file says the artifact is made of (model,
    serving dtypes, engine, compression, weight seed)."""
    from repro import compile_cache
    h = hashlib.sha256()
    src = os.path.join(root, "src", "repro")
    for dp, _, fs in sorted(os.walk(src)):
        for f in sorted(fs):
            if f.endswith(".py"):
                p = os.path.join(dp, f)
                h.update(os.path.relpath(p, src).encode())
                with open(p, "rb") as fh:
                    h.update(fh.read())
    made_of = {k: config[k] for k in ("name", "arch", "model", "serving",
                                      "engine", "compression", "weights")}
    h.update(json.dumps(made_of, sort_keys=True).encode())
    return os.path.join(compile_cache.cache_root(), "bench",
                        f"{config['name']}-{h.hexdigest()[:16]}")


def serve_options(config: Dict, **kw):
    from repro.serve import api
    e = config["engine"]
    return api.ServeOptions(arch=config["name"], aot=True,
                            batch=e["batch"], max_len=e["max_len"],
                            kv_block=e["kv_block"], **kw)


def compress(root: str, config: Dict, cfg) -> str:
    """The artifact of a compressed configuration, made on first use in
    this checkout through the program's own compress-and-save path."""
    from repro.serve import api

    from bench import weights
    art = artifact_dir(root, config)
    if os.path.isdir(art):
        return art
    if config["weights"]["rule"] != "fixed":
        raise Failure(f"{config['name']}: a compressed configuration "
                      f"needs a fixed weight_seed (one artifact per "
                      f"checkout)")
    c = config["compression"]
    part = art + ".partial"
    shutil.rmtree(part, ignore_errors=True)
    t0 = time.perf_counter()
    params = weights.make(param_shapes(cfg), config["weights"]["weight_seed"])
    opts = serve_options(
        config, compress=c["method"], ratio=c["ratio"],
        group_size=c["group_size"], beta=c["beta"],
        device_compress=c["device_compress"],
        calib_samples=c["calib_samples"], calib_seq=c["calib_seq"],
        save_compressed=part)
    api._compress_in_process(opts, params, cfg)
    del params
    os.replace(part, art)
    log(f"compress: artifact={os.path.basename(art)} "
        f"compress_s={time.perf_counter() - t0:.3f}")
    return art


def build_engine(root: str, config: Dict, cfg, seed: int):
    """A warmed ``ContinuousBatcher`` serving the configuration."""
    from repro.serve import api
    if config.get("compression"):
        art = compress(root, config, cfg)
        return api.load_engine(serve_options(config, compressed_ckpt=art))
    from repro.serve import aot
    from repro.serve.engine import ContinuousBatcher

    from bench import weights
    # the dense boot of api.load_engine, with the benchmark's weights in
    # place of the program's own random init
    params = weights.make(param_shapes(cfg), weight_seed(config, seed))
    opts = serve_options(config)
    scfg = opts.serve_config()
    reg = aot.AotRegistry(cfg, scfg, aot.live_fingerprint(params, cfg))
    cb = ContinuousBatcher(params, cfg, scfg, executables=reg)
    cb.warm_executables()
    return cb


def dense_reference_params(m: "Measured") -> Dict:
    """The dense weights, made again from the seed by the benchmark, in
    the reference's layout: those the program was given to serve, or to
    compress."""
    from bench import weights
    params = weights.make(param_shapes(m.cfg), weight_seed(m.config, m.seed))
    return m.ref.from_program(params, m.cfg)


def reference_params(m: "Measured") -> Dict:
    """The served parameters in the reference's layout, loaded afresh:
    the compressed artifact read from disk with numpy, or the dense
    weights made again from the seed."""
    from bench import weights
    if m.config.get("compression"):
        return m.ref.from_program(weights.load_artifact(
            artifact_dir(m.root, m.config)), m.cfg)
    return dense_reference_params(m)


# ---------------------------------------------------------------------------
# tracing: the program's spans, also on the profiler's clock
# ---------------------------------------------------------------------------
def profiler_tracer():
    """A ``repro.obs.trace.Tracer`` whose spans also open a
    ``jax.profiler.TraceAnnotation`` of the same name and arguments."""
    import jax

    from repro.obs import trace

    class _Both:
        __slots__ = ("_span", "_ann")

        def __init__(self, span, ann):
            self._span, self._ann = span, ann

        def __enter__(self):
            self._ann.__enter__()
            self._span.__enter__()
            return self

        def __exit__(self, *exc):
            self._span.__exit__(*exc)
            return self._ann.__exit__(*exc)

    class ProfilerTracer(trace.Tracer):
        def span(self, name, **args):
            ann = jax.profiler.TraceAnnotation(
                name, **{k: v for k, v in args.items()
                         if isinstance(v, (int, float, str))})
            return _Both(super().span(name, **args), ann)

    return ProfilerTracer()


def spans_of(tracer) -> List[Dict]:
    """The tracer's complete spans on the host clock (seconds)."""
    e0 = tracer.epoch_ns * 1e-9
    return [{"name": ev["name"], "t0": e0 + ev["ts"] * 1e-6,
             "t1": e0 + (ev["ts"] + ev["dur"]) * 1e-6, "args": ev["args"]}
            for ev in tracer.events if ev.get("ph") == "X"]


def mark() -> float:
    import jax
    t = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench_trace_mark"):
        pass
    return t


# ---------------------------------------------------------------------------
# what a run hands its metric readers
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    seconds: float
    w0: float                       # window on the host clock
    w1: float
    setup_s: float
    records: List[load.Record]
    batch: int
    work: Dict                      # the reference module's work(cfg, params)
    peaks: Dict[str, float]
    t0: float = 0.0                 # traced window on the host clock
    t1: float = 0.0
    trace: Any = None               # trace_reduce.Trace
    trace_lo: float = 0.0           # the traced window on its clock
    trace_hi: float = 0.0
    spans: List[Dict] = dataclasses.field(default_factory=list)
    calls: List[Any] = dataclasses.field(default_factory=list)

    def tokens_between(self, lo: float, hi: float):
        """(record, index, time) of each token received in [lo, hi)."""
        for r in self.records:
            for j, t in enumerate(r.times):
                if lo <= t < hi:
                    yield r, j, t

    def host(self, x: float) -> float:
        """A time on the trace's clock, on the host clock (the first
        window mark is at ``trace_lo`` on the one and ``t0`` on the
        other)."""
        return self.t0 + (x - self.trace_lo)

    def whole_calls(self, role: str) -> List[Any]:
        """The traced calls of ``role`` whose first run on the device
        starts in the traced window."""
        return [c for c in self.calls if c.role == role
                and self.trace_lo <= c.first <= self.trace_hi]

    def decode_context(self) -> Optional[float]:
        """Mean live context (prompt and tokens so far) of the tokens
        decoded in the traced window."""
        c = [r.prompt_len + j for r, j, _ in self.tokens_between(
            self.t0, self.t1) if j > 0]
        return sum(c) / len(c) if c else None

    def admitted(self, ends: List[float]) -> List[List[int]]:
        """For prefills ending at ``ends`` (host clock, ascending): the
        prompt lengths of the requests each admitted, matched by their
        first token arriving after it and within a second."""
        out: List[List[int]] = [[] for _ in ends]
        for r in self.records:
            if r.times:
                i = bisect.bisect_right(ends, r.times[0]) - 1
                if i >= 0 and r.times[0] - ends[i] < 1.0:
                    out[i].append(r.prompt_len)
        return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------
def sample(records: List[load.Record], w1: float, k: int,
           seed: int) -> List[load.Record]:
    """``k`` requests finished in the window, drawn from the seed, the
    longest (prompt + output) always among them."""
    done = [r for r in records if r.status == "done" and r.times
            and r.times[-1] <= w1 and len(r.out) == r.spec.n_new]
    if not done:
        return []
    done.sort(key=lambda r: r.spec.rid)
    longest = max(done, key=lambda r: (r.prompt_len + len(r.out),
                                       -r.spec.rid))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 7])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick)]


def gaps_of(ref, params, sizes: Dict, recs: List[load.Record],
            max_len: int, quant=None) -> List[float]:
    """Per sampled request, the widest gap of a served token below the
    float32 reference's best (with ``quant``: of the token that
    precision puts first). ``ref`` is the reference module."""
    import jax
    import jax.numpy as jnp
    fn = jax.jit(lambda p, t, s: ref.token_gaps(p, sizes, t, s, quant))
    out = []
    for r in recs:
        toks, served, P, n = sequence(r, max_len)
        g = np.asarray(fn(params, jnp.asarray(toks), jnp.asarray(served)))
        out.append(float(g[P - 1:P - 1 + n].max()))
    return out


def sequence(r: load.Record, max_len: int):
    """A request's prompt and served tokens at the reference's one
    length: (tokens fed, token served after each position, prompt
    length, tokens served)."""
    P, n = r.prompt_len, len(r.out)
    toks = np.zeros((max_len,), np.int32)
    served = np.zeros((max_len,), np.int32)
    toks[:P] = r.spec.tokens
    toks[P:P + n] = r.out
    served[P - 1:P - 1 + n] = r.out
    return toks, served, P, n


def misfits_of(ref, dense, served, sizes: Dict, r: load.Record,
               max_len: int) -> List[Dict[str, float]]:
    """Per layer and linear, how far the served linear lies from the
    dense one, on the dense model's inputs over request ``r``'s prompt
    and served tokens."""
    import jax.numpy as jnp
    toks, _, P, n = sequence(r, max_len)
    return ref.factor_misfits(dense, served, sizes, jnp.asarray(toks),
                              P + n)


def achieved_ratio(ref, ref_params: Dict, cfg) -> float:
    """1 - (parameters of the served linears) / (their dense count), as
    the reference module ``ref`` counts them."""
    served, dense = ref.linear_counts(ref_params, cfg)
    return 1.0 - served / dense


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------
def check_device(chips: int, require_tpu: bool):
    import jax
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise Failure(f"no TPU: JAX reports platform {devs[0].platform!r}; "
                      f"this benchmark runs only on a TPU")
    if len(devs) < chips:
        raise Failure(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[0]


def compile_counter():
    """A list whose length is the number of XLA compiles since made, and
    the listener that fills it (to unregister)."""
    import jax
    seen: List[float] = []

    def on_event(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            seen.append(secs)
    jax.monitoring.register_event_duration_secs_listener(on_event)
    return seen, on_event


def warm(door, mix: Dict, seed: int, max_len: int, vocab: int) -> int:
    """One request per prefill bucket the mix can reach, each decoding a
    few tokens: deserializes or runs every program the window uses."""
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 5])
    n = 0
    for b in load.bucket_lengths(mix, max_len):
        plen = min(b, max_len - 4)
        spec = load.Spec(rid=-1 - n, n_new=3, tokens=rng.integers(
            0, vocab, size=plen, dtype=np.int32))
        rec = load.send(door, spec, time.perf_counter())
        if rec.status != "done":
            raise Failure(f"warm-up request at bucket {b} ended "
                          f"{rec.status!r}")
        n += 1
    return n


@dataclasses.dataclass
class Measured:
    """What a run's measurement leaves for judging, the engine freed."""
    root: str
    seed: int
    config: Dict
    ref: Any                        # the configuration's reference module
    cfg: Any
    run: Run
    picked: List[load.Record]       # sampled for the reference
    failed: int
    attempted: int
    device: Dict
    breakdown: Optional[Dict]
    metrics: List[Dict]             # the cell's entries to report


def measure(argv: Optional[List[str]] = None, *, root: str = ROOT,
            t_start: Optional[float] = None,
            require_tpu: bool = True) -> Measured:
    """Build the cell, warm it, serve its mix for ``--seconds`` (with
    ``--trace 1``, profiling a few seconds of it), and free the engine."""
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(prog="bench/run.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    traced = bool(args.trace)

    cell = find_cell(root, args.workload, traced)
    try:
        import jax

        from repro import compile_cache
    except ImportError as e:
        raise Failure(f"cannot import the program ({e}); run from the root "
                      f"of a checkout that holds src/repro") from None
    dev = check_device(cell.chips, require_tpu)
    from repro.obs import trace as ptrace
    from repro.serve.frontdoor import FrontDoor

    from bench import peaks, trace_reduce
    compile_cache.enable()
    pk = peaks.peak(dev.device_kind) if require_tpu else {}
    config, mix = cell.config, cell.mix
    ref = load_module(root, config["reference"])
    cfg = model_config(config, ref)
    max_len = config["engine"]["max_len"]
    compiles, on_compile = compile_counter()

    t_boot = time.perf_counter()
    cb = build_engine(root, config, cfg, args.seed)
    door = FrontDoor(cb).start()
    try:
        t_warm = time.perf_counter()
        n_warm = warm(door, mix, args.seed, max_len, cfg.vocab_size)
        setup_s = time.perf_counter() - t_start
        aot0, comp0 = cb.stats["aot_compiles"], len(compiles)
        log(f"setup: setup_s={setup_s:.4f} "
            f"import_s={t_boot - t_start:.3f} "
            f"boot_s={t_warm - t_boot:.3f} "
            f"warm_s={t_start + setup_s - t_warm:.3f} warm_requests={n_warm} "
            f"aot_compiles={aot0} aot_cache_hits={cb.stats['aot_cache_hits']}"
            f" xla_compiles={comp0}")

        specs = load.generate(mix, args.seed, cfg.vocab_size)
        clients = load.Clients(door, mix, specs).start()
        w0 = clients.t0 + RAMP_S
        w1 = w0 + args.seconds
        tracer = None
        t0 = t1 = 0.0
        logdir = os.path.join(root, ".cache", "bench", "trace",
                              f"{cell.name}-{args.seed}")
        if traced:
            shutil.rmtree(logdir, ignore_errors=True)
            at = w0 + min(TRACE_AT_S, args.seconds / 4)
            time.sleep(max(0.0, at - time.perf_counter()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(logdir, profiler_options=opts)
            tracer = profiler_tracer()
            ptrace.enable(tracer)
            t0 = mark()
            time.sleep(max(0.0, min(at + TRACE_S, w1) - time.perf_counter()))
            t1 = mark()
            ptrace.disable()
            jax.profiler.stop_trace()
        time.sleep(max(0.0, w1 - time.perf_counter()))
        clients.stop()
        mem = dev.memory_stats() or {}
        memory_peak = int(mem.get("peak_bytes_in_use", 0))
        in_window_aot = cb.stats["aot_compiles"] - aot0
        in_window_xla = len(compiles) - comp0
        t_drain = time.perf_counter()
        drained = clients.join(DRAIN_S)
        late = clients.lateness_s()
        log(f"window: seconds={args.seconds} records={len(clients.records)} "
            f"drain_s={time.perf_counter() - t_drain:.3f} "
            f"compiles_in_window aot={in_window_aot} xla={in_window_xla} "
            f"clients_ended={drained}"
            + (f" generator_lateness_max_s={late:.6f}"
               if late is not None else ""))
        work = ref.work(cfg, cb.params)
    finally:
        door.close()
        jax.monitoring.unregister_event_duration_listener(on_compile)
    batch = cb.scfg.batch
    del door, cb
    gc.collect()

    records = clients.records
    runinfo = Run(seconds=args.seconds, w0=w0, w1=w1, setup_s=setup_s,
                  records=records, batch=batch, work=work, peaks=pk,
                  t0=t0, t1=t1)
    breakdown = None
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    if traced:
        runinfo.spans = spans_of(tracer)
        paths = [os.path.join(dp, f) for dp, _, fs in os.walk(logdir)
                 for f in fs if f.endswith(".xplane.pb")]
        if len(paths) != 1:
            raise Failure(f"expected one profile under {logdir}, "
                          f"found {paths}")
        tr = trace_reduce.read(paths[0])
        lo, hi = trace_reduce.window(tr)
        runinfo.trace, runinfo.trace_lo, runinfo.trace_hi = tr, lo, hi
        runinfo.calls = trace_reduce.calls(tr, lo, hi)
        busy_s = trace_reduce.busy_seconds(tr, lo, hi)
        device.update(busy_s=busy_s, window_s=hi - lo)
        breakdown = {
            "device_ops": [[n, s] for n, s in
                           trace_reduce.top_ops(tr, lo, hi)],
            "idle_gaps": [[n, s] for n, s in trace_reduce.idle_gaps(
                tr, lo, hi, {sp["name"] for sp in runinfo.spans})]}
        roles = trace_reduce.role_times(tr, lo, hi)
        log("trace: " + " ".join(f"{r}_device_s={s:.6f} {r}_calls={n}"
                                 for r, (s, n) in roles.items())
            + f" busy_s={busy_s:.6f} window_s={hi - lo:.6f}")
        for role, mods in trace_reduce.module_times(runinfo.calls).items():
            log(f"trace executables of {role}: " + " ".join(
                f"{n}={t:.6f}" for n, t in sorted(mods.items(),
                                                  key=lambda x: -x[1])))

    in_window = [r for r in records if w0 <= r.due < w1]
    failed = sum(1 for r in in_window if r.status != "done")
    picked = sample(records, w1, int(config["correct"]["sample"]),
                    args.seed)
    return Measured(root=root, seed=args.seed, config=config, ref=ref,
                    cfg=cfg, run=runinfo, picked=picked, failed=failed,
                    attempted=len(in_window), device=device,
                    breakdown=breakdown, metrics=cell.metrics)


def judge(m: Measured) -> Dict[str, Dict]:
    """The numbers that decide ``correct``, each beside its limit,
    computed by the plain reference once the engine is freed: the widest
    gap of a sampled served token below the reference's best; for a
    compressed configuration, how far the served linears lie from the
    dense weights they were made from, and the ratio achieved; and the
    requests that failed in the window."""
    import jax
    t_ref = time.perf_counter()
    config, cfg, ref = m.config, m.cfg, m.ref
    cc = config["correct"]
    sizes = ref.sizes(cfg)
    max_len = config["engine"]["max_len"]
    served = reference_params(m)
    checks: Dict[str, Dict] = {}
    ratio = (achieved_ratio(ref, served, cfg) if config.get("compression")
             else None)
    served = jax.device_put(served)
    with jax.default_matmul_precision("highest"):
        gaps = gaps_of(ref, served, sizes, m.picked, max_len)
        checks["token_gap"] = {"value": max(gaps) if gaps else None,
                               "limit": cc["token_gap"]}
        if config.get("compression"):
            fits = []
            if m.picked:
                dense = jax.device_put(dense_reference_params(m))
                fits = [v for row in misfits_of(ref, dense, served, sizes,
                                                m.picked[0], max_len)
                        for v in row.values()]
                del dense
                log(f"factors: misfit_max={max(fits):.6f} "
                    f"misfit_median={float(np.median(fits)):.6f} "
                    f"linears={len(fits)}")
            checks["factor_misfit"] = {
                "value": max(fits) if fits else None,
                "limit": cc["factor_misfit"]}
            checks["ratio_error"] = {
                "value": abs(ratio - config["compression"]["ratio"]),
                "limit": cc["ratio_error"]}
    del served
    checks["requests_failed"] = {"value": m.failed, "limit": 0}
    log(f"correct: reference_s={time.perf_counter() - t_ref:.3f} "
        f"sampled_requests={len(m.picked)} "
        f"sampled_tokens={sum(len(r.out) for r in m.picked)} "
        f"per_request_gap=" + ",".join(f"{g:.6f}" for g in gaps))
    return checks


def run(argv: Optional[List[str]] = None, *, root: str = ROOT,
        t_start: Optional[float] = None,
        require_tpu: bool = True) -> Dict:
    """One benchmark run; returns the result object."""
    t_start = time.perf_counter() if t_start is None else t_start
    m = measure(argv, root=root, t_start=t_start, require_tpu=require_tpu)
    metrics = {}
    for e in m.metrics:
        v = read_metric(root, e["name"])(m.run)
        if v is not None:
            metrics[e["name"]] = {"value": v, "unit": e["unit"]}
    checks = judge(m)
    ok = bool(m.picked) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    log(f"run: run_s={time.perf_counter() - t_start:.3f}")
    for k, c in checks.items():
        log(f"check {k}: {c['value']} limit {c['limit']}")
    result = {"correct": ok, "attempted": m.attempted, "failed": m.failed,
              "metrics": metrics, "device": m.device}
    if m.breakdown is not None:
        result["breakdown"] = m.breakdown
    result["checks"] = checks
    return result


def main(argv: Optional[List[str]] = None,
         t_start: Optional[float] = None) -> int:
    try:
        result = run(argv, t_start=t_start)
    except Failure as e:
        log(f"bench: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0

