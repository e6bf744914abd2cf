"""Boot time-to-first-token benchmark (ISSUE 7 / DESIGN.md §5.6):
cold-trace vs AOT-compiled boot of the serving stack on the llama-mini
compressed artifact.

This is the deployment cost the AOT front door exists to kill: a pod
restart under load used to pay jit tracing for the admission prefill,
the decode step and the cache scatter before emitting token one. The
AOT path (``serve/aot.py``) compiles that whole surface ahead of time
into a persistent cache keyed on the artifact fingerprint, so a warm
boot deserializes executables instead of compiling them.

Three cells, each a FRESH subprocess (an honest boot — no XLA state,
no in-process jit caches, JAX's own compilation cache turned off). The
artifact is built in a child process too: the parent never imports JAX,
so on a TPU host every child can take the chip.

* ``traced``   — historical lazy-jit boot; TTFT pays the traces.
* ``aot_cold`` — AOT boot with an empty cache; pays the same compiles
  up front (worst case) but populates the cache.
* ``aot_warm`` — AOT boot against the populated cache; zero compiles.

Every cell must emit IDENTICAL tokens (greedy decode; the registries
may only change cost, never results) and the warm cell must report
``aot_compiles == 0`` — both asserted here, not just recorded.

Emits ``BENCH_boot.json`` rows
``{bench, config:{model, mode}, ttft_s, boots_per_s, ...}`` with
``speedup_vs_traced`` on the warm row; ``scripts/ci.sh`` gates
``boots_per_s`` against the committed smoke baseline.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import time

from benchmarks.common import ROOT, cached

BENCH_JSON = os.path.join(ROOT, "BENCH_boot.json")
ARTIFACT = os.path.join(ROOT, "runs", "boot_ttft_artifact")
AOT_CACHE = os.path.join(ROOT, "runs", "boot_ttft_aotcache")
RATIO = 0.5
MARK = "BOOTCELL "

GRID = {"slots": 4, "max_len": 256, "prompt_len": 16, "n_new": 32}
SMOKE_GRID = {"slots": 2, "max_len": 64, "prompt_len": 8, "n_new": 8}


def ensure_artifact(path: str = ARTIFACT) -> str:
    """Build (once, in a child process) the llama-mini drank artifact the
    boot cells serve. Reuse is deliberate: the bench's claim is about boot
    mechanics, and all three cells share whatever artifact sits here."""
    if not os.path.exists(os.path.join(path, "compressed", "manifest.json")):
        print(_spawn(["--build-artifact", path], _child_env()).stdout,
              end="", flush=True)
    return path


def build_artifact(path: str) -> None:
    import jax

    from benchmarks.common import calib_batches
    from repro.configs import get_config
    from repro.core import compress as CC
    from repro.models import transformer as T

    cfg = get_config("llama-mini")
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    calib = calib_batches(cfg, n_samples=4, seq_len=32)
    ccfg = CC.CompressionConfig(method="drank", ratio=RATIO,
                                group_size=2, beta=0.3)
    comp, plan = CC.build_plan_and_params(params, cfg, ccfg, calib)
    CC.save_plan(path, comp, plan, cfg)
    print(f"  built boot artifact at {path} "
          f"({plan.summary['achieved_ratio']:.1%} removed)", flush=True)


# ---------------------------------------------------------------------------
# child: one boot cell in a fresh process
# ---------------------------------------------------------------------------

def run_cell(cell: str, artifact: str, grid: dict) -> None:
    import numpy as np

    from repro.configs import get_config
    from repro.serve.api import ServeOptions, load_engine
    from repro.serve.engine import Request

    cfg = get_config("llama-mini")
    opts = ServeOptions(arch="llama-mini", compressed_ckpt=artifact,
                        aot=(cell != "traced"),
                        batch=grid["slots"], max_len=grid["max_len"],
                        prompt_len=grid["prompt_len"], n_new=grid["n_new"])
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, size=(grid["prompt_len"],),
                          dtype=np.int32)
    t0 = time.perf_counter()
    cb = load_engine(opts)
    req = Request(rid=0, tokens=prompt, n_new=grid["n_new"])
    assert cb.submit(req)
    while not req.out:                 # first step admits: prefill emits
        cb.step()
    ttft = time.perf_counter() - t0
    res = cb.run_until_drained()
    assert res.status == "drained", res.status
    keys = ("aot_compiles", "aot_cache_hits", "aot_deser_failures",
            "aot_fallbacks", "prefill_retraces", "decode_retraces")
    print(MARK + json.dumps({
        "cell": cell, "ttft_s": ttft,
        "tokens": [int(t) for t in req.out],
        "stats": {k: cb.stats.get(k, 0) for k in keys}}), flush=True)


def _child_env() -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(ROOT, "src") + os.pathsep + ROOT
                         + os.pathsep + env.get("PYTHONPATH", ""))
    return env


def _spawn(args: list, env: dict) -> subprocess.CompletedProcess:
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.boot_ttft", *args],
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=1800)
    if proc.returncode != 0:
        raise RuntimeError(f"boot_ttft child {args} failed:\n{proc.stdout}"
                           f"\n{proc.stderr}")
    return proc


def _spawn_cell(cell: str, artifact: str, grid: dict) -> dict:
    env = _child_env()
    # JAX's own persistent compilation cache would silently warm the
    # "cold" cells; the only cache under test is serve/aot.py's
    env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    env["REPRO_AOT_CACHE"] = AOT_CACHE
    t0 = time.perf_counter()
    proc = _spawn(["--cell", cell, "--artifact", artifact,
                   "--grid", json.dumps(grid)], env)
    wall = time.perf_counter() - t0
    line = [ln for ln in proc.stdout.splitlines() if ln.startswith(MARK)]
    assert line, f"no {MARK!r} line from cell {cell}:\n{proc.stdout}"
    out = json.loads(line[-1][len(MARK):])
    out["proc_wall_s"] = round(wall, 2)
    return out


# ---------------------------------------------------------------------------
# parent: the three-cell experiment
# ---------------------------------------------------------------------------

def run(force: bool = False, smoke: bool = False):
    name = "boot_ttft" + ("_smoke" if smoke else "")
    grid = SMOKE_GRID if smoke else GRID

    def compute():
        artifact = ensure_artifact()
        shutil.rmtree(AOT_CACHE, ignore_errors=True)
        cells = {}
        for cell in ("traced", "aot_cold", "aot_warm"):
            cells[cell] = _spawn_cell(cell, artifact, grid)
            s = cells[cell]["stats"]
            print(f"  boot {cell}: ttft={cells[cell]['ttft_s']:.2f}s "
                  f"compiles={s['aot_compiles']} "
                  f"hits={s['aot_cache_hits']}", flush=True)
        # correctness before speed: registries may only change cost
        tok = cells["traced"]["tokens"]
        assert cells["aot_cold"]["tokens"] == tok, \
            (tok, cells["aot_cold"]["tokens"])
        assert cells["aot_warm"]["tokens"] == tok, \
            (tok, cells["aot_warm"]["tokens"])
        warm = cells["aot_warm"]["stats"]
        assert warm["aot_compiles"] == 0, warm
        assert warm["aot_cache_hits"] > 0, warm
        speedup = cells["traced"]["ttft_s"] / cells["aot_warm"]["ttft_s"]
        rows = []
        for cell, c in cells.items():
            row = {"bench": "boot_ttft",
                   "config": {"model": f"drank@{RATIO:.0%}", "mode": cell},
                   "ttft_s": round(c["ttft_s"], 3),
                   "boots_per_s": round(1.0 / c["ttft_s"], 3),
                   "aot_compiles": c["stats"]["aot_compiles"],
                   "aot_cache_hits": c["stats"]["aot_cache_hits"]}
            if cell == "aot_warm":
                row["speedup_vs_traced"] = round(speedup, 2)
            rows.append(row)
        print(f"  boot speedup warm-AOT vs traced: {speedup:.1f}x",
              flush=True)
        return {"rows": rows}

    out = cached(name, compute, force)
    write_bench_json(out["rows"])
    return out


def write_bench_json(rows, path: str = BENCH_JSON) -> str:
    with open(path, "w") as f:
        json.dump(rows, f, indent=1)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--cell", default="",
                    help=argparse.SUPPRESS)   # internal: child mode
    ap.add_argument("--artifact", default="", help=argparse.SUPPRESS)
    ap.add_argument("--grid", default="", help=argparse.SUPPRESS)
    ap.add_argument("--build-artifact", default="",
                    help=argparse.SUPPRESS)   # internal: child mode
    args = ap.parse_args(argv)
    if args.build_artifact:
        build_artifact(args.build_artifact)
        return 0
    if args.cell:
        run_cell(args.cell, args.artifact, json.loads(args.grid))
        return 0
    out = run(force=args.force, smoke=args.smoke)
    print(json.dumps(out["rows"], indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
