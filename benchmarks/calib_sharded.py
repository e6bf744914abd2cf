"""Sharded streaming-calibration capture on a multi-device host mesh
(the PR-5 tentpole; DESIGN.md §1.6).

This is a CPU rehearsal of the mesh paths: it sets ``JAX_PLATFORMS=cpu``
(unless the caller set a platform) and
``--xla_force_host_platform_device_count=8`` BEFORE jax initializes,
so the (data=8) mesh runs on eight virtual CPU devices with real
per-device buffers. Its timings are CPU numbers. The chip version of the
mesh-capture parity check is ``python chip_smoke.py --chips 4``. It
measures three capture routes per grid cell:

  mesh-replicated   per-shard partial Grams psum'd into replicated (D,D)
                    accumulators (the PR-2 layout, now pipelined)
  mesh-sharded      (D,D) accumulators row-sharded over the data axis —
                    no device materializes a full (D,D); the fold
                    all-gathers activation rows and GEMMs its row block
  mesh-whiten       streaming whitening per shard (QR updates, no Gram
                    anywhere), factors tree-reduced at finalize

Every row records ``max_rel_err`` against the eager fp64 oracle (grams /
RᵀR of factors), so the CI smoke run re-proves mesh parity on every push.
If the process already initialized jax with fewer devices (e.g. under
``benchmarks.run``), the bench re-executes itself in a subprocess and
reads the cached result.

Emits ``BENCH_calib_sharded.json`` at the repo root with the schema
``{bench, config, tokens_per_s, ms_per_batch, max_rel_err}``.
"""
from __future__ import annotations

import os

if __name__ == "__main__":
    # only when run as a standalone process: the flag must land before
    # jax's backend init, and must NOT leak into sibling benches when
    # this module is merely imported by benchmarks.run (their timings
    # assume the real single device — run() re-execs a subprocess then)
    if "--xla_force_host_platform_device_count" not in os.environ.get(
            "XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse      # noqa: E402
import json          # noqa: E402
import subprocess    # noqa: E402
import sys           # noqa: E402
import time          # noqa: E402

import jax           # noqa: E402

from benchmarks.common import (ROOT, cached,                # noqa: E402
                               calib_max_rel_err as _max_rel_err,
                               result_path)
from repro.configs import get_config                        # noqa: E402
from repro.core.capture import (StreamingCalibrator,  # noqa: E402
                                to_list_params)
from repro.core.compress import calibrate                   # noqa: E402
from repro.launch.mesh import make_host_mesh                # noqa: E402
from repro.models import transformer as T                   # noqa: E402

BENCH_JSON = os.path.join(ROOT, "BENCH_calib_sharded.json")
DEVICES = 8

GRID = {"batch": 8, "seq": 128, "n_batches": 8, "devices": DEVICES}
SMOKE_GRID = {"batch": 8, "seq": 32, "n_batches": 3, "devices": DEVICES}
PARITY_TOL = 1e-4


def _cfg(smoke: bool):
    cfg = get_config("llama-mini")
    if smoke:
        cfg = cfg.replace(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                          head_dim=16, d_ff=128, vocab_size=256)
    return cfg


def _batches(cfg, grid):
    key = jax.random.PRNGKey(7)
    return [{"tokens": jax.random.randint(
        jax.random.fold_in(key, i), (grid["batch"], grid["seq"]),
        0, cfg.vocab_size)} for i in range(grid["n_batches"])]


def _run_inprocess(smoke: bool):
    grid = SMOKE_GRID if smoke else GRID
    cfg = _cfg(smoke)
    mesh = make_host_mesh(data=DEVICES, model=1)
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    lp = to_list_params(params, cfg)
    batches = _batches(cfg, grid)
    oracle = calibrate(lp, cfg, batches, streaming=False)
    tokens = grid["batch"] * grid["seq"] * grid["n_batches"]
    rounds = 10 if smoke else 2
    rows = []

    paths = {
        "mesh-replicated": dict(shard_grams_above=0),
        "mesh-sharded": dict(shard_grams_above=1),
        "mesh-whiten": dict(whiten_tags=True),
    }
    for path, kw in paths.items():
        # pass 1 (untimed): pays compile, covers every batch once — the
        # finalized stats feed the parity bar vs the eager fp64 oracle
        cal = StreamingCalibrator(lp, cfg, mesh=mesh, **kw)
        for b in batches:
            cal.ingest(b)
        err = _max_rel_err(cal.finalize(), oracle)
        assert err < PARITY_TOL, f"{path} diverged: {err:.2e}"
        # pass 2 (timed): finalize reset the accumulators → steady state;
        # repeat the batch list to widen the window past scheduler noise
        # and take the best of 3 windows — the 8-fake-device mesh
        # oversubscribes this container ~4×, so single windows swing 2-3×
        # (same best-of-N convention as benchmarks/compress_path.py)
        dt = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(rounds):
                for b in batches:
                    cal.ingest(b)
            cal.sync()
            dt = min(dt, time.perf_counter() - t0)
        n_timed = grid["n_batches"] * rounds
        rows.append({
            "bench": "calib_sharded",
            "config": {"path": path, **grid},
            "tokens_per_s": tokens * rounds / dt,
            "ms_per_batch": dt / n_timed * 1000.0,
            "max_rel_err": err,
        })
        print(f"  calib {path:16s}: {rows[-1]['tokens_per_s']:8.0f} tok/s "
              f"({rows[-1]['ms_per_batch']:.0f} ms/batch, "
              f"rel err {err:.1e})", flush=True)
    return {"rows": rows}


def run(force: bool = False, smoke: bool = False):
    name = "calib_sharded" + ("_smoke" if smoke else "")
    if len(jax.devices()) < DEVICES:
        # jax already initialized without the forced device count (e.g.
        # benchmarks.run imported other benches first, or the parent env
        # pinned a smaller count) — re-exec so the XLA flag lands before
        # backend init, then read the cache
        if os.environ.get("_CALIB_SHARDED_CHILD"):
            raise RuntimeError(
                f"re-exec'd child still sees {len(jax.devices())} devices "
                f"< {DEVICES}; check XLA_FLAGS")
        if force or not os.path.exists(result_path(name)):
            args = [sys.executable, "-m", "benchmarks.calib_sharded"]
            if smoke:
                args.append("--smoke")
            if force:
                args.append("--force")
            # strip any caller-pinned force-device flag so the child's
            # __main__ guard re-adds it at 8 (a preset smaller value
            # would otherwise recurse forever)
            flags = " ".join(
                f for f in os.environ.get("XLA_FLAGS", "").split()
                if "--xla_force_host_platform_device_count" not in f)
            subprocess.run(args, check=True, cwd=ROOT, env={
                **os.environ,
                "XLA_FLAGS": flags,
                "_CALIB_SHARDED_CHILD": "1",
                "PYTHONPATH": os.path.join(ROOT, "src") + (
                    os.pathsep + os.environ["PYTHONPATH"]
                    if os.environ.get("PYTHONPATH") else "")})
        with open(result_path(name)) as f:
            out = json.load(f)
        write_bench_json(out["rows"])
        return out

    out = cached(name, lambda: _run_inprocess(smoke), force)
    write_bench_json(out["rows"])
    return out


def write_bench_json(rows, path: str = BENCH_JSON) -> str:
    payload = [{"bench": r["bench"], "config": r["config"],
                "tokens_per_s": r["tokens_per_s"],
                "ms_per_batch": r["ms_per_batch"],
                "max_rel_err": r["max_rel_err"]} for r in rows]
    with open(path, "w") as f:
        json.dump(payload, f, indent=1)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + grid (CI)")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args(argv)
    out = run(force=args.force, smoke=args.smoke)
    for r in out["rows"]:
        c = r["config"]
        print(f"  {c['path']:16s} b={c['batch']} s={c['seq']} "
              f"n={c['n_batches']} x{c['devices']}dev "
              f"{r['tokens_per_s']:8.0f} tok/s "
              f"(err {r['max_rel_err']:.1e})")
    print(f"  wrote {BENCH_JSON}")
    return out


if __name__ == "__main__":
    main()
