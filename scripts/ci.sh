#!/usr/bin/env bash
# CI gate: lint (if ruff is installed) + fast-lane tests + benchmark smokes
# (interpret-mode Pallas — CI runners have no TPU) + bench regression gate
# against committed baselines. Run from anywhere.
#
# The fast lane runs `-m "not slow"`; the tier-1 full suite (ROADMAP.md)
# is plain `pytest -q` and still covers the slow-marked sweeps.
# Set BENCH_GATE=off to skip the regression diff (e.g. exotic hardware).
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# One persistent XLA compilation cache for every step in this script (and,
# via the workflow's cache action, across CI runs): each jit program is
# compiled once, then replayed. The same default root as
# src/repro/compile_cache.py. The boot-TTFT bench turns JAX's cache off in
# its boot cells — its cold/warm boots must stay honest.
export JAX_COMPILATION_CACHE_DIR="${JAX_COMPILATION_CACHE_DIR:-$PWD/.cache/jax}"
mkdir -p "$JAX_COMPILATION_CACHE_DIR"

if command -v ruff >/dev/null 2>&1; then
  echo "== lint (ruff) =="
  ruff check .
else
  echo "== lint skipped (ruff not installed; the CI lint job enforces it) =="
fi

echo "== fast-lane tests (-m 'not slow') =="
python -m pytest -x -q -m "not slow"

echo "== public-API doctests =="
python -m pytest -q --doctest-modules \
  src/repro/core/compress.py src/repro/core/capture.py \
  src/repro/serve/engine.py src/repro/serve/api.py

echo "== README command smoke =="
python scripts/check_readme.py

echo "== observability chaos drill (traced poison + flight dump) =="
# A seed-deterministic poisoned request must fail typed while the run
# still drains; the trace must be valid Chrome-trace JSON (uploaded as a
# workflow artifact) and the flight recorder must dump an artifact that
# identifies the poisoned rid and the rung it failed at — from the dump
# alone, no logs.
rm -rf runs/ci_chaos && mkdir -p runs/ci_chaos
python -m repro.launch.serve --arch llama-mini \
  --requests 4 --n-new 4 --prompt-len 4 --batch 2 --max-len 64 \
  --fault-plan '{"seed": 3, "poison_rids": [2]}' --max-retries 1 \
  --trace-out runs/ci_chaos/trace.json \
  --metrics-json runs/ci_chaos/metrics.json \
  --flightrec-dir runs/ci_chaos
python - <<'EOF'
import glob
import json

from repro.obs.flightrec import validate_dump
from repro.obs.trace import validate_chrome_trace

trace = json.load(open("runs/ci_chaos/trace.json"))
errs = validate_chrome_trace(trace)
assert errs == [], errs
names = {e["name"] for e in trace["traceEvents"]}
assert {"engine_step", "decode_step", "prefill",
        "request"} <= names, sorted(names)

dumps = sorted(glob.glob("runs/ci_chaos/flightrec-*.json"))
assert dumps, "poison failure produced no flight-recorder dump"
dump = json.load(open(dumps[0]))
errs = validate_dump(dump)
assert errs == [], errs
assert dump["reason"] == "failed_poison", dump["reason"]
assert dump["context"]["rid"] == 2, dump["context"]
assert dump["context"]["fault_plan"]["poison_rids"] == [2]
assert any(ev["kind"] == "poison" and 2 in ev["rids"]
           for ev in dump["events"]), "no poison event in the ring"

snap = json.load(open("runs/ci_chaos/metrics.json"))
assert snap["schema"] == "repro.serve.metrics/v2", snap.get("schema")
assert snap["counters"]["poison_failures"] == 1, snap["counters"]
print(f"ok: chaos drill — {len(trace['traceEvents'])} trace events, "
      f"dump {dumps[0]} names rid=2 at rung "
      f"{dump['context']['rank_level']}")
EOF

echo "== decode-path benchmark smoke =="
python -m benchmarks.fig4_decode_path --smoke --force

echo "== calibration-capture benchmark smoke =="
python -m benchmarks.calib_capture --smoke --force

echo "== compression-math benchmark smoke =="
python -m benchmarks.compress_path --smoke --force

echo "== sharded-calibration benchmark smoke (8-device host mesh) =="
# --force even though the README smoke above usually just ran this bench:
# relying on that cross-file coincidence would let an edited README leave
# a stale cache re-emitting numbers the current commit never produced
python -m benchmarks.calib_sharded --smoke --force

echo "== serve-degradation benchmark smoke (elastic-rank ladder) =="
python -m benchmarks.serve_degrade --smoke --force

echo "== boot-TTFT benchmark smoke (AOT front door) =="
python -m benchmarks.boot_ttft --smoke --force

echo "== paged-KV benchmark smoke (block-table pool + prefix reuse) =="
python -m benchmarks.serve_paged --smoke --force

echo "== BENCH json schemas =="
python - <<'EOF'
import json
rows = json.load(open("BENCH_decode.json"))
assert rows, "no decode benchmark rows"
for r in rows:
    assert {"bench", "config", "tokens_per_s", "ms_per_step"} <= set(r), r
models = {r["config"]["model"] for r in rows}
assert "dense" in models and len(models) > 1, models
print(f"ok: BENCH_decode.json {len(rows)} rows, models={sorted(models)}")

rows = json.load(open("BENCH_calib.json"))
assert rows, "no calib benchmark rows"
for r in rows:
    assert {"bench", "config", "tokens_per_s", "ms_per_batch"} <= set(r), r
paths = {r["config"]["path"] for r in rows}
assert {"eager-host", "jit-device", "pallas-interpret"} <= paths, paths
err = max(r.get("max_rel_err", 0.0) for r in rows)
assert err < 1e-4, f"streaming capture parity broke: {err}"
print(f"ok: BENCH_calib.json {len(rows)} rows, paths={sorted(paths)}, "
      f"max_rel_err={err:.1e}")

rows = json.load(open("BENCH_calib_sharded.json"))
assert rows, "no sharded-calib benchmark rows"
for r in rows:
    assert {"bench", "config", "tokens_per_s", "ms_per_batch",
            "max_rel_err"} <= set(r), r
paths = {r["config"]["path"] for r in rows}
assert {"mesh-replicated", "mesh-sharded", "mesh-whiten"} <= paths, paths
assert all(r["config"]["devices"] == 8 for r in rows), rows
err = max(r["max_rel_err"] for r in rows)
assert err < 1e-4, f"mesh capture parity broke: {err}"
print(f"ok: BENCH_calib_sharded.json {len(rows)} rows, "
      f"paths={sorted(paths)}, max_rel_err={err:.1e}")

rows = json.load(open("BENCH_compress.json"))
assert rows, "no compress benchmark rows"
for r in rows:
    assert {"bench", "config", "params_per_s", "ms_per_group"} <= set(r), r
paths = {r["config"]["path"] for r in rows}
assert {"host-eager", "jit-device", "randomized"} <= paths, paths
exact_err = max(r["max_rel_err"] for r in rows
                if r["config"]["path"] == "jit-device")
assert exact_err < 1e-3, f"device compression math diverged: {exact_err}"
# the committed baseline records >=10x on a quiet runner; at CI time only
# assert a loose floor so scheduler noise can't flake the lane — and only
# when perf gating is on at all (BENCH_GATE=off covers exotic hardware)
import os
speedups = [r["speedup"] for r in rows
            if r["config"]["path"] == "jit-device" and "speedup" in r]
if os.environ.get("BENCH_GATE", "on") != "off":
    assert speedups and max(speedups) >= 5.0, \
        f"jit-device compression speedup collapsed: {speedups}"
top = max(speedups) if speedups else float("nan")
print(f"ok: BENCH_compress.json {len(rows)} rows, paths={sorted(paths)}, "
      f"exact_err={exact_err:.1e}, speedup={top:.1f}x")

rows = json.load(open("BENCH_serve_degrade.json"))
assert rows, "no serve-degrade benchmark rows"
for r in rows:
    assert {"bench", "config", "tokens_per_s", "ms_per_step",
            "ttft_p50_ms"} <= set(r), r
pinned = {r["config"]["level"]: r for r in rows
          if r["config"]["mode"] == "pinned"}
assert set(pinned) >= {0, 1, 2}, sorted(pinned)
# rank must genuinely drop down the ladder (pow2 buckets, ISSUE 6)
rmax = [pinned[lv]["rank_max"] for lv in sorted(pinned)]
assert rmax == sorted(rmax, reverse=True) and rmax[-1] < rmax[0], rmax
elastic = [r for r in rows if r["config"]["mode"] == "elastic"]
assert elastic and elastic[0]["rank_residency"], elastic
# the tracing-overhead pair (ISSUE 8) must be present; the ratio
# itself is perf and is gated below only when BENCH_GATE is on
tr = {r["config"]["mode"] for r in rows
      if str(r["config"]["mode"]).startswith("trace-")}
assert tr == {"trace-off", "trace-on"}, sorted(tr)
print(f"ok: BENCH_serve_degrade.json {len(rows)} rows, "
      f"rank ladder {rmax}, elastic residency "
      f"{elastic[0]['rank_residency']}")

rows = json.load(open("BENCH_boot.json"))
assert rows, "no boot benchmark rows"
for r in rows:
    assert {"bench", "config", "ttft_s", "boots_per_s",
            "aot_compiles", "aot_cache_hits"} <= set(r), r
cells = {r["config"]["mode"]: r for r in rows}
assert {"traced", "aot_cold", "aot_warm"} <= set(cells), sorted(cells)
warm = cells["aot_warm"]
# the AOT contract, not a perf claim: a warm boot never compiles
assert warm["aot_compiles"] == 0 and warm["aot_cache_hits"] > 0, warm
# the acceptance bar (ISSUE 7): warm-AOT first token >=5x faster than the
# tracing boot — perf, so honored only when perf gating is on at all
if os.environ.get("BENCH_GATE", "on") != "off":
    assert warm.get("speedup_vs_traced", 0.0) >= 5.0, warm
print(f"ok: BENCH_boot.json {len(rows)} rows, warm-AOT "
      f"{warm['ttft_s']}s to first token "
      f"({warm.get('speedup_vs_traced', float('nan'))}x vs traced)")

rows = json.load(open("BENCH_serve_paged.json"))
assert rows, "no paged-KV benchmark rows"
for r in rows:
    assert {"bench", "config", "tokens_per_s", "ms_per_step",
            "peak_kv_mib"} <= set(r), r
cells = {r["config"]["mode"]: r for r in rows}
assert set(cells) == {"contiguous", "paged", "paged+prefix"}, sorted(cells)
# the memory claim, not a perf claim: the paged pool's peak block
# footprint stays below the contiguous pool's full allocation
contig = cells["contiguous"]["peak_kv_mib"]
for mode in ("paged", "paged+prefix"):
    assert cells[mode]["peak_kv_mib"] < contig, (mode, cells[mode], contig)
# prefix reuse must actually fire on the shared-header group
assert cells["paged+prefix"]["prefix_hits"] > 0, cells["paged+prefix"]
print(f"ok: BENCH_serve_paged.json {len(rows)} rows, peak KV "
      f"{contig:.2f} -> {cells['paged']['peak_kv_mib']:.2f} MiB, "
      f"prefix_hits={cells['paged+prefix']['prefix_hits']}")
EOF

# Baselines carry a per-machine _calibration row (scripts/bench_gate.py
# --update): at gate time a fixed numpy probe rescales the recorded
# tokens/s to THIS runner's speed (clamped 3x), so a slower machine no
# longer needs BENCH_GATE_THRESHOLD loosened by hand. The threshold now
# only absorbs run-to-run noise; BENCH_GATE=off still skips entirely.
if [ "${BENCH_GATE:-on}" != "off" ]; then
  THRESH="${BENCH_GATE_THRESHOLD:-0.25}"
  echo "== bench regression gate (>${THRESH} scaled tokens/s drop fails) =="
  python scripts/bench_gate.py BENCH_decode.json \
    benchmarks/baselines/BENCH_decode.smoke.json --threshold "$THRESH"
  python scripts/bench_gate.py BENCH_calib.json \
    benchmarks/baselines/BENCH_calib.smoke.json --threshold "$THRESH"
  python scripts/bench_gate.py BENCH_compress.json \
    benchmarks/baselines/BENCH_compress.smoke.json --threshold "$THRESH" \
    --metric params_per_s
  # the 8-fake-device mesh oversubscribes the 2-core runner ~4x: even
  # best-of-3 windows swing ~2x under co-tenancy, so gate at 3x the base
  # threshold — still catches a broken capture path (those regress by
  # orders of magnitude) without flaking the lane; parity is gated hard
  # above regardless
  python scripts/bench_gate.py BENCH_calib_sharded.json \
    benchmarks/baselines/BENCH_calib_sharded.smoke.json \
    --threshold "$(python -c "print(min(0.9, 3*float('$THRESH')))")"
  python scripts/bench_gate.py BENCH_serve_degrade.json \
    benchmarks/baselines/BENCH_serve_degrade.smoke.json \
    --threshold "$THRESH"
  # tracing overhead: enabled tracing must keep >=95% of disabled
  # tok/s. Both rows come from one interleaved best-of-N run in one
  # process, so the ratio holds even when absolute tok/s swings under
  # co-tenancy — no baseline file, no machine calibration
  python scripts/bench_gate.py BENCH_serve_degrade.json \
    --ratio mode=trace-on mode=trace-off --min-ratio 0.95
  # boot cells are one-shot subprocesses (no best-of-N window to hide
  # scheduler noise), so gate at 2x the base threshold; the >=5x
  # warm-vs-traced ratio is asserted hard in the schema block above
  python scripts/bench_gate.py BENCH_boot.json \
    benchmarks/baselines/BENCH_boot.smoke.json \
    --metric boots_per_s \
    --threshold "$(python -c "print(min(0.9, 2*float('$THRESH')))")"
  python scripts/bench_gate.py BENCH_serve_paged.json \
    benchmarks/baselines/BENCH_serve_paged.smoke.json --threshold "$THRESH"
else
  echo "== bench regression gate skipped (BENCH_GATE=off) =="
fi

echo "CI OK"
