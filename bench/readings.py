"""Readings from which a cell's correctness limits are set. For each seed,
a whole run of the cell (engine built afresh, window at the cell's own
load), judged as the benchmark judges it; then, on the same sampled
requests and in the same process:

* ``--int8``: the widest gap of the token that the float32 reference
  computed at int8 puts first (the control put in the program's place);
* for a compressed configuration, the factor misfit of planted faults in
  place of the served factors: ``random`` (every factor redrawn, same
  shapes and scale), ``zero`` (every ``C`` zero), ``one_layer`` (one layer's
  factors redrawn), ``negated`` (every ``C`` negated).

    python3 bench/readings.py --workload <cell> --seconds <s> --seeds 1,2,3 [--int8]

Compiling the int8 control of the compressed model holds much of a
one-chip host's 40 GiB; give such a cell one seed per process.

Prints one JSON line per seed, then a summary line. Not part of the
benchmark's own runs.
"""
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

FAULTS = ("random", "zero", "one_layer", "negated")


def planted(served, fault: str, seed: int):
    """The served factors (reference layout, numpy) with ``fault``."""
    import numpy as np
    rng = np.random.default_rng([int(seed) & (2 ** 63 - 1), 11])
    hit = rng.integers(len(served["layers"]))
    layers = []
    for i, lp in enumerate(served["layers"]):
        out = dict(lp)
        for k, f in lp.items():
            if not isinstance(f, dict) or "B" not in f:
                continue
            B, C = f["B"], f["C"]
            if fault == "zero":
                C = np.zeros_like(C)
            elif fault == "negated":
                C = -C
            elif fault == "random" or (fault == "one_layer" and i == hit):
                B = (rng.standard_normal(B.shape) * B.std()).astype(B.dtype)
                C = (rng.standard_normal(C.shape) * C.std()).astype(C.dtype)
            out[k] = {"B": B, "C": C}
        layers.append(out)
    return dict(served, layers=layers)


def main(argv=None) -> int:
    import argparse
    # the caches bench/run.py uses, so that a checkout's artifact and
    # compiled programs serve both
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".cache",
                                                           "jax")
    os.environ.pop("REPRO_AOT_CACHE", None)

    import jax

    from bench import harness
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", default="8")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--int8", action="store_true")
    a = ap.parse_args(argv)
    rows = []
    for seed in a.seeds.split(","):
        t = time.perf_counter()
        m = harness.measure(["--workload", a.workload, "--seed", seed,
                             "--seconds", a.seconds, "--trace", "0"])
        checks = harness.judge(m)
        row = {"seed": int(seed), "attempted": m.attempted,
               "failed": m.failed,
               **{k: c["value"] for k, c in checks.items()}}
        config, ref = m.config, m.ref
        sizes = ref.sizes(m.cfg)
        max_len = config["engine"]["max_len"]
        served = harness.reference_params(m)
        with jax.default_matmul_precision("highest"):
            if a.int8:
                cg = harness.gaps_of(ref, jax.device_put(served), sizes,
                                     m.picked, max_len, ref.int8_quant)
                row["int8_token_gap"] = max(cg)
            if config.get("compression"):
                dense = jax.device_put(harness.dense_reference_params(m))
                for fault in FAULTS:
                    bad = jax.device_put(planted(served, fault, m.seed))
                    fits = harness.misfits_of(ref, dense, bad, sizes,
                                              m.picked[0], max_len)
                    row[f"{fault}_factor_misfit"] = max(
                        v for r in fits for v in r.values())
                    del bad
                del dense
        del served
        row["run_s"] = time.perf_counter() - t
        rows.append(row)
        print(json.dumps(row), flush=True)
    keys = [k for k in rows[0] if k not in ("seed", "run_s")]
    print(json.dumps({"workload": a.workload, "seeds": len(rows),
                      **{f"{k}_max": max(r[k] for r in rows) for k in keys},
                      **{f"{k}_min": min(r[k] for r in rows)
                         for k in keys}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
