"""Shared benchmark plumbing: trained-model loading, data, PPL eval,
result caching (every bench caches to experiments/results/<name>.json so
the aggregate runner is resumable).

JAX is imported inside the helpers that need it, never at module level: a
bench whose parent process only spawns children (``boot_ttft``) must not
touch JAX, because on a TPU host the first process to do so holds the
chip and its children could not get it."""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RESULTS = os.path.join(ROOT, "experiments", "results")
RUNS = os.path.join(ROOT, "runs")

EVAL_SEED_STEP = 777_001        # disjoint from train steps and calib seed


def result_path(name: str) -> str:
    os.makedirs(RESULTS, exist_ok=True)
    return os.path.join(RESULTS, f"{name}.json")


def cached(name: str, fn, force: bool = False):
    path = result_path(name)
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    t0 = time.time()
    out = fn()
    out["_wall_s"] = round(time.time() - t0, 1)
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    return out


def data_config(cfg, seq_len: int = 128, seed: int = 0):
    from repro.data.synthetic import DataConfig
    return DataConfig(vocab_size=cfg.vocab_size, seq_len=seq_len,
                      global_batch=8, seed=seed)


def load_trained(arch: str = "llama-mini", run: str = "mini_mha",
                 overrides: Optional[Dict] = None):
    """Load the latest checkpoint of a background training run."""
    import jax

    from repro.ckpt import store
    from repro.configs import get_config
    from repro.train import step as TS
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    state, _ = TS.init_train_state(cfg, jax.random.PRNGKey(0))
    ckpt_dir = os.path.join(RUNS, run)
    step, state = store.restore(ckpt_dir, state)
    return cfg, state.params, step


def eval_batches(cfg, n_batches: int = 4, batch: int = 8,
                 seq_len: int = 128, seed: int = 0) -> List[Dict]:
    import jax.numpy as jnp
    import numpy as np

    from repro.data.synthetic import SyntheticLM
    lm = SyntheticLM(data_config(cfg, seq_len, seed))
    out = []
    for i in range(n_batches):
        rows = np.arange(i * batch, (i + 1) * batch)
        out.append({"tokens": jnp.asarray(
            lm.sample_rows(EVAL_SEED_STEP, rows))})
    return out


def calib_batches(cfg, n_samples: int = 16, batch: int = 8,
                  seq_len: int = 128, seed: int = 0) -> List[Dict]:
    import jax.numpy as jnp

    from repro.data.synthetic import calibration_batches
    dcfg = data_config(cfg, seq_len, seed)
    return [{"tokens": jnp.asarray(b["tokens"])}
            for b in calibration_batches(dcfg, n_samples, batch)]


def ppl_of(params, cfg, batches) -> Dict[str, float]:
    from repro.train import step as TS
    return TS.evaluate_ppl(params, cfg, batches)


def calib_max_rel_err(col, oracle) -> float:
    """Worst relative error of a captured Collector vs the eager fp64
    oracle, over every tag's Gram AND abs-sum statistics. Tags captured
    as streaming-whitening factors compare through RᵀR (the Gram the
    factor represents) — shared by the capture benches so the CI parity
    bar stays uniform across the single-device and mesh paths."""
    import numpy as np
    worst = 0.0
    for tag in oracle.gram:
        got = (col.gram[tag] if tag in col.gram
               else col.chol[tag].T @ col.chol[tag])
        ref = oracle.gram[tag]
        worst = max(worst, float(np.abs(got - ref).max()
                                 / (np.abs(ref).max() + 1e-12)))
        aref = oracle.absmean[tag]
        worst = max(worst, float(np.abs(col.absmean[tag] - aref).max()
                                 / (np.abs(aref).max() + 1e-12)))
    return worst
