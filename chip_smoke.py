#!/usr/bin/env python3
"""Smoke test of the main path on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the multi-chip paths, on four chips

One chip: SmolLM-360M at its published widths (32 layers, d_model 960, 15
heads over 5 KV heads, vocab 49152), random weights from ``--seed``, goes
through the repo's own entry points:

  device    the first JAX device must be a TPU; there is no CPU fallback.
  kernels   each Pallas kernel of the main path runs once with
            interpret=False at SmolLM-360M widths, against its
            ``kernels/ref.py`` oracle.
  compress  streaming calibration (the Pallas Gram kernel), D-Rank
            allocation at ratio 0.2, factorization on the device, artifact
            saved under runs/; the factors of two full-width groups are
            checked against the host fp64 oracle.
  serve     boot from the artifact with AOT executables and serve 16
            requests through the continuous batcher, with the contiguous
            and with the paged+prefix KV cache. Every served token is
            checked against a float32 teacher-forced forward of the same
            weights. A second boot from the same AOT cache must compile
            nothing.

Four chips (``--chips 4``): only the paths that exist across chips, at
SmolLM-360M widths with the depth cut to ``CUT_LAYERS`` — calibration on
a (data=4) mesh with sharded Grams against the one-device capture, and
four replicas, one per device, behind the Router against a single
replica.

Each phase prints one line of its numbers. Times are set-up (compiles
included) on the host clock, not speeds. Any failed check exits non-zero;
the last line of a passing run is the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(ROOT, "runs", "chip_smoke")       # git-ignored
ARCH = "smollm-360m"

# kernels: max |kernel - oracle| / max |oracle|, bf16 operands (the
# interpret-mode sweeps in tests/test_kernels.py use the same bar)
KERNEL_TOL = 2e-2
# compress: rank-k reconstruction B·C against the host fp64 oracle, the
# bar of benchmarks/compress_path.py
PARITY_TOL = 1e-3
# serve: the served token's float32 reference logit must lie within this
# many standard deviations (of that position's reference logits) of the
# reference maximum. bf16 serving moves near-ties; a wrong cache or
# position lands a token several deviations below the maximum.
MARGIN_STD = 0.5
# four chips: mesh capture against the one-device capture, the bar of
# benchmarks/calib_sharded.py
CALIB_TOL = 1e-4
# depth of the four-chip checks: every layer shards its Grams and places
# its weights the same way, and each layer adds compile time on all four
CUT_LAYERS = 4

CALIB_SAMPLES, CALIB_SEQ, CALIB_BATCH = 32, 512, 8
RATIO = 0.2
BATCH, MAX_LEN, REQUESTS, PROMPT, N_NEW, KV_BLOCK = 8, 512, 16, 128, 32, 16


def report(phase: str, **nums) -> None:
    print(f"{phase}: " + " ".join(f"{k}={v}" for k, v in nums.items()),
          flush=True)


def check(ok: bool, msg: str) -> None:
    if not ok:
        sys.exit(f"chip_smoke: FAILED: {msg}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def phase_device(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX reports platform "
                 f"{devs[0].platform!r}); this script runs only on a TPU")
    check(len(devs) >= chips, f"--chips {chips} but JAX sees {len(devs)}")
    report("device", platform=devs[0].platform,
           kind=repr(devs[0].device_kind), count=len(devs))
    return devs[0]


def phase_kernels(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.kernels import ops, ref

    check(ops._on_tpu(), "kernels would run in interpret mode")
    cfg = get_config(ARCH)
    D, H, KV, HD, FF = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                        cfg.head_dim, cfg.d_ff)
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def rnd(shape, scale=1.0, dtype=bf):
        return (scale * jax.random.normal(next(keys), shape)).astype(dtype)

    lengths = jnp.asarray([1, 37, 128, 129, 300, 511, MAX_LEN, 0],
                          dtype=jnp.int32)
    nb = MAX_LEN // KV_BLOCK
    arena_k = rnd((BATCH * nb + 1, KV_BLOCK, KV, HD))
    arena_v = rnd((BATCH * nb + 1, KV_BLOCK, KV, HD))
    table = jnp.asarray(1 + np.random.default_rng(seed).permutation(
        BATCH * nb).reshape(BATCH, nb), dtype=jnp.int32)
    x_gram = rnd((CALIB_BATCH * CALIB_SEQ, D)).astype(jnp.float32)
    x_dec, x_pre = rnd((BATCH, D)), rnd((BATCH * PROMPT, D))
    wb, wc = rnd((D, 300), 0.05), rnd((300, FF), 0.05)
    q, k, v = rnd((BATCH, PROMPT, H, HD)), rnd((BATCH, PROMPT, KV, HD)), \
        rnd((BATCH, PROMPT, KV, HD))
    qd, kc, vc = rnd((BATCH, H, HD)), rnd((BATCH, MAX_LEN, KV, HD)), \
        rnd((BATCH, MAX_LEN, KV, HD))

    def paged_oracle(q, ka, va, n, tbl):
        def gathered(arena):
            return arena[tbl.reshape(-1)].reshape(BATCH, MAX_LEN, KV, HD)
        return ref.decode_attention(q, gathered(ka), gathered(va), n)

    # name: (kernel, oracle, arguments)
    cases = {
        "gram": (ops.gram, ref.gram, (x_gram,)),
        "lowrank_gemv": (ops.lowrank_matmul, ref.lowrank_matmul,
                         (x_dec, wb, wc)),
        "lowrank_matmul": (ops.lowrank_matmul, ref.lowrank_matmul,
                           (x_pre, wb, wc)),
        "flash_prefill": (ops.flash_attention, ref.flash_attention,
                          (q, k, v)),
        "decode": (ops.decode_attention, ref.decode_attention,
                   (qd, kc, vc, lengths)),
        "decode_paged": (ops.decode_attention_paged, paged_oracle,
                         (qd, arena_k, arena_v, lengths, table)),
    }
    errs = {}
    for name, (kernel, oracle, args) in cases.items():
        got = jax.jit(kernel)(*args).astype(jnp.float32)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args).astype(jnp.float32)
        errs[name] = float(jnp.max(jnp.abs(got - want))
                           / (jnp.max(jnp.abs(want)) + 1e-6))
    report("kernels", tol=KERNEL_TOL,
           **{f"{k}_max_rel_err": f"{e:.3e}" for k, e in errs.items()})
    bad = {k: e for k, e in errs.items() if not e <= KERNEL_TOL}
    check(not bad, f"kernels off their oracles: {bad}")


def _node(tree, path):
    for p in path:
        tree = tree[p]
    return tree


def phase_compress(seed: int, artifact: str) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import compress as CC
    from repro.core import numerics as num
    from repro.core.capture import to_list_params
    from repro.core.groups import enumerate_matrices
    from repro.data.synthetic import DataConfig, calibration_batches
    from repro.models import transformer as T

    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    params, _ = T.init_model(cfg, jax.random.PRNGKey(seed))
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=CALIB_SEQ,
                      global_batch=CALIB_BATCH, seed=seed)
    calib = [{"tokens": jnp.asarray(b["tokens"])} for b in
             calibration_batches(dcfg, CALIB_SAMPLES, CALIB_BATCH)]
    lp = to_list_params(params, cfg)
    col = CC.calibrate(lp, cfg, calib, streaming=True)
    t_calib = time.perf_counter() - t0
    ccfg = CC.CompressionConfig(method="drank", ratio=RATIO, group_size=2,
                                beta=0.3)
    comp, plan = CC.build_plan_and_params(params, cfg, ccfg, calib,
                                          collector=col, device=True)
    shutil.rmtree(artifact, ignore_errors=True)
    CC.save_plan(artifact, comp, plan, cfg)
    t_total = time.perf_counter() - t0
    # the device factors of layer 0's gate (d_in < n·d_out: eigh on the
    # input side) and down (d_in > n·d_out: eigh on the output side)
    # projections against the host fp64 whitened SVD at the same rank
    errs = {}
    for r in enumerate_matrices(lp, cfg):
        if r.layer != 0 or r.mtype not in ("gate", "down"):
            continue
        fac = _node(comp, r.path)
        got = (np.asarray(fac["B"], np.float64)
               @ np.asarray(fac["C"], np.float64))
        wh = num.cholesky_whitener(col.gram[r.tag], ccfg.damp)
        U, s, Vt = num.whitened_svd(
            np.asarray(_node(lp, r.path)["w"], np.float64), wh)
        B0, C0 = num.truncate_factors(U, s, Vt, fac["B"].shape[1], wh)
        want = B0 @ C0
        errs[f"{r.mtype}{r.d_in}x{r.d_out}"] = float(
            np.abs(got - want).max() / np.abs(want).max())
    ratio = plan.summary["achieved_ratio"]
    report("compress", calib_tokens=CALIB_SAMPLES * CALIB_SEQ,
           achieved_ratio=f"{ratio:.4f}", groups=len(plan.groups),
           tol=PARITY_TOL,
           **{f"{k}_max_rel_err": f"{e:.3e}" for k, e in errs.items()},
           calib_setup_s=f"{t_calib:.1f}", total_setup_s=f"{t_total:.1f}")
    check(len(errs) == 2, f"parity groups not found: {sorted(errs)}")
    bad = {k: e for k, e in errs.items() if not e <= PARITY_TOL}
    check(not bad, f"device factors off the fp64 oracle: {bad}")
    check(abs(ratio - RATIO) < 0.02, f"achieved ratio {ratio}")


def _worst_margins(artifact: str, results) -> list:
    """Per result set: the largest (reference max - reference logit of the
    served token) / std(reference logits) over every generated position,
    from one teacher-forced float32 forward of the saved weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.core import compress as CC
    from repro.models import transformer as T

    cfg = get_config(ARCH).replace(dtype="float32")
    params, _ = CC.load_plan(artifact, cfg=cfg)

    @jax.jit
    def deficits(p, toks, served):
        logits = T.forward(p, cfg, {"tokens": toks})[0]
        lg = logits[:, PROMPT - 1:PROMPT - 1 + N_NEW]       # predicts out[j]
        pick = jnp.take_along_axis(lg, served[..., None], axis=-1)[..., 0]
        return (lg.max(-1) - pick) / lg.std(-1)

    out = []
    for res in results:
        reqs = sorted(res, key=lambda r: r.rid)
        toks = np.zeros((len(reqs), PROMPT + N_NEW), np.int32)
        served = np.zeros((len(reqs), N_NEW), np.int32)
        for i, r in enumerate(reqs):
            toks[i, :PROMPT] = r.tokens
            toks[i, PROMPT:PROMPT + N_NEW - 1] = r.out[:-1]
            served[i] = r.out
        with jax.default_matmul_precision("highest"):
            d = deficits(params, jnp.asarray(toks), jnp.asarray(served))
        out.append(float(jnp.max(d)))
    return out


def _check_drained(res, name: str) -> None:
    check(res.status == "drained", f"{name}: drain ended {res.status!r}")
    check(not res.failed, f"{name}: {len(res.failed)} failed")
    check(len(res) == REQUESTS
          and all(len(r.out) == N_NEW for r in res),
          f"{name}: {len(res)}/{REQUESTS} requests done")


def phase_serve(seed: int, artifact: str, aot_dir: str) -> None:
    from repro.serve import api
    from repro.serve.aot import AOT_STAT_KEYS

    shutil.rmtree(aot_dir, ignore_errors=True)
    opts = api.ServeOptions(arch=ARCH, compressed_ckpt=artifact, aot=True,
                            aot_cache_dir=aot_dir, batch=BATCH,
                            max_len=MAX_LEN, requests=REQUESTS,
                            prompt_len=PROMPT, n_new=N_NEW, seed=seed)
    layouts = {
        "contiguous": opts,
        "paged_prefix": dataclasses.replace(opts, kv_block=KV_BLOCK,
                                            prefix_cache=True),
    }
    results = {}
    for name, o in layouts.items():
        t0 = time.perf_counter()
        res = api.serve(o)
        setup = time.perf_counter() - t0
        _check_drained(res, name)
        st = res.report["engine_stats"]
        check(st["aot_store_failures"] == 0 and st["aot_deser_failures"] == 0,
              f"{name}: AOT cache failures {st}")
        results[name] = (res, st, setup)
    margins = _worst_margins(artifact, [r for r, _, _ in results.values()])
    contig = {r.rid: r.out for r in results["contiguous"][0]}
    for (name, (res, st, setup)), m in zip(results.items(), margins):
        same = sum(contig[r.rid] == r.out for r in res)
        report(f"serve[{name}]", drained=f"{len(res)}/{REQUESTS}",
               failed=len(res.failed), tokens=sum(len(r.out) for r in res),
               worst_margin_std=f"{m:.4f}", margin_tol=MARGIN_STD,
               same_tokens_as_contiguous=f"{same}/{REQUESTS}",
               aot_compiles=st["aot_compiles"],
               aot_store_failures=st["aot_store_failures"],
               boot_compile_serve_setup_s=f"{setup:.1f}")
        check(m <= MARGIN_STD,
              f"{name}: a served token is {m:.3f} std below the reference "
              f"maximum (tolerance {MARGIN_STD})")

    # a second boot from the same AOT cache, in this process: nothing may
    # compile, and the cached executables must reproduce the tokens
    t0 = time.perf_counter()
    cb = api.load_engine(opts)
    first = sorted(results["contiguous"][0], key=lambda r: r.rid)[:BATCH]
    for r in first:
        check(cb.submit(api.Request(rid=r.rid, tokens=r.tokens,
                                    n_new=N_NEW)), "warm boot refused")
    res = cb.run_until_drained()
    setup = time.perf_counter() - t0
    st = {k: cb.stats[k] for k in AOT_STAT_KEYS}
    same = sum(a.out == b.out
               for a, b in zip(sorted(res, key=lambda r: r.rid), first))
    report("serve[aot_warm_boot]", **st, same_tokens=f"{same}/{BATCH}",
           boot_serve_setup_s=f"{setup:.1f}")
    check(res.status == "drained" and not res.failed, "warm boot drain")
    check(st["aot_compiles"] == 0 and st["aot_cache_hits"] > 0
          and st["aot_deser_failures"] == 0
          and st["aot_store_failures"] == 0, f"warm AOT boot: {st}")
    check(same == BATCH, "warm boot tokens differ from the cold boot's")


def phase_calib_mesh(seed: int, n: int) -> None:
    import jax
    import jax.numpy as jnp

    from benchmarks.common import calib_max_rel_err
    from repro.configs import get_config
    from repro.core import compress as CC
    from repro.core.capture import StreamingCalibrator, to_list_params
    from repro.data.synthetic import DataConfig, calibration_batches
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T

    cfg = get_config(ARCH).replace(n_layers=CUT_LAYERS)
    params, _ = T.init_model(cfg, jax.random.PRNGKey(seed))
    lp = to_list_params(params, cfg)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=CALIB_SEQ,
                      global_batch=CALIB_BATCH, seed=seed)
    calib = [{"tokens": jnp.asarray(b["tokens"])} for b in
             calibration_batches(dcfg, 2 * CALIB_BATCH, CALIB_BATCH)]
    t0 = time.perf_counter()
    one = CC.calibrate(lp, cfg, calib, streaming=True)
    t_one = time.perf_counter() - t0
    # the d_ff-wide Grams (W_down's input) shard row-wise over the mesh
    t0 = time.perf_counter()
    cal = StreamingCalibrator(lp, cfg, mesh=make_host_mesh(data=n, model=1),
                              shard_grams_above=cfg.d_ff)
    for b in calib:
        cal.ingest(b)
    meshed = cal.finalize()
    t_mesh = time.perf_counter() - t0
    sharded = sum(r == "sharded" for r in cal.routes.values())
    err = calib_max_rel_err(meshed, one)
    report("calib_mesh", mesh_data=n, layers=cfg.n_layers,
           tags=len(one.gram),
           sharded_tags=sharded, max_rel_err=f"{err:.3e}", tol=CALIB_TOL,
           one_device_setup_s=f"{t_one:.1f}", mesh_setup_s=f"{t_mesh:.1f}")
    check(sharded == cfg.n_layers, f"{sharded} sharded Gram tags")
    check(err <= CALIB_TOL, f"mesh capture off the one-device capture: "
                            f"{err:.3e}")


def phase_replicas(seed: int, n: int) -> None:
    import jax

    from repro import configs
    from repro.serve import api

    cut = f"{ARCH}-{CUT_LAYERS}l"
    configs.register(cut, configs.get_config(ARCH).replace(
        n_layers=CUT_LAYERS))
    opts = api.ServeOptions(arch=cut, batch=BATCH, max_len=MAX_LEN,
                            requests=REQUESTS, prompt_len=PROMPT,
                            n_new=N_NEW, seed=seed)
    t0 = time.perf_counter()
    single = api.serve(opts)
    t_single = time.perf_counter() - t0
    _check_drained(single, "single replica")

    t0 = time.perf_counter()
    opts_n = dataclasses.replace(opts, replicas=n)
    engines = [api.load_engine(opts_n, replica=i) for i in range(n)]
    router = api.Router([api.FrontDoor(e) for e in engines]).start()
    for r in sorted(single, key=lambda r: r.rid):
        check(router.submit(r.tokens, N_NEW, rid=r.rid) is not None,
              "router refused a request")
    res = router.drain_all()
    router.close()
    t_multi = time.perf_counter() - t0
    _check_drained(res, f"{n} replicas")
    # after serving: the weights and the KV cache the steps wrote back
    placed = [{d for leaf in jax.tree.leaves((e.params, e.cache))
               for d in leaf.devices()} for e in engines]
    for i, p in enumerate(placed):
        check(p == {jax.local_devices()[i]}, f"replica {i} lives on {p}")
    want = {r.rid: r.out for r in single}
    same = sum(want[r.rid] == r.out for r in res)
    per = [e.stats["admitted"] for e in engines]
    report("replicas", replicas=n, layers=CUT_LAYERS,
           devices=len(set().union(*placed)),
           requests_per_replica=per, same_tokens=f"{same}/{REQUESTS}",
           single_setup_s=f"{t_single:.1f}", replicas_setup_s=f"{t_multi:.1f}")
    check(same == REQUESTS, "replica tokens differ from a single replica's")
    check(all(p > 0 for p in per), f"an idle replica: {per}")


# ---------------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the multi-chip paths")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro import compile_cache
    except ImportError:
        sys.exit("chip_smoke: src/repro not found next to this script; "
                 "run it from a checkout of the repository")
    dev = phase_device(args.chips)
    compile_cache.enable()
    os.makedirs(WORK, exist_ok=True)
    if args.chips == 1:
        artifact = os.path.join(WORK, f"{ARCH}-drank{int(RATIO * 100)}")
        phase_kernels(args.seed)
        phase_compress(args.seed, artifact)
        phase_serve(args.seed, artifact, os.path.join(WORK, "aot"))
    else:
        phase_calib_mesh(args.seed, args.chips)
        phase_replicas(args.seed, args.chips)
    import jax
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
