import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512")
"""Multi-pod dry-run: prove every (architecture × input-shape × mesh) cell
lowers AND compiles against the production meshes, and extract the roofline
terms from the compiled artifact.

MUST be run as its own process (the two lines above force 512 host devices
BEFORE jax initializes — never import this module from tests).

Per cell:
    jit(step).lower(...).compile()
    memory_analysis()      -> bytes/device (fits-or-not)
    cost_analysis()        -> HLO FLOPs + HBM bytes        (compute/memory terms)
    compiled.as_text()     -> collective ops + operand bytes (collective term)

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --mesh single --cells all
    PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --arch qwen3-4b \
        --shape train_4k
Results are cached as JSON under experiments/dryrun/<mesh>/<cell>.json; use
--force to re-run. benchmarks/roofline.py consumes the JSONs.
"""
import argparse
import json
import re
import time
import traceback
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import SHAPES, ModelConfig, ShapeConfig, shape_applicable
from repro.configs import ARCH_IDS, get_config
from repro.dist import sharding as SH
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.optim.adamw import OptimizerConfig
from repro.train import step as TS

# TPU v5e hardware constants (per chip)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
ICI_BW = 50e9                # bytes/s/link

RESULT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                          "experiments", "dryrun")


# ---------------------------------------------------------------------------
# Input specs (ShapeDtypeStruct stand-ins; no allocation)
# ---------------------------------------------------------------------------
def sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, *,
                with_labels: bool) -> Dict:
    gb, S = shape.global_batch, shape.seq_len
    b: Dict = {}
    if cfg.is_encoder_decoder:
        b["enc_embeds"] = sds((gb, S, cfg.d_model), cfg.dtype)
        b["tokens"] = sds((gb, S), jnp.int32)
    elif cfg.frontend:
        b["embeds"] = sds((gb, S, cfg.d_model), cfg.dtype)
        if with_labels:
            b["labels"] = sds((gb, S), jnp.int32)
        if cfg.rope_kind == "mrope":
            b["positions"] = sds((3, gb, S), jnp.int32)
    else:
        b["tokens"] = sds((gb, S), jnp.int32)
    return b


def batch_shardings(batch: Dict, mesh) -> Dict:
    out = {}
    for k, v in batch.items():
        if k == "positions" and len(v.shape) == 3:
            axes = (None, "batch", "seq")
        else:
            axes = ("batch", "seq") + (None,) * (len(v.shape) - 2)
        out[k] = jax.sharding.NamedSharding(
            mesh, SH.shape_aware_spec(v.shape, axes, mesh))
    return out


CACHE_AXES = {
    # kv cache (n, B, L, K, hd): shard batch over dp, cache seq over model
    5: ("layer_stack", "batch", "kv_seq_model", None, None),
    4: ("layer_stack", "batch", None, None),
    3: ("layer_stack", "batch", None),
    2: ("layer_stack", "batch"),
}


def cache_shardings(cache, mesh):
    def leaf(v):
        nd = len(v.shape)
        if nd == 1:        # pos (B,)
            axes = ("batch",)
        else:
            axes = CACHE_AXES.get(nd, ("layer_stack",) + ("batch",)
                                  + (None,) * (nd - 2))
        return jax.sharding.NamedSharding(
            mesh, SH.shape_aware_spec(v.shape, axes, mesh))
    return jax.tree.map(leaf, cache)


# ---------------------------------------------------------------------------
# Model-FLOPs accounting (6·N·D train / 2·N·D inference, N = active matmul
# params; MoE counts the routed fraction top_k/E)
# ---------------------------------------------------------------------------
def active_matmul_params(cfg: ModelConfig, params_shapes) -> float:
    total = 0.0
    moe_scale = (cfg.moe.top_k / cfg.moe.num_experts
                 if cfg.moe.num_experts else 1.0)

    def walk(node, path):
        nonlocal total
        if hasattr(node, "shape"):
            if len(node.shape) < 2 or path[-1] in ("embed",):
                return
            scale = moe_scale if ("moe" in path and path[-1] in (
                "w_gate", "w_up", "w_down")) else 1.0
            # stacked runs carry their layer count in dim 0
            total += float(np.prod(node.shape)) * scale
            return
        if isinstance(node, dict):
            for k, v in node.items():
                walk(v, path + (k,))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, path + (i,))

    walk(params_shapes, ())
    if cfg.tie_embeddings:
        total += float(cfg.vocab_size * cfg.d_model)   # logits matmul
    return total


def model_flops(cfg: ModelConfig, shape: ShapeConfig, params_shapes) -> float:
    n = active_matmul_params(cfg, params_shapes)
    tokens = shape.global_batch * (shape.seq_len if shape.mode in
                                   ("train", "prefill") else 1)
    per_tok = 6.0 if shape.mode == "train" else 2.0
    return per_tok * n * tokens


# ---------------------------------------------------------------------------
# Collective parsing from post-SPMD HLO
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s32": 4, "u32": 4,
                "s64": 8, "u64": 8, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


_HEADER_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*\(")
_WHILE_RE = re.compile(
    r"while\(%?[\w\.\-]+\), condition=%?([\w\.\-]+), body=%?([\w\.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_INSTR_RE = re.compile(r"^%?[\w\.\-]+ = ((?:\([^{]*?\))|(?:\S+)) ([\w\-]+)")


def parse_collectives(hlo: str) -> Dict:
    """Sum result bytes of collective ops, scaling ops inside while-loop
    bodies by the trip count (XLA annotates lax.scan loops with
    known_trip_count; fallback: largest constant in the loop condition)."""
    # ---- computation segmentation (headers end with '{', instructions
    # carry ' = '; header return types may contain /*index=N*/ comments) ---
    comps: Dict[str, str] = {}
    cur = None
    buf: list = []
    for line in hlo.splitlines():
        if cur is None:
            if line.rstrip().endswith("{"):
                head = line.split("(")[0]
                if " = " not in head:
                    m = _HEADER_RE.match(line)
                    if m:
                        cur = m.group(1)
                        buf = []
            continue
        if line.strip() == "}":
            comps[cur] = "\n".join(buf)
            cur = None
        else:
            buf.append(line)

    # ---- body computation -> trip count ----------------------------------
    trip: Dict[str, int] = {}
    for cname, body in comps.items():
        for line in body.splitlines():
            m = _WHILE_RE.search(line)
            if not m:
                continue
            cond, wbody = m.group(1), m.group(2)
            t = None
            tm = _TRIP_RE.search(line)
            if tm:
                t = int(tm.group(1))
            else:
                consts = [int(c) for c in re.findall(
                    r"constant\((\d+)\)", comps.get(cond, ""))]
                t = max(consts) if consts else 1
            trip[wbody] = max(trip.get(wbody, 1), t)

    per_op: Dict[str, float] = {}
    total = 0.0
    for cname, body in comps.items():
        mult = trip.get(cname, 1)
        for line in body.splitlines():
            m = _INSTR_RE.match(line.strip())
            if not m:
                continue
            op = m.group(2)
            if op.endswith("-done"):
                continue                    # counted at -start
            base = op[:-6] if op.endswith("-start") else op
            if base not in _COLLECTIVES:
                continue
            byts = _shape_bytes(m.group(1)) * mult
            per_op[base] = per_op.get(base, 0.0) + byts
            total += byts
    return {"total_bytes": total, "per_op": per_op,
            "while_trip_counts": trip}


# ---------------------------------------------------------------------------
# Synthetic compressed-deploy shapes (uniform rank, MXU-aligned)
# ---------------------------------------------------------------------------
_COMPRESSIBLE = {"wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                 "w_in", "w_z", "w_out", "w_bc", "ff_gate", "ff_up",
                 "ff_down"}


def factorized_shapes(tree, specs, ratio: float, multiple: int = 128):
    """Map dense linear {w} shape-structs to factorized {B, C} at a uniform
    parameter ratio (shape-level plan for dry-running the deploy form)."""
    def walk(node, spec, path):
        if isinstance(node, dict):
            if "w" in node and hasattr(node["w"], "shape") \
                    and path and path[-1] in _COMPRESSIBLE \
                    and ("decoder" in path or "encoder" in path):
                w = node["w"]
                *stack, d1, d2 = w.shape
                r = int((1 - ratio) * d1 * d2 / (d1 + d2))
                r = max(multiple, r // multiple * multiple)
                r = min(r, d1, d2)
                wspec = spec["w"]
                st = tuple(wspec[:-2])
                new = {
                    "B": sds((*stack, d1, r), w.dtype),
                    "C": sds((*stack, r, d2), w.dtype),
                }
                nspec = {"B": st + (wspec[-2], "rank"),
                         "C": st + ("rank", wspec[-1])}
                if "b" in node:
                    new["b"] = node["b"]
                    nspec["b"] = spec["b"]
                return new, nspec
            out_n, out_s = {}, {}
            for k in node:
                out_n[k], out_s[k] = walk(node[k], spec[k], path + (k,))
            return out_n, out_s
        return node, spec

    return walk(tree, specs, ())


# ---------------------------------------------------------------------------
# Per-cell lowering
# ---------------------------------------------------------------------------
def lower_cell(arch: str, shape_name: str, mesh, *, compressed: float = 0.0,
               microbatches: int = 1, donate: bool = True,
               overrides: Optional[Dict] = None,
               rules: Optional[Dict] = None,
               hlo_out: str = "", pallas_flash: bool = False) -> Dict:
    cfg = get_config(arch)
    if overrides:
        cfg = cfg.replace(**overrides)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": why}

    t0 = time.time()
    # abstract init: params as ShapeDtypeStructs; specs (string tuples) are
    # built host-side during the trace and captured by side effect
    holder = {}

    def _init(k):
        p, s = T.init_model(cfg, k)
        holder["specs"] = s
        return p

    params_shapes = jax.eval_shape(_init, jax.random.PRNGKey(0))
    specs = holder["specs"]
    if compressed > 0:
        params_shapes, specs = factorized_shapes(params_shapes, specs,
                                                 compressed)
    with SH.use_rules(rules or {}, mesh=mesh):
        p_shardings = SH.shardings_for_tree(params_shapes, specs, mesh)

    with mesh, SH.use_rules(rules or {}, mesh=mesh):
        if shape.mode == "train":
            tcfg = TS.TrainConfig(
                microbatches=microbatches,
                optimizer=OptimizerConfig(total_steps=10 ** 5))
            state_shapes = jax.eval_shape(
                lambda p: TS.TrainState(
                    params=p, opt=__import__(
                        "repro.optim.adamw", fromlist=["adamw_init"]
                    ).adamw_init(p)), params_shapes)
            opt_shardings = TS.AdamWState(
                step=jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec()),
                mu=p_shardings, nu=p_shardings)
            st_shardings = TS.TrainState(params=p_shardings,
                                         opt=opt_shardings)
            batch = batch_specs(cfg, shape, with_labels=True)
            b_shardings = batch_shardings(batch, mesh)
            fn = jax.jit(TS.make_train_step(cfg, tcfg),
                         in_shardings=(st_shardings, b_shardings),
                         donate_argnums=(0,) if donate else ())
            lowered = fn.lower(state_shapes, batch)
        elif shape.mode == "prefill":
            batch = batch_specs(cfg, shape, with_labels=False)
            b_shardings = batch_shardings(batch, mesh)
            fn = jax.jit(
                lambda p, b: T.prefill(p, cfg, b,
                                       max_len=shape.seq_len + 128),
                in_shardings=(p_shardings, b_shardings))
            lowered = fn.lower(params_shapes, batch)
        else:   # decode
            gb = shape.global_batch
            cache_shapes = jax.eval_shape(
                lambda: T.init_cache(cfg, gb, shape.seq_len,
                                     enc_len=min(shape.seq_len, 4096)))
            c_shardings = cache_shardings(cache_shapes, mesh)
            tok = sds((gb, 1), jnp.int32)
            tok_sh = jax.sharding.NamedSharding(
                mesh, SH.shape_aware_spec((gb, 1), ("batch", None), mesh))
            fn = jax.jit(
                lambda p, c, t: T.decode_step(p, cfg, c, t),
                in_shardings=(p_shardings, c_shardings, tok_sh),
                donate_argnums=(1,) if donate else ())
            lowered = fn.lower(params_shapes, cache_shapes, tok)
        t_lower = time.time() - t0

        t1 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t1

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_txt = compiled.as_text()
    if hlo_out:
        import zstandard
        with open(hlo_out, "wb") as f:
            f.write(zstandard.ZstdCompressor(level=6).compress(
                hlo_txt.encode()))
    from repro.launch import hlo_analysis
    an = hlo_analysis.analyze(hlo_txt, pallas_flash=pallas_flash)
    n_dev = mesh.size

    mf = model_flops(cfg, shape, params_shapes)
    # trip-count-aware totals from our own HLO analyzer (XLA's
    # cost_analysis does not scale while bodies — see hlo_analysis.py)
    hlo_flops = an["flops"]
    hlo_bytes = an["hbm_bytes"]
    coll_bytes = an["collective_bytes"]
    result = {
        "arch": arch, "shape": shape_name, "mesh": list(mesh.shape.values()),
        "mesh_axes": list(mesh.shape.keys()), "devices": n_dev,
        "mode": shape.mode, "compressed": compressed,
        "microbatches": microbatches, "pallas_flash": pallas_flash,
        "overrides": overrides or {}, "rules": rules or {},
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        },
        "cost": {"hlo_flops": hlo_flops, "hlo_bytes": hlo_bytes,
                 "xla_cost_flops": float(cost.get("flops", 0.0)),
                 "xla_cost_bytes": float(cost.get("bytes accessed", 0.0))},
        "collectives": {"total_bytes": coll_bytes,
                        "per_op": an["collectives"]},
        "model_flops": mf,
        "roofline": {
            "compute_s": hlo_flops / PEAK_FLOPS,
            "memory_s": hlo_bytes / HBM_BW,
            "collective_s": coll_bytes / ICI_BW,
            "useful_flops_ratio": mf / max(hlo_flops * n_dev, 1.0),
        },
    }
    terms = result["roofline"]
    dom = max(("compute_s", "memory_s", "collective_s"),
              key=lambda k: terms[k])
    result["roofline"]["dominant"] = dom
    return result


def reanalyze_cell(json_path: str, hlo_path: str,
                   pallas_flash: bool = False) -> Optional[Dict]:
    """Recompute analyzer-derived fields from the saved HLO (no compile)."""
    if not (os.path.exists(json_path) and os.path.exists(hlo_path)):
        return None
    import zstandard
    with open(json_path) as f:
        res = json.load(f)
    if "roofline" not in res:
        return None
    with open(hlo_path, "rb") as f:
        hlo = zstandard.ZstdDecompressor().decompress(f.read()).decode()
    from repro.launch import hlo_analysis
    an = hlo_analysis.analyze(hlo, pallas_flash=pallas_flash)
    n_dev = res["devices"]
    res["pallas_flash"] = pallas_flash
    res["cost"]["hlo_flops"] = an["flops"]
    res["cost"]["hlo_bytes"] = an["hbm_bytes"]
    res["collectives"] = {"total_bytes": an["collective_bytes"],
                          "per_op": an["collectives"]}
    rf = {
        "compute_s": an["flops"] / PEAK_FLOPS,
        "memory_s": an["hbm_bytes"] / HBM_BW,
        "collective_s": an["collective_bytes"] / ICI_BW,
        "useful_flops_ratio": res["model_flops"] / max(
            an["flops"] * n_dev, 1.0),
    }
    rf["dominant"] = max(("compute_s", "memory_s", "collective_s"),
                         key=lambda k: rf[k])
    res["roofline"] = rf
    return res


def cell_path(mesh_name: str, arch: str, shape: str, tag: str = "") -> str:
    d = os.path.join(RESULT_DIR, mesh_name)
    os.makedirs(d, exist_ok=True)
    sfx = f"__{tag}" if tag else ""
    return os.path.join(d, f"{arch}__{shape}{sfx}.json")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--compressed", type=float, default=0.0,
                    help="also lower the factorized deploy form at this ratio")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--override", default="",
                    help='JSON ModelConfig overrides, e.g. {"remat":"dots"}')
    ap.add_argument("--rules", default="",
                    help='JSON logical-axis rule overrides, '
                         'e.g. {"seq":"model"}')
    ap.add_argument("--tag", default="")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--pallas-flash", action="store_true",
                    help="model the fused Pallas attention/lowrank kernels "
                         "in the HBM accounting (dots feeding only dots "
                         "stay in VMEM)")
    ap.add_argument("--reanalyze", action="store_true",
                    help="recompute analysis from saved HLO (no compile)")
    args = ap.parse_args(argv)
    overrides = json.loads(args.override) if args.override else None
    rules = json.loads(args.rules) if args.rules else None
    if rules:
        rules = {k: (tuple(v) if isinstance(v, list) else v)
                 for k, v in rules.items()}

    assert len(jax.devices()) == 512, \
        f"dryrun needs 512 host devices, got {len(jax.devices())}"
    mesh = make_production_mesh(multi_pod=(args.mesh == "multi"))
    archs = ARCH_IDS if args.arch == "all" else [args.arch]
    archs = [a for a in archs if a != "llama-mini"]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]

    n_ok = n_skip = n_fail = 0
    for arch in archs:
        for shape in shapes:
            out = cell_path(args.mesh, arch, shape, args.tag)
            hlo_out = out.replace(".json", ".hlo.zst")
            if args.reanalyze:
                res = reanalyze_cell(out, hlo_out,
                                     pallas_flash=args.pallas_flash)
                if res is None:
                    continue
            elif os.path.exists(out) and not args.force:
                print(f"[cached] {arch} x {shape}")
                continue
            else:
                try:
                    res = lower_cell(arch, shape, mesh,
                                     compressed=args.compressed,
                                     microbatches=args.microbatches,
                                     overrides=overrides, rules=rules,
                                     hlo_out=hlo_out,
                                     pallas_flash=args.pallas_flash)
                except Exception as e:
                    res = {"arch": arch, "shape": shape, "error":
                           f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
            with open(out, "w") as f:
                json.dump(res, f, indent=1)
            if res.get("skipped"):
                n_skip += 1
                print(f"[skip]   {arch} x {shape}: {res['reason']}")
            elif "error" in res:
                n_fail += 1
                print(f"[FAIL]   {arch} x {shape}: {res['error'][:200]}")
            else:
                n_ok += 1
                r = res["roofline"]
                print(f"[ok]     {arch} x {shape} dominant={r['dominant']} "
                      f"compute={r['compute_s']:.4f}s "
                      f"memory={r['memory_s']:.4f}s "
                      f"coll={r['collective_s']:.4f}s "
                      f"(compile {res['compile_s']}s)")
    print(f"done: ok={n_ok} skip={n_skip} fail={n_fail}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
