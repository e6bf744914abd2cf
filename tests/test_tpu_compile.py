"""Compile every main-path Pallas kernel for a described TPU v5e at
SmolLM-360M widths (d_model 960, 15 heads over 5 KV heads, head_dim 64,
d_ff 2560) with ``interpret=False``.

Nothing runs: the TPU compiler that ships with libtpu compiles for a chip
that is described, not attached. That catches what interpret mode cannot
— block shapes Mosaic refuses, VMEM overflows — at no chip time. The
topology is described inside a module fixture (never at import), so
pytest-xdist workers all collect the same tests and only the worker that
runs this file loads the TPU library."""
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.decode_attention import (decode_attention_bkgh,
                                            decode_attention_paged_bkgh)
from repro.kernels.flash_attention import flash_attention_bh
from repro.kernels.gram import gram_blocked
from repro.kernels.lowrank_matmul import lowrank_gemv, lowrank_matmul_2d

D, H, KV, HD, FF = 960, 15, 5, 64, 2560       # SmolLM-360M widths
G = H // KV
BATCH, MAX_LEN, KV_BLOCK, RANK = 8, 512, 16, 300
BF, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# (kernel, shapes as the ops wrappers hand them over after padding).
# Ranks come out of the allocator unaligned, so RANK is deliberately not
# a multiple of 128.
CASES = {
    "gram": (lambda x: gram_blocked(x, bi=256, bj=256, bn=512),
             [((BATCH * 512, 1024), F32)]),
    "lowrank_gemv": (lambda x, b, c: lowrank_gemv(x, b, c, bk=128, bn=128),
                     [((BATCH, 1024), BF), ((1024, RANK), BF),
                      ((RANK, FF), BF)]),
    "lowrank_matmul_2d": (
        lambda x, b, c: lowrank_matmul_2d(x, b, c, bm=128, bk=512, bn=512),
        [((BATCH * 128, 1024), BF), ((1024, RANK), BF), ((RANK, FF), BF)]),
    "flash_attention": (
        lambda q, k, v: flash_attention_bh(q, k, v, heads=H, kv_heads=KV,
                                           bq=128, bk=128),
        [((BATCH * H, 128, HD), BF), ((BATCH * KV, 128, HD), BF),
         ((BATCH * KV, 128, HD), BF)]),
    "decode_attention": (
        lambda q, k, v, n: decode_attention_bkgh(q, k, v, n, bk=128),
        [((BATCH, KV, G, HD), BF), ((BATCH, MAX_LEN, KV, HD), BF),
         ((BATCH, MAX_LEN, KV, HD), BF), ((BATCH,), I32)]),
    "decode_attention_paged": (
        decode_attention_paged_bkgh,
        [((BATCH, KV, G, HD), BF),
         ((BATCH * MAX_LEN // KV_BLOCK + 1, KV_BLOCK, KV, HD), BF),
         ((BATCH * MAX_LEN // KV_BLOCK + 1, KV_BLOCK, KV, HD), BF),
         ((BATCH,), I32), ((BATCH, MAX_LEN // KV_BLOCK), I32)]),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")   # no compiler logs
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep it out of the cache entirely
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", prev)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


def test_mesh_capture_compiles_for_v5e_2x2(topo, monkeypatch):
    """The (data=4) mesh calibration capture runs the Pallas Gram kernel
    inside ``shard_map``; its capture and fold programs must compile for
    four chips (a small model: the sharding does not depend on width)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.core.capture import (StreamingCalibrator,
                                    discover_capture_dims, to_list_params)
    from repro.kernels import ops
    from repro.models import transformer as T

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)   # kernels, not interpret
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    cfg = get_config("smollm-360m").reduced(d_ff=256)
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    cal = StreamingCalibrator(to_list_params(params, cfg), cfg, mesh=mesh,
                              shard_grams_above=cfg.d_ff, use_kernel=True)
    shape = (8, 64)
    cal._dims = discover_capture_dims(
        cal.tagged, cfg, {"tokens": jax.ShapeDtypeStruct(shape, I32)})
    cal._routes = {t: cal._route_of(t, d) for t, d in cal._dims.items()}
    assert "sharded" in cal._routes.values()
    capture, folds = cal._build_mesh_steps()
    weights = [jax.ShapeDtypeStruct(w.shape, w.dtype,
                                    sharding=NamedSharding(mesh, P()))
               for w in cal._weights]
    batch = {"tokens": jax.ShapeDtypeStruct(
        shape, I32, sharding=NamedSharding(mesh, P("data")))}
    text = capture.lower(weights, batch).compile().as_text()
    assert "tpu_custom_call" in text
    parts = jax.eval_shape(capture, weights, batch)

    def acc(tag):
        d = cal._dims[tag]
        rows = P(cal.row_axes, None) if cal._routes[tag] == "sharded" \
            else P()
        return {"gram": jax.ShapeDtypeStruct(
                    (d, d), F32, sharding=NamedSharding(mesh, rows)),
                "absx": jax.ShapeDtypeStruct(
                    (d,), F32, sharding=NamedSharding(mesh, P())),
                "count": jax.ShapeDtypeStruct(
                    (), I32, sharding=NamedSharding(mesh, P()))}

    for tags, fold in folds:
        text = fold.lower({t: acc(t) for t in tags},
                          {t: parts[t] for t in tags}).compile().as_text()
        assert "all-gather" in text and "all-reduce" in text


def test_decode_step_updates_the_pool_in_place_for_v5e(one_chip, tmp_path):
    """The served decode executable at SmolLM-360M size (32 layers, 32
    slots, max_len 2048): the donated pool is aliased whole, no top-level
    operation outputs a whole-pool or per-layer cache buffer other than
    the in-place row writes, and the scratch stays below one pool (a
    copy of the pool, as the pre-donation step made, needs more)."""
    import re

    from repro.configs import get_config
    from repro.models import transformer as T
    from repro.serve import aot as aotlib
    from repro.serve.engine import ServeConfig

    cfg = get_config("smollm-360m")
    scfg = ServeConfig(batch=32, max_len=2048)
    fn, donate = aotlib.AotRegistry(cfg, scfg, "x", cache_dir=str(tmp_path)
                                    )._role_fn(aotlib.ROLE_DECODE)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: T.init_model(cfg, jax.random.PRNGKey(0))[0]))
    cache = on_chip(jax.eval_shape(lambda: T.init_cache(cfg, 32, 2048)))
    tok = on_chip(jax.ShapeDtypeStruct((32, 1), I32))
    compiled = jax.jit(fn, donate_argnums=donate).lower(
        params, cache["runs"], cache["pos"], tok).compile()
    pool = sum(a.size * a.dtype.itemsize
               for a in jax.tree.leaves(cache["runs"]))
    ma = compiled.memory_analysis()
    assert ma.alias_size_in_bytes >= pool
    assert ma.temp_size_in_bytes < pool
    body, fused = [], False
    for line in compiled.as_text().splitlines():
        if not line.startswith(" "):
            fused = "fused" in line or "clone" in line
        elif not fused:
            body.append(line)
    cache_ops = {m.group(1) for m in (
        re.search(r"= bf16\[(?:32|1),32,2048,5,64\]\{[^}]*\} ([\w-]+)\(", ln)
        for ln in body) if m}
    assert cache_ops <= {"parameter", "get-tuple-element",
                         "dynamic-update-slice", "bitcast", "tuple"}, \
        cache_ops
