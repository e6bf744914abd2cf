"""PR-2 tentpole coverage: device-side streaming calibration capture and
the compressed-checkpoint serving round trip.

Parity bars (ISSUE acceptance): the jit/device capture Gram must match the
eager fp64 host oracle within 1e-4 relative on EVERY tag, and an engine
booted from a saved compressed checkpoint must decode token-identically to
one compressed in-process.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core import compress as CC
from repro.core.capture import (Collector, StreamingCalibrator,
                                discover_capture_dims, streaming_calibrate,
                                tag_linears, to_list_params)
from repro.kernels import ops
from repro.launch.mesh import make_host_mesh
from repro.models import transformer as T
from repro.serve.engine import ContinuousBatcher, Engine, Request, \
    ServeConfig

RTOL = 1e-4

CFG = get_config("llama-mini").replace(n_layers=2, d_model=64, n_heads=4,
                                       n_kv_heads=4, head_dim=16, d_ff=128,
                                       vocab_size=256, rank_multiple=4)


def _batches(cfg, n=2, batch=2, seq=32, seed=7):
    key = jax.random.PRNGKey(seed)
    return [{"tokens": jax.random.randint(jax.random.fold_in(key, i),
                                          (batch, seq), 0, cfg.vocab_size)}
            for i in range(n)]


def _eager(lp, cfg, batches) -> Collector:
    return CC.calibrate(lp, cfg, batches, streaming=False)


def _assert_parity(got: Collector, oracle: Collector, rtol=RTOL):
    assert set(got.gram) == set(oracle.gram), \
        set(got.gram) ^ set(oracle.gram)
    for tag in oracle.gram:
        ref = oracle.gram[tag]
        rel = np.abs(got.gram[tag] - ref).max() / (np.abs(ref).max() + 1e-12)
        assert rel < rtol, (tag, rel)
        aref = oracle.absmean[tag]
        arel = np.abs(got.absmean[tag] - aref).max() / (
            np.abs(aref).max() + 1e-12)
        assert arel < rtol, (tag, arel)
        assert got.count[tag] == oracle.count[tag], tag


# ---------------------------------------------------------------------------
# gram_blocked vs fp64 numpy oracle (padded / ragged N, interpret mode)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("N,D", [(7, 12), (100, 48), (513, 96), (64, 64)])
def test_gram_kernel_vs_fp64_numpy_oracle(N, D):
    x = jax.random.normal(jax.random.PRNGKey(0), (N, D), dtype=jnp.float32)
    g = np.asarray(ops.gram(x), dtype=np.float64)     # pad-and-mask wrapper
    xn = np.asarray(x, dtype=np.float64)
    ref = xn.T @ xn
    rel = np.abs(g - ref).max() / (np.abs(ref).max() + 1e-12)
    assert rel < RTOL, rel


def test_gram_kernel_zero_pad_rows_are_exact():
    """Zero-padding the token axis must not perturb G at all."""
    x = jax.random.normal(jax.random.PRNGKey(1), (10, 24), jnp.float32)
    xp = jnp.concatenate([x, jnp.zeros((22, 24), jnp.float32)], axis=0)
    assert jnp.allclose(ops.gram(x), ops.gram(xp), atol=0.0)


# ---------------------------------------------------------------------------
# streaming capture vs eager fp64 oracle
# ---------------------------------------------------------------------------
def test_streaming_matches_eager_oracle_every_tag():
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    lp = to_list_params(params, CFG)
    batches = _batches(CFG, n=3)
    oracle = _eager(lp, CFG, batches)
    col = streaming_calibrate(lp, CFG, batches)
    _assert_parity(col, oracle)


def test_streaming_flush_boundary_invariance():
    """fp64 host sums must not depend on the fp32 flush cadence."""
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    lp = to_list_params(params, CFG)
    batches = _batches(CFG, n=3)
    col1 = streaming_calibrate(lp, CFG, batches, flush_every=1)
    col8 = streaming_calibrate(lp, CFG, batches, flush_every=8)
    for tag in col1.gram:
        rel = np.abs(col1.gram[tag] - col8.gram[tag]).max() / (
            np.abs(col8.gram[tag]).max() + 1e-12)
        assert rel < 1e-6, (tag, rel)


def test_streaming_pallas_gram_kernel_path():
    """Interpret-mode evidence that the TPU gram kernel feeds the stream."""
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    lp = to_list_params(params, CFG)
    batches = _batches(CFG, n=1)
    oracle = _eager(lp, CFG, batches)
    col = streaming_calibrate(lp, CFG, batches, use_kernel=True)
    _assert_parity(col, oracle)


def test_streaming_mesh_psum_path():
    """Shard-aware accumulation: per-shard partials psum'd in shard_map."""
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    lp = to_list_params(params, CFG)
    batches = _batches(CFG, n=2)
    oracle = _eager(lp, CFG, batches)
    mesh = make_host_mesh(data=1, model=1)
    col = streaming_calibrate(lp, CFG, batches, mesh=mesh)
    _assert_parity(col, oracle)


@pytest.mark.slow           # MoE capture sweep (per-expert dispatch Grams)
def test_streaming_moe_expert_capture():
    cfg = get_config("granite-moe-1b-a400m").reduced()
    params, _ = T.init_model(cfg, jax.random.PRNGKey(0))
    lp = to_list_params(params, cfg)
    batches = _batches(cfg, n=1, seq=16)
    oracle = _eager(lp, cfg, batches)
    col = streaming_calibrate(lp, cfg, batches)
    assert any("/expert" in t for t in col.gram)
    _assert_parity(col, oracle)


@pytest.mark.parametrize("meshed", [False, True])
def test_capture_step_takes_weights_as_arguments(meshed):
    """Closed over, the weights would be baked into the capture executable
    as constants — gigabytes, and a compile to match, at full width."""
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    cal = StreamingCalibrator(
        to_list_params(params, CFG), CFG,
        mesh=make_host_mesh(data=1, model=1) if meshed else None)
    batch = _batches(CFG, n=1)[0]
    cal.ingest(batch)
    lowered = (cal._capture.lower(cal._weights, batch) if meshed else
               cal._step.lower(cal._accs, cal._weights, batch))
    consts = re.findall(r'constant dense<"0x[0-9A-Fa-f]*"> : '
                        r'tensor<([0-9x]+)x\w+>', lowered.as_text())
    smallest = min(w.size for w in cal._weights if w.ndim == 2)
    assert all(np.prod([int(d) for d in c.split("x")]) < smallest
               for c in consts), consts


def test_discovery_and_ragged_batch_shapes():
    """Tag/dim discovery is abstract (no FLOPs) and the calibrator accepts
    mixed batch shapes (one retrace per shape, stats still exact)."""
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    lp = to_list_params(params, CFG)
    tagged = tag_linears(lp)
    dims = discover_capture_dims(tagged, CFG, _batches(CFG, n=1)[0])
    assert all(isinstance(d, int) for d in dims.values()) and dims
    mixed = _batches(CFG, n=1, batch=2, seq=32) + \
        _batches(CFG, n=1, batch=1, seq=16, seed=11)
    oracle = _eager(lp, CFG, mixed)
    cal = StreamingCalibrator(lp, CFG, flush_every=100)
    for b in mixed:
        cal.ingest(b)
    _assert_parity(cal.finalize(), oracle)


def test_eager_collector_refuses_tracers():
    col = Collector()
    with pytest.raises(RuntimeError, match="streaming"):
        with col:
            jax.jit(lambda x: col.add("t", x) or x)(jnp.ones((2, 4)))


# ---------------------------------------------------------------------------
# compress -> save -> restore -> serve round trip
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def compressed_mini():
    params, _ = T.init_model(CFG, jax.random.PRNGKey(0))
    batches = _batches(CFG, n=1)
    ccfg = CC.CompressionConfig(method="drank", ratio=0.3, group_size=2,
                                beta=0.3)
    comp, plan = CC.build_plan_and_params(params, CFG, ccfg, batches)
    return comp, plan


def test_save_restore_serve_token_identical(compressed_mini, tmp_path):
    comp, plan = compressed_mini
    CC.save_plan(str(tmp_path), comp, plan, CFG)
    loaded, plan2 = CC.load_plan(str(tmp_path), cfg=CFG)
    assert plan2.to_json() == plan.to_json()
    # deduped shared bases survive the round trip byte- and identity-wise
    assert CC.compressed_param_count(loaded) == \
        CC.compressed_param_count(comp)
    for a, b in zip(jax.tree.leaves(comp), jax.tree.leaves(loaded)):
        assert a.dtype == b.dtype
        assert jnp.array_equal(a, b)
    prompts = np.arange(12, dtype=np.int32).reshape(2, 6) % CFG.vocab_size
    eng_mem = Engine(comp, CFG, ServeConfig())
    eng_ckpt = Engine.from_compressed(str(tmp_path), CFG, ServeConfig())
    assert eng_ckpt.plan is not None
    assert (eng_mem.generate(prompts, n_new=8)
            == eng_ckpt.generate(prompts, n_new=8)).all()


def test_batcher_boots_from_compressed(compressed_mini, tmp_path):
    comp, plan = compressed_mini
    CC.save_plan(str(tmp_path), comp, plan, CFG)
    cb = ContinuousBatcher.from_compressed(
        str(tmp_path), CFG, ServeConfig(batch=2, max_len=48))
    rng = np.random.default_rng(0)
    for i in range(3):
        cb.submit(Request(rid=i, tokens=rng.integers(
            0, CFG.vocab_size, size=(5 + i,), dtype=np.int32), n_new=4))
    done = cb.run_until_drained()
    assert len(done) == 3
    eng = Engine(comp, CFG, ServeConfig())
    for r in done:
        ref = eng.generate(r.tokens[None, :], n_new=4)[0]
        assert (np.asarray(r.out) == ref).all()


def test_load_plan_rejects_wrong_config(compressed_mini, tmp_path):
    comp, plan = compressed_mini
    CC.save_plan(str(tmp_path), comp, plan, CFG)
    with pytest.raises(ValueError, match="built for"):
        CC.load_plan(str(tmp_path), cfg=CFG.replace(n_layers=4))


def test_save_plan_artifact_dedupes_shared_bases(compressed_mini, tmp_path):
    """Group members share their basis B by object identity; the artifact
    must store each shared basis ONCE."""
    comp, plan = compressed_mini
    CC.save_plan(str(tmp_path), comp, plan, CFG)
    unique = len({id(a) for a in jax.tree.leaves(comp)})
    arrays = np.load(str(tmp_path / "compressed" / "arrays.npz"))
    assert len(arrays.files) == unique
    total = len(jax.tree.leaves(comp))
    assert unique < total     # grouping actually shared something


def test_artifact_verify_passes_on_clean_artifact(compressed_mini, tmp_path):
    comp, plan = compressed_mini
    CC.save_plan(str(tmp_path), comp, plan, CFG)
    loaded, _ = CC.load_plan(str(tmp_path), cfg=CFG, verify=True)
    for a, b in zip(jax.tree.leaves(comp), jax.tree.leaves(loaded)):
        assert jnp.array_equal(a, b)
    eng = Engine.from_compressed(str(tmp_path), CFG, ServeConfig(),
                                 verify=True)
    assert eng.plan is not None


def test_artifact_verify_catches_corruption(compressed_mini, tmp_path):
    """A single flipped array in arrays.npz must fail --verify at boot."""
    comp, plan = compressed_mini
    CC.save_plan(str(tmp_path), comp, plan, CFG)
    npz = tmp_path / "compressed" / "arrays.npz"
    arrays = dict(np.load(str(npz)))
    victim = sorted(arrays)[0]
    arrays[victim] = arrays[victim] + np.float32(1e-3)
    np.savez(str(npz), **arrays)
    with pytest.raises(ValueError, match="integrity"):
        CC.load_plan(str(tmp_path), cfg=CFG, verify=True)
    # unverified load still works (prod escape hatch for legacy artifacts)
    loaded, _ = CC.load_plan(str(tmp_path), cfg=CFG)
    assert loaded is not None


def test_artifact_verify_rejects_unhashed_manifest(compressed_mini,
                                                   tmp_path):
    import json as _json
    comp, plan = compressed_mini
    CC.save_plan(str(tmp_path), comp, plan, CFG)
    mpath = tmp_path / "compressed" / "manifest.json"
    manifest = _json.loads(mpath.read_text())
    del manifest["hashes"]
    mpath.write_text(_json.dumps(manifest))
    with pytest.raises(ValueError, match="no content hashes"):
        CC.load_plan(str(tmp_path), cfg=CFG, verify=True)


def test_pytree_store_roundtrip_bf16_and_lists(tmp_path):
    from repro.ckpt import store
    tree = {"a": [jnp.ones((2, 3), jnp.bfloat16),
                  {"b": jnp.arange(4, dtype=jnp.int32)}],
            "c": (jnp.zeros((1,), jnp.float32),)}
    store.save_pytree(str(tmp_path), tree, meta={"k": 1})
    back, meta = store.load_pytree(str(tmp_path))
    assert meta == {"k": 1}
    assert isinstance(back["a"], list) and isinstance(back["c"], tuple)
    assert back["a"][0].dtype == jnp.bfloat16
    assert jnp.array_equal(back["a"][1]["b"], tree["a"][1]["b"])
