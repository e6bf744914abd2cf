"""What the harness takes from a configuration's reference module
(``program_config``, ``sizes``, ``from_program``, ``linear_counts``,
``work``), the readers that count work through it, and the benchmark's
one weight rule: for both SmolLM-360M configurations at their published
widths (shapes only), each number equals the one the harness computed
before these moved into ``bench/reference/llama.py``, frozen here."""
import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import counts, harness, load, peaks, weights  # noqa: E402
from bench import trace_reduce as tr  # noqa: E402

CONFIGS = ["smollm-360m-drank20", "smollm-360m-dense"]
# the ModelConfig fields, reference sizes and counts the harness read
# for both before the move
PARENT_OVERRIDES = {
    "n_layers": 32, "d_model": 960, "n_heads": 15, "n_kv_heads": 5,
    "d_ff": 2560, "vocab_size": 49152, "rope_theta": 10000.0,
    "norm_eps": 1e-05, "tie_embeddings": True, "head_dim": 64,
    "dtype": "bfloat16", "param_dtype": "float32"}
PARENT_SIZES = {
    "n_layers": 32, "d_model": 960, "n_heads": 15, "n_kv_heads": 5,
    "head_dim": 64, "d_ff": 2560, "vocab_size": 49152,
    "rope_theta": 10000.0, "norm_eps": 1e-05}
PARENT_PARAM_BYTES = 723_642_240
PARENT_LINEAR_FLOPS = 723_517_440
PARENT_DENSE_LINEARS = 314_572_800
# 27 live slots at a mean live context of 301.37 positions
PARENT_KV_BYTES = 333_291_520
# the readers on the chip's trace excerpt with the records made below
PARENT_DECODE_ROOFLINE = 2.5404550614879766
PARENT_SERVE_MFU_PCT = 0.34655959283405163
# sha256 of the tiny dense configuration's weights from seed 2**31 + 77
PARENT_TINY_WEIGHTS = ("416df78a3340f36763ae914b97bec20a"
                       "444d05e63b52ff3dc3afb144429024b9")


def config_of(name):
    with open(os.path.join(REPO, "bench", "configs", f"{name}.json")) as f:
        return json.load(f)


def reference(config):
    return harness.load_module(REPO, config["reference"])


@pytest.fixture(scope="module", params=CONFIGS)
def published(request):
    """(configuration, reference module, ModelConfig, program shapes)."""
    config = config_of(request.param)
    ref = reference(config)
    cfg = harness.model_config(config, ref)
    return config, ref, cfg, harness.param_shapes(cfg)


def test_model_config_and_sizes_are_the_parents(published):
    from repro import configs
    _, ref, cfg, _ = published
    assert cfg == configs.get_config("smollm-360m").replace(
        **PARENT_OVERRIDES)
    assert ref.sizes(cfg) == PARENT_SIZES


def test_work_and_counts_are_the_parents(published):
    _, ref, cfg, shapes = published
    w = ref.work(cfg, shapes)
    assert w["linear_flops_per_token"] == PARENT_LINEAR_FLOPS
    assert w["decode_param_bytes"](32) == PARENT_PARAM_BYTES
    assert w["decode_param_bytes"](1) == PARENT_PARAM_BYTES
    assert w["windows"] == [0] * 32
    assert counts.kv_bytes(27, 301.37, w["windows"], w["n_kv_heads"],
                           w["head_dim"], w["kv_dtype"]) == PARENT_KV_BYTES
    plain = jax.eval_shape(lambda p: ref.from_program(p, cfg), shapes)
    assert ref.linear_counts(plain, cfg) == (PARENT_DENSE_LINEARS,
                                             PARENT_DENSE_LINEARS)
    assert harness.achieved_ratio(ref, plain, cfg) == 0.0


EXCERPT = os.path.join(REPO, "bench", "testdata",
                       "dense_batch_decode_prefill.pbtxt.gz")
LO, HI, T0 = 0.66, 0.96, 1000.0


def excerpt_records(calls):
    """32 clients receiving a token every 50 ms through the traced
    window, and one request whose first token follows the prefill."""
    recs = []
    for i in range(32):
        recs.append(load.Record(
            spec=load.Spec(rid=i, tokens=np.zeros(100 + 7 * i, np.int32),
                           n_new=30),
            due=0.0, status="done", out=[0] * 30,
            times=[T0 - 0.5 + 0.05 * j + 0.001 * i for j in range(30)]))
    pre = [c for c in calls if c.role == "prefill"]
    end = T0 + (pre[0].span.end - LO)
    recs.append(load.Record(
        spec=load.Spec(rid=99, tokens=np.zeros(200, np.int32), n_new=4),
        due=0.0, status="done", out=[0] * 4,
        times=[end + 0.005 + 0.04 * j for j in range(4)]))
    return recs


@pytest.mark.parametrize("name,want", [
    ("decode_roofline", PARENT_DECODE_ROOFLINE),
    ("serve_mfu_pct", PARENT_SERVE_MFU_PCT)])
def test_readers_on_the_excerpt_read_the_parents_numbers(name, want):
    config = config_of("smollm-360m-dense")
    ref = reference(config)
    cfg = harness.model_config(config, ref)
    chip = tr.read(EXCERPT)
    calls = tr.calls(chip, LO, HI)
    run = harness.Run(
        seconds=51.0, w0=0.0, w1=1e9, setup_s=1.0,
        records=excerpt_records(calls), batch=32,
        work=ref.work(cfg, harness.param_shapes(cfg)),
        peaks=peaks.peak("TPU v5 lite"), t0=T0, t1=T0 + (HI - LO),
        trace=chip, trace_lo=LO, trace_hi=HI, calls=calls)
    assert harness.read_metric(REPO, name)(run) == want


def parent_make(shapes, seed):
    """The weight rule before it went by shape alone: by the leaf's
    name, ``w``, ``B`` and ``C`` drawn N(0, 1/d_in), in one jitted call."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sds) in enumerate(flat):
            name = str(getattr(path[-1], "key", path[-1]))
            if name == "scale":
                out.append(jnp.ones(sds.shape, sds.dtype))
                continue
            assert name in ("embed", "w", "B", "C"), path
            std = sds.shape[-1 if name == "embed" else -2] ** -0.5
            out.append((std * jax.random.normal(jax.random.fold_in(key, i),
                                                sds.shape, jnp.float32)
                        ).astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(weights._key(seed))


def test_weights_at_the_tiny_size_are_bit_identical_to_the_parents():
    config = config_of("smollm-360m-dense")
    ref = reference(config)
    config["model"].update(ref.TINY_MODEL)
    cfg = harness.model_config(config, ref)
    shapes = harness.param_shapes(cfg)
    seed = 2 ** 31 + 77
    got = weights.make(shapes, seed)
    want = parent_make(shapes, seed)
    h = hashlib.sha256()
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(got)[0],
                            jax.tree.leaves(want)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), path
        h.update(jax.tree_util.keystr(path).encode())
        h.update(np.asarray(a).tobytes())
    assert h.hexdigest() == PARENT_TINY_WEIGHTS


def test_weights_fill_every_leaf_of_an_moe_tree():
    """Stacked experts ``(L, E, d_in, d_out)`` and the router need no
    rule of their own: each is drawn with std 1/sqrt(d_in)."""
    from repro import configs
    cfg = configs.get_config("granite-moe-1b-a400m").reduced()
    shapes = harness.param_shapes(cfg)
    got = weights.make(shapes, 2 ** 33 + 5)
    names = []
    for path, a in jax.tree_util.tree_flatten_with_path(got)[0]:
        key = jax.tree_util.keystr(path)
        names.append(key)
        a = np.asarray(a, np.float64)
        if key.endswith("['scale']"):
            assert np.all(a == 1.0), key
            continue
        d_in = a.shape[-1] if key.endswith("['embed']") else a.shape[-2]
        assert a.std() == pytest.approx(d_in ** -0.5, rel=0.1), key
        assert abs(a.mean()) < 0.1 * d_in ** -0.5, key
    assert any("['moe']['w_gate']" in n for n in names)
    assert any("['moe']['router']['w']" in n for n in names)


@pytest.mark.parametrize("stacked", [True, False])
def test_from_program_walks_every_run_in_layer_order(stacked):
    """Three window layers to one full one, over 8 layers: four runs
    ``(swa 3, attn 1, swa 3, attn 1)``, stacked or lists of layers; each
    layer's norm scale holds its index, and the head is untied."""
    gqa_window = harness.load_module(
        REPO, os.path.join("tests", "bench", "windowed", "gqa_window.py"))
    with open(os.path.join(REPO, "tests", "bench", "windowed",
                           "gqa-window-tiny.json")) as f:
        config = json.load(f)
    cfg = harness.model_config(config, gqa_window)
    assert cfg.layer_runs() == (("swa", 3), ("attn", 1), ("swa", 3),
                                ("attn", 1))
    params = weights.make(harness.param_shapes(cfg), 3)
    at = 0
    for r, (_, n) in enumerate(cfg.layer_runs()):
        run = params["decoder"][f"run{r}"]
        run["ln1"]["scale"] = jnp.arange(at, at + n, dtype=jnp.float32
                                         )[:, None] * jnp.ones((n, 64))
        if not stacked:
            params["decoder"][f"run{r}"] = [
                jax.tree.map(lambda a, i=i: a[i], run) for i in range(n)]
        at += n
    plain = gqa_window.from_program(params, cfg)
    assert [float(lp["ln1"][0]) for lp in plain["layers"]] == list(range(8))
    assert plain["layers"][0]["q"]["w"].shape == (64, 4 * 32)
    assert plain["head"]["w"].shape == (64, 256)
    assert gqa_window.sizes(cfg)["windows"] == [16, 16, 16, 0] * 2
