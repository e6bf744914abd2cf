"""What decides ``correct``, driven through a whole benchmark run on the
CPU at a tiny size (``bench/tiny.py``), skipping only the harness's look
for a TPU: a sound run is correct; the timed path broken underneath, the
compression broken before its artifact is saved, or the float32
reference's int8 control put in the program's place, is not.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import harness, readings, tiny  # noqa: E402


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("bench") / "root"))


@pytest.fixture
def cache(tmp_path_factory):
    return str(tmp_path_factory.getbasetemp() / "cache")


@pytest.fixture
def run(root, monkeypatch, cache):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    monkeypatch.setenv("REPRO_AOT_CACHE", os.path.join(cache, "aot"))
    monkeypatch.setattr(harness, "RAMP_S", 0.5)

    def go(cell, seed=2 ** 31 + 77, at=None):
        return harness.run(["--workload", cell, "--seed", str(seed),
                            "--seconds", "1", "--trace", "0"],
                           root=at or root, require_tpu=False)
    return go


@pytest.fixture
def measure(root, run):
    def go(cell, seed=2 ** 31 + 77):
        return harness.measure(["--workload", cell, "--seed", str(seed),
                                "--seconds", "1", "--trace", "0"],
                               root=root, require_tpu=False)
    return go


def test_sound_dense_run_is_correct(run):
    res = run("smollm-360m-dense.batch")
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"])[-1] == "requests_failed"


def test_sound_compressed_run_is_correct(run):
    res = run("smollm-360m-drank20.batch")
    assert res["correct"], res["checks"]
    assert res["checks"]["ratio_error"]["value"] <= 0.01


def test_int8_control_in_the_programs_place_is_not_correct(measure):
    m = measure("smollm-360m-dense.batch")
    ref = m.ref
    served = jax.device_put(harness.reference_params(m))
    with jax.default_matmul_precision("highest"):
        gaps = harness.gaps_of(ref, served, ref.sizes(m.cfg),
                               m.picked, m.config["engine"]["max_len"],
                               ref.int8_quant)
    assert max(gaps) > m.config["correct"]["token_gap"], gaps


def _broken_decode(monkeypatch, fault):
    from repro.serve import aot
    real = aot.AotRegistry.decode

    def decode(self, params, cache, tokens, *, level=0):
        logits, new = real(self, params, cache, tokens, level=level)
        if fault == "token":       # each decoded token altered as produced
            return jnp.roll(logits, 1, axis=-1), new
        return logits, cache       # the step returns its state unchanged
    monkeypatch.setattr(aot.AotRegistry, "decode", decode)


@pytest.mark.parametrize("fault", ["token", "state"])
def test_broken_timed_path_is_not_correct(run, monkeypatch, fault):
    _broken_decode(monkeypatch, fault)
    res = run("smollm-360m-dense.batch")
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["random", "zero"])
def test_broken_compression_is_not_correct(run, monkeypatch, tmp_path,
                                           fault):
    """The program serves what it saved, so its tokens agree with a
    reference of the same factors; only the factors against the dense
    weights show the fault."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv("REPRO_AOT_CACHE", str(tmp_path / "aot"))
    from repro.core import compress as CC
    real = CC.save_plan
    rng = np.random.default_rng(5)

    def broken(node):
        if isinstance(node, dict):
            if "C" in node and "B" in node:
                C = np.asarray(node["C"])
                # redrawn at the factor's own scale, so the model it
                # serves stays in range
                C = (rng.standard_normal(C.shape) * C.std()
                     if fault == "random" else np.zeros(C.shape))
                return dict(node, C=jnp.asarray(C, node["C"].dtype))
            return {k: broken(v) for k, v in node.items()}
        if isinstance(node, list):
            return [broken(v) for v in node]
        return node

    def save_plan(ckpt_dir, list_params, plan, cfg=None):
        return real(ckpt_dir, broken(list_params), plan, cfg)
    monkeypatch.setattr(CC, "save_plan", save_plan)
    res = run("smollm-360m-drank20.batch")
    assert not res["correct"], res["checks"]
    c = res["checks"]
    assert c["token_gap"]["value"] <= c["token_gap"]["limit"], c
    assert c["factor_misfit"]["value"] > c["factor_misfit"]["limit"], c


@pytest.mark.parametrize("fault", readings.FAULTS)
def test_planted_factor_faults_read_above_the_limit(measure, fault):
    m = measure("smollm-360m-drank20.batch")
    ref = m.ref
    sizes, max_len = ref.sizes(m.cfg), m.config["engine"]["max_len"]
    served = harness.reference_params(m)
    dense = harness.dense_reference_params(m)
    with jax.default_matmul_precision("highest"):
        fits = harness.misfits_of(ref, dense, readings.planted(
            served, fault, m.seed), sizes, m.picked[0], max_len)
    worst = max(v for row in fits for v in row.values())
    assert worst > m.config["correct"]["factor_misfit"], fits


def test_a_mix_added_as_a_file_runs(run, tmp_path):
    """An open-loop mix and a cell that uses it, added as files and
    entries only, run through the harness as they are."""
    from test_bench_harness import add_cell, copy_bench
    src = copy_bench(str(tmp_path / "src"))
    add_cell(src)
    root = tiny.make_root(str(tmp_path / "root"), src=src)
    res = run("newmodel.newmix", at=root)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}
