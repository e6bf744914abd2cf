"""The benchmark harness on the CPU: traffic generation, finding cells,
configurations, mixes and metrics by name, and refusing to run without a
TPU. No timing here means anything."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from bench import harness, load, tiny  # noqa: E402


def mix(name):
    if name == "open":      # the batch mix's lengths, as an open loop
        return dict(mix("batch"), loop="open", rate_per_s=3.0)
    with open(os.path.join(REPO, "bench", "traffic", f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name", ["batch", "open"])
def test_generator_is_deterministic_per_seed(name):
    m = mix(name)
    a = load.generate(m, 2 ** 31 + 5, 49152)
    b = load.generate(m, 2 ** 31 + 5, 49152)
    c = load.generate(m, 6, 49152)
    assert [(s.n_new, s.at) for s in a] == [(s.n_new, s.at) for s in b]
    assert all(np.array_equal(x.tokens, y.tokens) for x, y in zip(a, b))
    assert any(not np.array_equal(x.tokens, y.tokens)
               for x, y in zip(a, c))


@pytest.mark.parametrize("name", ["batch", "open"])
def test_generator_matches_its_declared_parameters(name):
    m = mix(name)
    a = load.generate(m, 11, 49152)
    c = load.generate(m, 12, 49152)
    assert len(a) == m["requests"]
    P = np.array([len(s.tokens) for s in a])
    O = np.array([s.n_new for s in a])
    for arr, d in ((P, m["prompt"]), (O, m["output"])):
        assert arr.min() >= d["min"] and arr.max() <= d["max"]
        assert abs(np.median(arr) - d["median"]) <= 1
    # every block of requests holds the same multiset of lengths, in
    # another order for each seed
    b = m["block"]
    for i in range(0, len(a), b):
        assert sorted(P[i:i + b]) == sorted(P[:b])
        assert sorted(len(s.tokens) for s in c[i:i + b]) == sorted(P[:b])
        assert sorted(O[i:i + b]) == sorted(s.n_new for s in c[i:i + b])
    assert [len(s.tokens) for s in a] != [len(s.tokens) for s in c]
    toks = np.concatenate([s.tokens for s in a])
    assert toks.min() >= 0 and toks.max() < 49152
    if m["loop"] == "open":
        at = np.array([s.at for s in a])
        assert np.all(np.diff(at) > 0)
        rate = len(at) / at[-1]
        assert rate == pytest.approx(m["rate_per_s"], rel=0.02)


def test_bucket_lengths_cover_the_mix():
    assert load.bucket_lengths(mix("batch"), 2048) == [32, 64, 128, 256,
                                                       512, 1024]
    wide = dict(mix("batch"), prompt={"min": 3, "max": 1536})
    assert load.bucket_lengths(wide, 2048) == [4, 8, 16, 32, 64, 128, 256,
                                               512, 1024, 2048]


def add_cell(src):
    """Add a configuration, an open-loop mix, a metric and a cell using
    them to the benchmark copy at ``src``, as files and entries only."""
    cdir = os.path.join(src, "bench", "configs")
    with open(os.path.join(cdir, "smollm-360m-dense.json")) as f:
        conf = json.load(f)
    conf["name"] = "newmodel"
    with open(os.path.join(cdir, "newmodel.json"), "w") as f:
        json.dump(conf, f)
    newmix = dict(mix("open"), rate_per_s=20.0)
    with open(os.path.join(src, "bench", "traffic", "newmix.json"),
              "w") as f:
        json.dump(newmix, f)
    with open(os.path.join(src, "bench", "metrics", "new_metric.py"),
              "w") as f:
        f.write("def read(run):\n    return 42.0\n")
    with open(os.path.join(src, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append(dict(bench["configs"][1], name="newmodel",
                                 file="bench/configs/newmodel.json"))
    bench["workloads"].append({"name": "newmodel.newmix",
                               "config": "newmodel", "traffic": "newmix",
                               "chips": 1, "why": "added by a test"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("newmodel.newmix")
    bench["per_layer"].append({
        "name": "new_metric", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "scheduler",
        "moves": "itl_p95_ms", "workloads": ["newmodel.newmix"]})
    with open(os.path.join(src, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def copy_bench(dst):
    os.makedirs(dst)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return dst


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    src = copy_bench(str(tmp_path / "src"))
    before = {}
    for dp, _, fs in os.walk(os.path.join(src, "bench")):
        for f in fs:
            p = os.path.join(dp, f)
            with open(p, "rb") as fh:
                before[p] = fh.read()
    add_cell(src)
    for p, data in before.items():           # nothing there was edited
        with open(p, "rb") as fh:
            assert fh.read() == data, p
    # the tiny copy, which the CPU tests run, takes the new files as well
    root = tiny.make_root(str(tmp_path / "root"), src=src)

    cell = harness.find_cell(root, "newmodel.newmix", traced=True)
    assert cell.config["name"] == "newmodel"
    assert cell.config["engine"] == dict(cell.config["engine"],
                                         **tiny.ENGINE)
    assert cell.mix["loop"] == "open" and cell.mix["rate_per_s"] == 20.0
    assert cell.mix["prompt"]["max"] == tiny.TOP["prompt"]
    assert [m["name"] for m in cell.metrics] == ["new_metric"]
    assert harness.read_metric(root, "new_metric")(None) == 42.0
    e2e = harness.find_cell(root, "newmodel.newmix", traced=False)
    assert [m["name"] for m in e2e.metrics] == ["itl_p95_ms", "setup_s"]


@pytest.mark.parametrize("name", ["batch", "open"])
def test_tiny_mix_keeps_the_shape_and_fits(name):
    m = tiny.shrink_mix(mix(name))
    assert m["loop"] == mix(name)["loop"]
    assert m["prompt"]["max"] + m["output"]["max"] <= tiny.ENGINE["max_len"]
    for part in ("prompt", "output"):
        d = m[part]
        assert tiny.FLOOR[part] <= d["min"] <= d["median"] <= d["max"]
        assert d["sigma"] == mix(name)[part]["sigma"]
    specs = load.generate(m, 3, 256)
    assert max(len(s.tokens) + s.n_new for s in specs) <= (
        tiny.ENGINE["max_len"])


def test_every_metric_of_benchmark_json_has_a_reader():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(harness.read_metric(REPO, m["name"])), m["name"]
    for w in bench["workloads"]:
        cell = harness.find_cell(REPO, w["name"], traced=False)
        assert "setup_s" in [m["name"] for m in cell.metrics]


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"),
         "--workload", "smollm-360m-dense.batch", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_run_outside_a_checkout_exits_nonzero(tmp_path):
    root = tmp_path / "bare"
    root.mkdir()
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "smollm-360m-dense.batch", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
