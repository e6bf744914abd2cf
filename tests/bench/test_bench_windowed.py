"""A configuration of another architecture joins the benchmark by files
alone: a grouped-query decoder with three sliding-window layers (window
16) to one full layer, head_dim 32 at hidden 64 over 4 heads, 8 layers
(four runs of the program's stack) and an untied head. Its
configuration file and reference module (``tests/bench/windowed/``) and
a ``configs`` and ``workloads`` entry are added to the benchmark's tiny
copy, whose harness files stay byte for byte the repository's; a whole
run on the CPU then reads ``correct``, and the reference with its window
removed does not.

At this size, over 10 seeds on the CPU (64 sampled requests of up to 60
positions each, so every window layer's ring wraps): a sound run's widest
gap read 0.0163-0.0980, the reference without its window 2.330-4.460;
the limit, 0.3, lies three times above the one and nearly eight times
below the other. The int8 control read 0.158-0.466: at 8 layers the
bfloat16 noise comes within three times of it, so this test leaves it out.
"""
import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

from bench import harness, tiny  # noqa: E402

HERE = os.path.join(REPO, "tests", "bench", "windowed")
NAME = "gqa-window-tiny"
CELL = f"{NAME}.batch"


def add_windowed(root):
    """The configuration's two files and its entries, added to the
    benchmark copy at ``root``."""
    shutil.copy(os.path.join(HERE, "gqa_window.py"),
                os.path.join(root, "bench", "reference"))
    shutil.copy(os.path.join(HERE, f"{NAME}.json"),
                os.path.join(root, "bench", "configs"))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": NAME, "source": "tests/bench/windowed",
        "file": f"bench/configs/{NAME}.json", "reduced": [],
        "why": "windowed and full attention in one stack"})
    bench["workloads"].append({
        "name": CELL, "config": NAME, "traffic": "batch", "chips": 1,
        "why": "the batch mix through window layers whose rings wrap"})
    for m in bench["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append(CELL)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def harness_files(root):
    """Every file under ``bench/`` a configuration does not bring."""
    out = {}
    for dp, dns, fs in os.walk(os.path.join(root, "bench")):
        dns[:] = [d for d in dns if d not in ("__pycache__", "testdata",
                                              "configs", "traffic")]
        for f in fs:
            p = os.path.join(dp, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = fh.read()
    return out


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    r = tiny.make_root(str(tmp_path_factory.mktemp("bench") / "root"))
    add_windowed(r)
    return r


@pytest.fixture
def args(monkeypatch, tmp_path_factory):
    cache = str(tmp_path_factory.getbasetemp() / "cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache)
    monkeypatch.setenv("REPRO_AOT_CACHE", os.path.join(cache, "aot"))
    monkeypatch.setattr(harness, "RAMP_S", 0.5)
    return ["--workload", CELL, "--seed", str(2 ** 31 + 91),
            "--seconds", "1", "--trace", "0"]


def test_harness_files_are_the_repositorys(root):
    got, want = harness_files(root), harness_files(REPO)
    added = os.path.join("bench", "reference", "gqa_window.py")
    assert set(got) == set(want) | {added}
    for rel, data in want.items():
        assert got[rel] == data, rel


def test_sound_windowed_run_is_correct(root, args):
    res = harness.run(args, root=root, require_tpu=False)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"itl_p95_ms", "setup_s"}


def test_reference_without_its_window_is_not_correct(root, args,
                                                     monkeypatch):
    m = harness.measure(args, root=root, require_tpu=False)
    # the longest sampled request runs past the window, which is what
    # the fault needs to show
    assert max(r.prompt_len + len(r.out) for r in m.picked) > 16
    sizes = m.ref.sizes
    monkeypatch.setattr(m.ref, "sizes", lambda cfg: dict(
        sizes(cfg), windows=[0] * cfg.n_layers))
    checks = harness.judge(m)
    gap = checks["token_gap"]
    assert gap["value"] > gap["limit"], checks
