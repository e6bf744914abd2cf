"""A copy of the benchmark at a size a CPU test can run: the same files,
with each configuration's ``model`` block cut to its reference module's
``TINY_MODEL`` (a few narrow layers, a short context), and mixes short
enough to finish in a second. For the benchmark's own tests; no cell of
``BENCHMARK.json`` runs at these sizes."""
from __future__ import annotations

import json
import os
import shutil
from typing import Dict

from bench.harness import ROOT, load_module

ENGINE = {"batch": 4, "max_len": 64}
COMPRESSION = {"calib_samples": 8, "calib_seq": 32}
# at these sizes, over 16 runs on the CPU (8 seeds x both configurations,
# 64 sampled requests each): the program's widest gap read 0.004-0.047,
# the int8 control's 0.135-0.392; the compressed configuration's factor
# misfit 0.095-0.130, planted faults 1.0 (C zero) to 1.97 (C negated);
# each limit lies between
CORRECT = {"sample": 64, "token_gap": 0.08, "factor_misfit": 0.5}
# every mix is shrunk to these longest prompt and output (their sum fits
# ENGINE's max_len), its other lengths scaled alike and kept at least
# the floor; closed loops get one client per slot
TOP = {"prompt": 36, "output": 24}
FLOOR = {"prompt": 4, "output": 2}


def shrink_mix(mix: Dict) -> Dict:
    """A mix at the tiny size: the same shape of lengths and the same
    loop, each length scaled by its longest's factor."""
    out = dict(mix)
    for part, top in TOP.items():
        d = dict(mix[part])
        f = top / d["max"]
        lo = FLOOR[part]
        d["max"] = top
        d["median"] = min(top, max(lo, round(d["median"] * f)))
        d["min"] = min(d["median"], max(lo, round(d["min"] * f)))
        out[part] = d
    if "clients" in out:
        out["clients"] = min(int(out["clients"]), ENGINE["batch"])
    return out


def _merge(base, over):
    for k, v in over.items():
        if isinstance(v, dict) and isinstance(base.get(k), dict):
            _merge(base[k], v)
        else:
            base[k] = v
    return base


def make_root(dst: str, src: str = ROOT) -> str:
    """Copy ``BENCHMARK.json`` and ``bench/`` from ``src`` to ``dst``,
    shrink every configuration and mix, and link the program's sources.
    Returns ``dst``."""
    os.makedirs(dst, exist_ok=True)
    shutil.copy(os.path.join(src, "BENCHMARK.json"), dst)
    shutil.copytree(os.path.join(src, "bench"), os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(dst, "src"))
    cdir = os.path.join(dst, "bench", "configs")
    for f in os.listdir(cdir):
        p = os.path.join(cdir, f)
        with open(p) as fh:
            c = json.load(fh)
        tiny_model = load_module(dst, c["reference"]).TINY_MODEL
        _merge(c, {"model": tiny_model, "engine": ENGINE,
                   "correct": CORRECT})
        if c.get("compression"):
            _merge(c["compression"], COMPRESSION)
        with open(p, "w") as fh:
            json.dump(c, fh)
    tdir = os.path.join(dst, "bench", "traffic")
    for f in os.listdir(tdir):
        p = os.path.join(tdir, f)
        with open(p) as fh:
            m = json.load(fh)
        with open(p, "w") as fh:
            json.dump(shrink_mix(m), fh)
    return dst
