"""Random weights made by the benchmark, on the device, in one jitted call.

The program gives only the layout (``jax.eval_shape`` of its own init);
the values come from here, so the plain reference never takes weights
the program made. One rule for every architecture, by a leaf's name and
shape alone: a norm ``scale`` is ones; the ``embed`` is N(0, 1/d_model),
so tied logits are O(1); any other floating leaf of rank 2 or more is a
matrix (or a stack of them: layers, experts) read along its
next-to-last axis, and is drawn N(0, 1/d_in), so each layer's output is
of the order of the residual stream and no layer is a near no-op.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np


def _path_names(path) -> List[str]:
    return [str(getattr(k, "key", getattr(k, "idx", k))) for k in path]


def make(shapes: Any, seed: int) -> Any:
    """A tree of arrays shaped like ``shapes`` (a tree of
    ``ShapeDtypeStruct``), drawn from ``seed``, in each leaf's dtype."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    kinds = []
    for path, sds in flat:
        names = _path_names(path)
        if names[-1] == "scale":
            kinds.append(("ones", None))
        elif names[-1] == "embed":
            kinds.append(("normal", sds.shape[-1] ** -0.5))
        elif (len(sds.shape) >= 2
              and jnp.issubdtype(sds.dtype, jnp.floating)):
            kinds.append(("normal", sds.shape[-2] ** -0.5))
        else:
            raise ValueError(f"no rule for parameter {'/'.join(names)}")

    def build(key):
        out = []
        for i, ((_, sds), (kind, std)) in enumerate(zip(flat, kinds)):
            if kind == "ones":
                out.append(jnp.ones(sds.shape, sds.dtype))
            else:
                k = jax.random.fold_in(key, i)
                out.append((std * jax.random.normal(k, sds.shape,
                                                    jnp.float32)
                            ).astype(sds.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(_key(seed))


def _key(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also past 32 bits."""
    seed = int(seed)
    k = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0x7FFFFFFF)


def load_artifact(path: str) -> Any:
    """A saved parameter tree read with numpy alone: the one directory
    under ``path`` that holds ``manifest.json`` (its ``structure`` of
    nested dicts, lists and leaves) and ``arrays.npz`` (a leaf's array
    under its ``key``; a leaf with an ``alias`` names another leaf's
    array, and gets the same array object back)."""
    found = [d for d in sorted(os.listdir(path))
             if os.path.isfile(os.path.join(path, d, "manifest.json"))]
    if len(found) != 1:
        raise ValueError(f"{path}: expected one saved tree, found {found}")
    base = os.path.join(path, found[0])
    with open(os.path.join(base, "manifest.json")) as f:
        structure = json.load(f)["structure"]
    arrays: Dict[str, np.ndarray] = {}
    with np.load(os.path.join(base, "arrays.npz")) as z:

        def build(spec):
            if spec["kind"] == "dict":
                return {k: build(v) for k, v in spec["items"].items()}
            if spec["kind"] in ("list", "tuple"):
                return [build(v) for v in spec["items"]]
            key = spec.get("alias", spec["key"])
            if key not in arrays:
                arrays[key] = z[key].astype(jnp.dtype(spec["dtype"]))
            return arrays[key]

        return build(structure)
