"""Operations and bytes that serving work needs, counted from shapes.

These count the work the algorithm needs, not what an implementation
happens to do: parameters are read once per step, each leaf at the
narrower of its stored dtype and the compute dtype; the KV cache is read
only over each slot's live positions, and a windowed layer's only over
its window; padding does no useful work. A faster implementation raises
a share computed from these counts; it can not change the counts.

Parameter trees are walked as nested dicts and lists of arrays (or
``jax.ShapeDtypeStruct``). A dict with a ``"w"`` leaf is a dense linear
(``(..., d_in, d_out)``, leading axes stack layers); a dict with ``"B"``
and ``"C"`` is a factorized linear ``x @ B @ C``. A key ``"embed"`` of
shape ``(vocab, d_model)`` is the embedding, also the output head when
embeddings are tied.
"""
from __future__ import annotations

import collections
from typing import Iterable, Iterator, List, Tuple

import numpy as np


def _itemsize(dtype) -> int:
    return np.dtype(dtype).itemsize


def served_itemsize(stored_dtype, compute_dtype) -> int:
    """Bytes a step must read per element of a leaf: the narrower of how
    it is stored and how it is computed with."""
    return min(_itemsize(stored_dtype), _itemsize(compute_dtype))


def _unique_leaves(tree) -> Iterator:
    seen = set()
    stack = [tree]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            stack.extend(node.values())
        elif isinstance(node, (list, tuple)):
            stack.extend(node)
        elif hasattr(node, "shape") and hasattr(node, "dtype"):
            if id(node) not in seen:       # a shared basis counts once
                seen.add(id(node))
                yield node


def param_bytes(tree, compute_dtype) -> int:
    """Bytes of the served parameters, each distinct leaf once."""
    return sum(int(np.prod(a.shape)) * served_itemsize(a.dtype,
                                                       compute_dtype)
               for a in _unique_leaves(tree))


def param_count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in _unique_leaves(tree))


def _linears(tree) -> Iterator[dict]:
    stack = [tree]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            if "w" in node or ("B" in node and "C" in node):
                yield node
                continue
            stack.extend(node.values())
        elif isinstance(node, (list, tuple)):
            stack.extend(node)


def linear_flops_per_token(tree, tied_head: bool) -> int:
    """Multiply-add FLOPs (2 per MAC) of every linear for one token,
    the output head included. A factorized linear costs its two factors,
    each member of a group paying for the basis it applies."""
    total = 0
    for node in _linears(tree):
        if "w" in node:
            total += 2 * int(np.prod(node["w"].shape))
        else:
            total += 2 * (int(np.prod(node["B"].shape))
                          + int(np.prod(node["C"].shape)))
    if tied_head:
        total += 2 * int(np.prod(tree["embed"].shape))
    return total


def _by_window(windows: Iterable[int]) -> List[Tuple[int, int]]:
    """(window, layers with it) per distinct window, in first-seen order."""
    return list(collections.Counter(windows).items())


def attention_flops(context: float, windows: Iterable[int], n_heads: int,
                    head_dim: int) -> float:
    """FLOPs of scores and weighted values for one query token attending
    over ``context`` positions, over the layers whose attention windows
    are ``windows``: a layer reads at most its window (0: all of them)."""
    total = 0
    for w, n in _by_window(windows):
        c = min(context, w) if w else context
        total += 4 * c * n_heads * head_dim * n
    return total


def prompt_attention_flops(prompt_len: int, windows: Iterable[int],
                           n_heads: int, head_dim: int) -> float:
    """FLOPs of scores and weighted values for every token of a prompt of
    ``prompt_len`` attending over its own context (itself and the tokens
    before it), each layer over at most its window."""
    P = prompt_len
    total = 0
    for w, n in _by_window(windows):
        pos = (P * (P + 1) / 2 if not w or P <= w
               else w * (w + 1) / 2 + (P - w) * w)
        total += 4 * pos * n_heads * head_dim * n
    return total


def kv_bytes(slots: int, context: float, windows: Iterable[int],
             n_kv_heads: int, head_dim: int, cache_dtype) -> int:
    """Bytes of K and V that ``slots`` slots at a mean live context of
    ``context`` positions read, each layer over at most its window."""
    per_pos = 2 * n_kv_heads * head_dim * _itemsize(cache_dtype)
    return sum(per_pos * n * round(slots * (min(context, w) if w
                                            else context))
               for w, n in _by_window(windows))


def roofline_seconds(flops: float, nbytes: float, peak_flops: float,
                     peak_bw: float) -> Tuple[float, str]:
    """The least time the work can take on a chip and which bound sets
    it: ``(seconds, "compute" | "memory")``."""
    tc, tm = flops / peak_flops, nbytes / peak_bw
    return (tc, "compute") if tc >= tm else (tm, "memory")
