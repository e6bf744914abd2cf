"""Plain float32 reference of a grouped-query decoder whose layers mix
sliding-window and full attention, as Gemma 2 and 3, Mellum 2 and others
lay them out: ``layer_types`` of the configuration's ``model`` block
repeats some ``sliding_attention`` layers and then some
``full_attention`` ones, and a sliding layer attends over its last
``sliding_window`` positions (itself included). Everything else is the
Llama block of ``bench/reference/llama.py``, whose functions this module
builds on; ``head_dim`` is the block's own, and the output head may be
untied.

A test-only configuration: it shows that a reference module and a
configuration file, added as files, carry an architecture through the
harness. ``sizes`` adds ``windows``, each layer's window (0: full).
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from bench.reference import llama
from bench.reference.llama import (  # noqa: F401 (the harness's names)
    HIGHEST, Quant, _linear, _q, _rms, _rope, from_program, int8_quant,
    linear_counts)

TINY_MODEL = {"num_hidden_layers": 8, "hidden_size": 64,
              "num_attention_heads": 4, "num_key_value_heads": 2,
              "head_dim": 32, "intermediate_size": 128, "vocab_size": 256,
              "max_position_embeddings": 64, "sliding_window": 16,
              "layer_types": ["sliding_attention"] * 3 + ["full_attention"]
              + ["sliding_attention"] * 3 + ["full_attention"]}


def _pattern(layer_types) -> Tuple[int, int]:
    """(sliding layers, full layers) of the period ``layer_types``
    repeats."""
    n_local = layer_types.index("full_attention")
    n_global = next((i for i, t in enumerate(layer_types[n_local:])
                     if t != "full_attention"),
                    len(layer_types) - n_local)
    period = ["sliding_attention"] * n_local + ["full_attention"] * n_global
    if layer_types != (period * len(layer_types))[:len(layer_types)]:
        raise ValueError(f"layer_types repeat no period: {layer_types}")
    return n_local, n_global


def program_config(model: Dict) -> Dict:
    over = llama.program_config(model)
    over["local_global_pattern"] = _pattern(model["layer_types"])
    over["sliding_window"] = model["sliding_window"]
    return over


def sizes(cfg) -> Dict:
    """Llama's sizes and each layer's window, from the period of
    ``cfg.local_global_pattern``: its sliding layers first."""
    n_local, n_global = cfg.local_global_pattern
    windows = [cfg.sliding_window if i % (n_local + n_global) < n_local
               else 0 for i in range(cfg.n_layers)]
    return dict(llama.sizes(cfg), windows=windows)


def work(cfg, params) -> Dict:
    return dict(llama.work(cfg, params), windows=sizes(cfg)["windows"])


def block(lp: Dict, sizes: Dict, window: int, x: jax.Array,
          quant: Quant = None) -> jax.Array:
    """One decoder block over the residual stream ``x`` (S, D), each
    position attending over at most its last ``window`` (0: all)."""
    S = x.shape[0]
    H, K, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    eps = sizes["norm_eps"]
    pos = jnp.arange(S)
    seen = pos[None, :] <= pos[:, None]                         # (S, T)
    if window:
        seen &= pos[None, :] > pos[:, None] - window
    h = _rms(x, lp["ln1"], eps)
    q = _rope(_linear(lp["q"], h, quant).reshape(S, H, hd), pos,
              sizes["rope_theta"])
    k = _rope(_linear(lp["k"], h, quant).reshape(S, K, hd), pos,
              sizes["rope_theta"])
    v = _linear(lp["v"], h, quant).reshape(S, K, hd)
    kh = jnp.repeat(k, H // K, axis=1)
    vh = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("shd,thd->hst", _q(q, quant), _q(kh, quant),
                   precision=HIGHEST) * hd ** -0.5
    w = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hst,thd->shd", _q(w, quant), _q(vh, quant),
                   precision=HIGHEST).reshape(S, H * hd)
    x = x + _linear(lp["o"], a, quant)
    h2 = _rms(x, lp["ln2"], eps)
    m = (jax.nn.silu(_linear(lp["gate"], h2, quant))
         * _linear(lp["up"], h2, quant))
    return x + _linear(lp["down"], m, quant)


def logits(params: Dict, sizes: Dict, tokens: jax.Array,
           quant: Quant = None) -> jax.Array:
    """tokens (S,) int32 -> next-token logits (S, V) float32."""
    x = params["embed"].astype(jnp.float32)[tokens]
    for lp, window in zip(params["layers"], sizes["windows"]):
        x = block(lp, sizes, window, x, quant)
    x = _rms(x, params["final_norm"], sizes["norm_eps"])
    if "head" in params:
        return _linear(params["head"], x, quant)
    return jnp.matmul(_q(x, quant), _q(params["embed"], quant).T,
                      precision=HIGHEST)


def token_gaps(params: Dict, sizes: Dict, tokens: jax.Array,
               served: jax.Array, quant: Quant = None) -> jax.Array:
    """As ``llama.token_gaps``, with this module's forward."""
    ref = logits(params, sizes, tokens)
    if quant is not None:
        served = jnp.argmax(logits(params, sizes, tokens, quant), -1)
    pick = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return ref.max(-1) - pick
