"""Plain float32 reference of a Llama-architecture decoder (SmolLM-360M's
family): token embedding, ``n_layers`` pre-norm blocks of grouped-query
attention with rotary positions and a SwiGLU MLP, final RMSNorm, output
head tied to the embedding.

It imports nothing of the program under test. It has no cache, no
batching, no padding and no kernels: one sequence, every position, every
matmul at ``Precision.HIGHEST``. A linear is ``{"w": (d_in, d_out)}`` or
a factorized ``{"B": (d_in, k), "C": (k, d_out)}`` applied as
``(x @ B) @ C``.

Departures from the published model card: none in the equations. Rotary
positions rotate the first half of each head against the second (the
GPT-NeoX layout Llama checkpoints use in Hugging Face transformers).

``sizes`` keys: ``n_layers d_model n_heads n_kv_heads head_dim d_ff
vocab_size rope_theta norm_eps``.

Params layout::

    {"embed": (V, D), "final_norm": (D,),
     "layers": [{"ln1": (D,), "ln2": (D,), "q", "k", "v", "o",
                 "gate", "up", "down": linear}, ...]}
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
Quant = Optional[Callable[[jax.Array], jax.Array]]


def int8_quant(x: jax.Array) -> jax.Array:
    """Round ``x`` to symmetric int8 with one per-tensor scale, returned
    in float32: an operand computed at int8 (the precision below the
    configuration's bfloat16)."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _q(x, quant: Quant):
    x = x.astype(jnp.float32)
    return quant(x) if quant is not None else x


def _linear(p: Dict, x: jax.Array, quant: Quant) -> jax.Array:
    if "B" in p:
        h = jnp.matmul(_q(x, quant), _q(p["B"], quant), precision=HIGHEST)
        return jnp.matmul(_q(h, quant), _q(p["C"], quant), precision=HIGHEST)
    return jnp.matmul(_q(x, quant), _q(p["w"], quant), precision=HIGHEST)


def _rms(x, scale, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, positions, theta):
    """x (S, H, hd): rotate first half against second half."""
    hd = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = positions[:, None].astype(jnp.float32) * freqs       # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(lp: Dict, sizes: Dict, x: jax.Array,
          quant: Quant = None) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decoder block over the residual stream ``x`` (S, D): the new
    stream, and the input of each of the block's linears."""
    S = x.shape[0]
    H, K, hd = sizes["n_heads"], sizes["n_kv_heads"], sizes["head_dim"]
    eps = sizes["norm_eps"]
    pos = jnp.arange(S)
    causal = pos[None, :] <= pos[:, None]                       # (S, T)
    h = _rms(x, lp["ln1"], eps)
    q = _linear(lp["q"], h, quant).reshape(S, H, hd)
    k = _linear(lp["k"], h, quant).reshape(S, K, hd)
    v = _linear(lp["v"], h, quant).reshape(S, K, hd)
    q = _rope(q, pos, sizes["rope_theta"])
    k = _rope(k, pos, sizes["rope_theta"])
    kh = jnp.repeat(k, H // K, axis=1)           # head h reads kv head h // G
    vh = jnp.repeat(v, H // K, axis=1)
    s = jnp.einsum("shd,thd->hst", _q(q, quant), _q(kh, quant),
                   precision=HIGHEST) * hd ** -0.5
    w = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("hst,thd->shd", _q(w, quant), _q(vh, quant),
                   precision=HIGHEST).reshape(S, H * hd)
    x = x + _linear(lp["o"], a, quant)
    h2 = _rms(x, lp["ln2"], eps)
    m = (jax.nn.silu(_linear(lp["gate"], h2, quant))
         * _linear(lp["up"], h2, quant))
    x = x + _linear(lp["down"], m, quant)
    return x, {"q": h, "k": h, "v": h, "o": a, "gate": h2, "up": h2,
               "down": m}


def logits(params: Dict, sizes: Dict, tokens: jax.Array,
           quant: Quant = None) -> jax.Array:
    """tokens (S,) int32 -> next-token logits (S, V) float32."""
    x = params["embed"].astype(jnp.float32)[tokens]
    for lp in params["layers"]:
        x, _ = block(lp, sizes, x, quant)
    x = _rms(x, params["final_norm"], sizes["norm_eps"])
    return jnp.matmul(_q(x, quant), _q(params["embed"], quant).T,
                      precision=HIGHEST)


def token_gaps(params: Dict, sizes: Dict, tokens: jax.Array,
               served: jax.Array, quant: Quant = None) -> jax.Array:
    """For each position t: how far the float32 logit of ``served[t]``
    lies below the float32 best. With ``quant``, ``served`` is ignored
    and the token is the one a forward at that precision puts first."""
    ref = logits(params, sizes, tokens)
    if quant is not None:
        served = jnp.argmax(logits(params, sizes, tokens, quant), -1)
    pick = jnp.take_along_axis(ref, served[:, None], axis=-1)[:, 0]
    return ref.max(-1) - pick


LINEARS = ("q", "k", "v", "o", "gate", "up", "down")


def _misfit(x: jax.Array, w: jax.Array, B: jax.Array,
            C: jax.Array) -> jax.Array:
    """1 - cos between ``x @ w`` and ``x @ B @ C`` (Frobenius)."""
    y = jnp.matmul(x, w, precision=HIGHEST)
    z = jnp.matmul(jnp.matmul(x, B, precision=HIGHEST), C,
                   precision=HIGHEST)
    ny, nz = jnp.linalg.norm(y), jnp.linalg.norm(z)
    cos = jnp.where(ny * nz > 0, jnp.vdot(y, z) / (ny * nz), 0.0)
    return 1.0 - cos


def factor_misfits(dense: Dict, served: Dict, sizes: Dict,
                   tokens: jax.Array, n: int) -> List[Dict[str, float]]:
    """How far each served linear lies from the dense linear it stands
    for, on the inputs the dense model gives that linear: per layer and
    linear, 1 - cos between ``x @ w`` (dense) and ``x @ B @ C`` (served),
    over the first ``n`` positions of ``tokens``. 0 where the served
    linear acts as the dense one does, about 1 where it is unrelated or
    zero, about 2 where it is negated. Layer by layer, so one layer's
    activations are held at a time; factors are padded with zeros to one
    rank per shape, which leaves ``B @ C`` as it is."""
    keep = (jnp.arange(tokens.shape[0]) < n)[:, None]
    step = jax.jit(lambda lp, x: block(lp, sizes, x))
    fit = jax.jit(_misfit)
    pad: Dict[Tuple[int, int], int] = {}
    for lp in served["layers"]:
        for k in LINEARS:
            if "B" in lp[k]:
                d_in, r = lp[k]["B"].shape
                shape = (d_in, lp[k]["C"].shape[1])
                pad[shape] = max(pad.get(shape, 0), -(-r // 128) * 128)
    x = dense["embed"].astype(jnp.float32)[tokens]
    out = []
    for lp, sp in zip(dense["layers"], served["layers"]):
        x, ins = step(lp, x)
        row = {}
        for k in LINEARS:
            w, f = lp[k]["w"], sp[k]
            if "B" in f:
                B, C = f["B"], f["C"]
                r = pad[w.shape] - B.shape[1]
                B = jnp.pad(B.astype(jnp.float32), ((0, 0), (0, r)))
                C = jnp.pad(C.astype(jnp.float32), ((0, r), (0, 0)))
            else:
                B, C = f["w"].astype(jnp.float32), jnp.eye(
                    w.shape[1], dtype=jnp.float32)
            row[k] = float(fit(ins[k] * keep, w.astype(jnp.float32), B, C))
        out.append(row)
    return out
