"""Flash attention (online-softmax) Pallas kernel: causal, sliding-window,
logit-softcap, GQA — the prefill/serve hot spot.

TPU adaptation: grid (batch·q_heads, q-blocks, kv-blocks) with the kv step
innermost ("arbitrary"); per-(head, q-block) running max/denominator/accum
live in VMEM scratch across kv steps. GQA never materializes repeated K/V —
the kv BlockSpec index map folds the q-head → kv-head mapping (h // group)
into the block index, so HBM reads stay at kv-head width.

Window/causal masking is positional per tile; fully-masked tiles are still
visited (grid is static) but their exp() work is zeroed — block-level
skipping is a §Perf iteration knob (see EXPERIMENTS.md).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(nkv: int, bq: int, bk: int, scale: float, causal: bool,
            window: int, softcap: float, kv_len: int,
            q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                   # (bq, hd)
    k = k_ref[0].astype(jnp.float32)                   # (bk, hd)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    qpos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = jnp.ones((bq, bk), dtype=jnp.bool_)
    if causal:
        mask &= kpos <= qpos
    if window:
        mask &= kpos > qpos - window
    if kv_len:
        # ragged key axis: columns past the real T are alignment padding
        mask &= kpos < kv_len
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)                             # masked -> ~0
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ki == nkv - 1)
    def _emit():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_bh(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       heads: int, kv_heads: int, causal: bool = True,
                       window: int = 0, softcap: float = 0.0,
                       bq: int = 128, bk: int = 128, kv_len: int = 0,
                       interpret: bool = False) -> jax.Array:
    """q: (B·H, S, hd); k/v: (B·KV, T, hd). q row b·H + h attends kv row
    b·KV + h // (H/KV) — the GQA fold lives in the kv index map, so repeated
    K/V are never materialized. kv_len > 0 marks key columns >= kv_len as
    alignment padding (masked in-kernel), which keeps ragged non-causal
    shapes on the kernel path. Returns (B·H, S, hd)."""
    BH, S, hd = q.shape
    BKV, T, _ = k.shape
    assert S % bq == 0 and T % bk == 0, (S, T, bq, bk)
    assert BH // heads == BKV // kv_heads, (BH, heads, BKV, kv_heads)
    nq = S // bq
    nkv = T // bk
    scale = hd ** -0.5
    G = heads // kv_heads

    def kv_index(bh, qi, ki):
        b = bh // heads
        h = bh % heads
        return (b * kv_heads + h // G, ki, 0)

    return pl.pallas_call(
        functools.partial(_kernel, nkv, bq, bk, scale, causal, window,
                          softcap, kv_len),
        grid=(BH, nq, nkv),
        in_specs=[
            pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
            pl.BlockSpec((1, bk, hd), kv_index),
            pl.BlockSpec((1, bk, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, hd), lambda bh, qi, ki: (bh, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),    # running max
            pltpu.VMEM((bq, 1), jnp.float32),    # denominator
            pltpu.VMEM((bq, hd), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)
