"""Ragged single-token decode attention over the KV cache pool — the
serving-loop hot spot (DESIGN.md §3.4).

The decode step attends ONE new query per sequence against that sequence's
live cache prefix. The jnp reference path scores the entire (B, L) cache
with a dense fp32 mask every step; at serving shapes (L = max_len, most
slots short) nearly all of that work is masked out. This kernel instead:

  * takes a per-slot length vector (B,) as a SCALAR-PREFETCH operand, so
    block index maps can see it before the body runs;
  * clamps the kv block index to the slot's live prefix — grid steps past
    ``ceil(len/bk)`` re-address the previous block, and Pallas skips the
    DMA for an unchanged block index, so dead cache blocks never leave HBM
    (the compute for those steps is skipped with ``pl.when``);
  * handles both cache layouts: full (slot s holds position s; valid iff
    s < len) and ring buffer (slot s holds the latest position p ≡ s mod
    window; valid iff (pos - s) mod window < min(len, window));
  * is GQA-aware: each grid step DMAs one (bk, KV, hd) tile holding EVERY
    kv head (the cache's own layout — Mosaic needs the last two block dims
    to be the full (KV, hd) unless KV is a multiple of 8) and a static
    loop over kv heads scores the G grouped q-heads of each against it —
    repeated K/V never materialize;
  * accumulates in fp32 with the online-softmax recurrence (running max m,
    denominator l, accumulator acc in VMEM scratch across kv steps).

Grid (B, n_blocks): slot-parallel, kv steps innermost ("arbitrary").

VMEM budget per step (bf16 cache, fp32 acc), bk=128: k/v tiles
2·128·KV·hd·2 B (KV=8, hd=128 → 512 KiB), q tile KV·G·hd·2 B, scratch
KV·(2·G + G·hd)·4 B — well inside the 16 MiB budget even double-buffered;
the kernel is DMA-bound, which is exactly why block skipping is the win."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _online_softmax_step(nkv_heads: int, scale: float, softcap: float,
                         valid_fn, q_ref, k_ref, v_ref, m_ref, l_ref,
                         acc_ref):
    """One kv block of the online-softmax recurrence, for every kv head of
    the (bk, KV, hd) tile. ``valid_fn(G, bk)`` gives the (G, bk) mask of
    live cache slots in this block."""
    for h in range(nkv_heads):
        q = q_ref[0, h].astype(jnp.float32) * scale    # (G, hd)
        k = k_ref[0, :, h].astype(jnp.float32)         # (bk, hd)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        s = jnp.where(valid_fn(*s.shape), s, NEG_INF)
        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, :, h], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[h] = m_new


def _init_scratch(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def _emit(o_ref, l_ref, acc_ref):
    l = jnp.maximum(l_ref[...], 1e-30)
    o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def _scratch(KV: int, G: int, hd: int):
    return [
        pltpu.VMEM((KV, G, 1), jnp.float32),     # running max
        pltpu.VMEM((KV, G, 1), jnp.float32),     # denominator
        pltpu.VMEM((KV, G, hd), jnp.float32),    # output accumulator
    ]


def _kernel(nkv: int, nkv_heads: int, bk: int, scale: float, window: int,
            softcap: float, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref,
            l_ref, acc_ref):
    b = pl.program_id(0)
    ki = pl.program_id(1)
    ln = len_ref[b]                                    # pos + 1; 0 = dead slot

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    if window:
        # ring: every block may hold live slots — except a dead slot
        # (ln == 0, e.g. freshly purged), which must emit exact zeros
        # rather than softmax over an all-masked row
        bound = jnp.where(ln > 0, nkv, 0)
    else:
        bound = (ln + bk - 1) // bk    # full cache: live prefix only (0 dead)

    def valid(G, bkk):
        slot = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bkk), 1)
        if window:
            # ring layout: slot s holds position pos - ((pos - s) mod w)
            age = jnp.mod(ln - 1 - slot, window)
            return (age < jnp.minimum(ln, window)) & (slot < window)
        return slot < ln

    @pl.when(ki < bound)
    def _step():
        _online_softmax_step(nkv_heads, scale, softcap, valid, q_ref, k_ref,
                             v_ref, m_ref, l_ref, acc_ref)

    @pl.when(ki == nkv - 1)
    def _done():
        _emit(o_ref, l_ref, acc_ref)


def decode_attention_bkgh(q: jax.Array, k: jax.Array, v: jax.Array,
                          lengths: jax.Array, *, window: int = 0,
                          softcap: float = 0.0, bk: int = 128,
                          interpret: bool = False) -> jax.Array:
    """q: (B, KV, G, hd) one token per sequence; k/v: (B, L, KV, hd) cache
    pool (L a multiple of bk — the ops wrapper pads); lengths: (B,) int32 =
    pos + 1 per slot (0 marks a dead/purged slot, whose output row is exact
    zeros). window > 0 selects the ring-buffer layout (real ring size =
    window; L may carry alignment padding past it).
    Returns (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    L = k.shape[1]
    assert L % bk == 0, (L, bk)
    assert lengths.shape == (B,) and lengths.dtype == jnp.int32
    nkv = L // bk
    scale = hd ** -0.5

    def kv_index(b, ki, len_ref):
        if window:
            return (b, ki, 0, 0)
        # clamp to the live prefix; the outer max guards length-0 slots
        # (freshly purged), whose nb - 1 would otherwise address block -1
        nb = (len_ref[b] + bk - 1) // bk
        return (b, jnp.maximum(jnp.minimum(ki, nb - 1), 0), 0, 0)

    def q_index(b, ki, len_ref):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, nkv),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), q_index),
            pl.BlockSpec((1, bk, KV, hd), kv_index),
            pl.BlockSpec((1, bk, KV, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), q_index),
        scratch_shapes=_scratch(KV, G, hd),
    )
    return pl.pallas_call(
        functools.partial(_kernel, nkv, KV, bk, scale, window, softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lengths, q, k, v)


def _paged_kernel(nb: int, nkv_heads: int, bk: int, scale: float,
                  softcap: float, len_ref, tbl_ref, q_ref, k_ref, v_ref,
                  o_ref, m_ref, l_ref, acc_ref):
    """Same online-softmax recurrence as ``_kernel``'s full-cache path; the
    kv tile for logical block ki arrives via the block-table indirection in
    the index map, so the math here is bit-identical to the contiguous
    kernel given the same token values."""
    b = pl.program_id(0)
    ki = pl.program_id(1)
    ln = len_ref[b]                                    # pos + 1; 0 = dead slot

    @pl.when(ki == 0)
    def _init():
        _init_scratch(m_ref, l_ref, acc_ref)

    bound = (ln + bk - 1) // bk        # live logical blocks (0 for dead slots)

    def valid(G, bkk):
        return ki * bk + jax.lax.broadcasted_iota(jnp.int32, (G, bkk), 1) < ln

    @pl.when(ki < bound)
    def _step():
        _online_softmax_step(nkv_heads, scale, softcap, valid, q_ref, k_ref,
                             v_ref, m_ref, l_ref, acc_ref)

    @pl.when(ki == nb - 1)
    def _done():
        _emit(o_ref, l_ref, acc_ref)


def decode_attention_paged_bkgh(q: jax.Array, k: jax.Array, v: jax.Array,
                                lengths: jax.Array, table: jax.Array, *,
                                softcap: float = 0.0,
                                interpret: bool = False) -> jax.Array:
    """Block-table paged variant of :func:`decode_attention_bkgh` (full
    cache layout only — ring/window stays contiguous).

    q: (B, KV, G, hd); k/v: (P, bk, KV, hd) — one flat arena of P physical
    blocks shared by every slot, block 0 reserved as the never-written null
    block; lengths: (B,) int32 = pos + 1 (0 = dead slot, exact-zero output);
    table: (B, NB) int32 — logical block j of slot b lives in physical
    block table[b, j].

    Both the lengths AND the table ride as scalar-prefetch operands, so the
    kv index map resolves the indirection before the body runs: grid step
    ki of slot b DMAs arena block table[b, clamp(ki)]. Steps past the live
    prefix re-address the previous physical block — Pallas skips the DMA
    for an unchanged index, exactly like the contiguous clamp — and their
    compute is skipped with ``pl.when``. Returns (B, KV, G, hd)."""
    B, KV, G, hd = q.shape
    P, bk = k.shape[0], k.shape[1]
    NB = table.shape[1]
    assert table.shape == (B, NB) and table.dtype == jnp.int32, table
    assert lengths.shape == (B,) and lengths.dtype == jnp.int32
    scale = hd ** -0.5

    def kv_index(b, ki, len_ref, tbl_ref):
        nb_live = (len_ref[b] + bk - 1) // bk
        j = jnp.maximum(jnp.minimum(ki, nb_live - 1), 0)
        return (tbl_ref[b, j], 0, 0, 0)

    def q_index(b, ki, len_ref, tbl_ref):
        return (b, 0, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, NB),
        in_specs=[
            pl.BlockSpec((1, KV, G, hd), q_index),
            pl.BlockSpec((1, bk, KV, hd), kv_index),
            pl.BlockSpec((1, bk, KV, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, KV, G, hd), q_index),
        scratch_shapes=_scratch(KV, G, hd),
    )
    return pl.pallas_call(
        functools.partial(_paged_kernel, NB, KV, bk, scale, softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), q.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lengths, table, q, k, v)
