"""Fused low-rank matmul kernel: y = (x @ B) @ C without the rank-k
intermediate touching HBM — the deploy-time hot spot of every D-Rank
compressed linear (DESIGN.md §3).

Why fusion matters: the factorized pair reads (K·R + R·N) weight bytes
instead of K·N, but an unfused implementation round-trips t = x·B
(M·R values) through HBM twice. At training/prefill token counts M is
large, so the round-trip rivals the weight traffic — fusing keeps t in a
VMEM scratch accumulator.

Structure — a PHASED grid over (m-blocks, k-steps + n-steps):
  phase 1 (s < nk):   t[bm, R] += x[bm, bk] @ B[bk, R]      (MXU, fp32 acc)
  phase 2 (s >= nk):  y[bm, bn] = t[bm, R] @ C[R, bn]
Block index maps clamp into the valid range per phase so each step streams
exactly one (bm×bk) x-tile + (bk×R) B-tile, or one (R×bn) C-tile. All tile
dims are rounded to MXU lane/sublane multiples by the ops wrapper.

VMEM budget per step (bf16 in, fp32 acc), defaults bm=128 bk=512 bn=512:
  x tile 128·512·2 = 128 KiB, B tile 512·R·2 (R≤2048 → ≤2 MiB),
  C tile R·512·2 ≤ 2 MiB, t scratch 128·R·4 ≤ 1 MiB, y 128·512·2 = 128 KiB
  — comfortably inside a 16 MiB VMEM with double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(nk: int, x_ref, b_ref, c_ref, y_ref, t_ref):
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(s < nk)
    def _accumulate():
        t_ref[...] += jnp.dot(x_ref[...], b_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(s >= nk)
    def _emit():
        y_ref[...] = jnp.dot(t_ref[...].astype(c_ref.dtype), c_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(y_ref.dtype)


def _gemv_kernel(nk: int, x_ref, b_ref, c_ref, y_ref, t_ref):
    s = pl.program_id(0)

    @pl.when(s == 0)
    def _init():
        t_ref[...] = jnp.zeros_like(t_ref)

    @pl.when(s < nk)
    def _accumulate():
        t_ref[...] += jnp.dot(x_ref[...], b_ref[...],
                              preferred_element_type=jnp.float32)

    @pl.when(s >= nk)
    def _emit():
        y_ref[...] = jnp.dot(t_ref[...].astype(c_ref.dtype), c_ref[...],
                             preferred_element_type=jnp.float32
                             ).astype(y_ref.dtype)


def lowrank_gemv(x: jax.Array, B: jax.Array, C: jax.Array, *,
                 bk: int = 512, bn: int = 512,
                 interpret: bool = False) -> jax.Array:
    """Decode-shaped fused low-rank matmul: y = (x @ B) @ C for SMALL M
    (M = decode batch, ≤ 64 rows after the ops wrapper pads to a sublane
    multiple of 8 — never a full 128 MXU tile).

    1-D grid (k-steps then n-steps), single m-block: each step streams
    exactly one (M×bk) x-tile + (bk×R) B-tile, or one (R×bn) C-tile; the
    rank-R intermediate lives in a (M×R) fp32 scratch. Every activation
    and weight byte is read exactly once — decode is weight-bandwidth-
    bound, so the wrapper aligns K/N to 128 (not the prefill kernel's 512)
    to keep zero-padding traffic off the ragged shapes the compressor
    emits, and pads M only to the 8-row sublane, never a 128 MXU tile.

    VMEM: x M·K·2 B (M≤64, K≤16384 → ≤2 MiB), B tile bk·R·2, C tile
    R·bn·2, t M·R·4 — inside budget with double buffering at defaults."""
    M, K = x.shape
    R = B.shape[1]
    N = C.shape[1]
    assert M <= 64 and K % bk == 0 and N % bn == 0, (M, K, N, bk, bn)
    nk = K // bk
    nn = N // bn
    grid = (nk + nn,)

    return pl.pallas_call(
        functools.partial(_gemv_kernel, nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((M, bk), lambda s: (0, jnp.minimum(s, nk - 1))),
            pl.BlockSpec((bk, R), lambda s: (jnp.minimum(s, nk - 1), 0)),
            pl.BlockSpec((R, bn), lambda s: (0, jnp.maximum(s - nk, 0))),
        ],
        out_specs=pl.BlockSpec((M, bn), lambda s: (0, jnp.maximum(s - nk, 0))),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((M, R), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
    )(x, B, C)


def lowrank_matmul_2d(x: jax.Array, B: jax.Array, C: jax.Array, *,
                      bm: int = 128, bk: int = 512, bn: int = 512,
                      interpret: bool = False) -> jax.Array:
    """x: (M, K); B: (K, R); C: (R, N) -> (M, N). M/K/N must divide by the
    block sizes (the ops wrapper pads); R rides whole in VMEM."""
    M, K = x.shape
    R = B.shape[1]
    N = C.shape[1]
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    nk = K // bk
    nn = N // bn
    grid = (M // bm, nk + nn)

    return pl.pallas_call(
        functools.partial(_kernel, nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, s: (i, jnp.minimum(s, nk - 1))),
            pl.BlockSpec((bk, R), lambda i, s: (jnp.minimum(s, nk - 1), 0)),
            pl.BlockSpec((R, bn), lambda i, s: (0, jnp.maximum(s - nk, 0))),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda i, s: (i, jnp.maximum(s - nk, 0))),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, R), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(x, B, C)
