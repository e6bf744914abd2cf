"""Blocked Gram accumulation kernel: G = XᵀX over calibration tokens —
the compression pipeline's device-side hot spot (DESIGN.md §3).

On TPU this is an MXU contraction over the token axis with fp32
accumulation; grid (D/bi, D/bj, N/bn) with the token step innermost and the
(bi × bj) output tile resident in VMEM across token steps. The paper's fp64
S-matrix precision is preserved by accumulating per-shard fp32 partials
that the host driver sums in fp64 (numpy) before the Cholesky.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(nn: int, xi_ref, xj_ref, g_ref, acc_ref):
    n = pl.program_id(2)

    @pl.when(n == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jax.lax.dot_general(
        xi_ref[...], xj_ref[...], (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(n == nn - 1)
    def _emit():
        g_ref[...] = acc_ref[...]


def gram_blocked(x: jax.Array, *, bi: int = 256, bj: int = 256,
                 bn: int = 512, interpret: bool = False) -> jax.Array:
    """x: (N, D) -> G (D, D) fp32. N, D must divide (wrapper pads)."""
    N, D = x.shape
    assert N % bn == 0 and D % bi == 0 and D % bj == 0, (N, D, bi, bj, bn)
    nn = N // bn
    return pl.pallas_call(
        functools.partial(_kernel, nn),
        grid=(D // bi, D // bj, nn),
        in_specs=[
            pl.BlockSpec((bn, bi), lambda i, j, n: (n, i)),
            pl.BlockSpec((bn, bj), lambda i, j, n: (n, j)),
        ],
        out_specs=pl.BlockSpec((bi, bj), lambda i, j, n: (i, j)),
        # inside shard_map (the mesh capture) the Gram varies over the
        # same mesh axes as its input rows
        out_shape=jax.ShapeDtypeStruct((D, D), jnp.float32,
                                       vma=jax.typeof(x).vma),
        scratch_shapes=[pltpu.VMEM((bi, bj), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(x, x)
