"""Pallas TPU kernel for WSSL's weighted client aggregation
θ_global = Σ_i w_i · θ_i.

The aggregation touches every client-stage byte once per round — a pure
memory-bound broadcast-reduce.  Fusing it into one pass (instead of N
scaled adds) reads each stacked parameter exactly once from HBM.

Input: stacked (N, M) fp-any (leaves are flattened by ops.weighted_average),
weights (N,) fp32.  Grid over M tiles; each step loads an (N, bm) tile into
VMEM and contracts with the weights.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wavg_kernel(w_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)        # (N, bm)
    w = w_ref[...].astype(jnp.float32)        # (N,)
    # fp32 contraction on the MXU: the mean of fp32 parameters must not
    # pass through a bf16 rounding of either operand
    o_ref[...] = jax.lax.dot_general(
        w[None, :], x, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)[0].astype(o_ref.dtype)


def weighted_average_2d(stacked: jax.Array, weights: jax.Array, *,
                        block_m: int = 2048,
                        interpret: bool = False) -> jax.Array:
    """stacked: (N, M) -> (M,)."""
    n, m = stacked.shape
    if m == 0:
        # degenerate empty leaf: block_m = min(block_m, 0) would divide the
        # grid by zero — there is nothing to reduce, return the empty row
        return jnp.zeros((0,), stacked.dtype)
    block_m = min(block_m, m)
    pad = (-m) % block_m
    if pad:
        stacked = jnp.pad(stacked, ((0, 0), (0, pad)))
    mp = m + pad
    out = pl.pallas_call(
        _wavg_kernel,
        grid=(mp // block_m,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((block_m,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((mp,), stacked.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(weights, stacked)
    return out[:m] if pad else out
