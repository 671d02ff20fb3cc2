"""Pallas TPU kernel for the RG-LRU recurrence  h_t = a_t·h_{t-1} + b_t.

Gates (the W×W matmuls) run outside; the kernel handles the sequential
recurrence, which on TPU is memory-bound VPU work.  Grid:
(batch, width_blocks, chunks) — width is blocked so each program touches a
(Q, bw) tile; the chunk dimension is sequential and the carried hidden
state (1, bw) lives in VMEM scratch.  Within a chunk a fori_loop runs the
recurrence row by row (the loop is the recurrence — there is no way around
the sequential dependency; blocking keeps every iteration's operands in
VMEM/VREGs).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rg_lru_kernel(loga_ref, b_ref, y_ref, h_ref, *, chunk: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _reset():
        h_ref[...] = jnp.zeros_like(h_ref)

    # rows are read from the refs with pl.ds: indexing a loaded value by
    # the loop counter lowers to dynamic_slice, which Mosaic cannot lower
    def body(t, h):
        row = pl.ds(t, 1)
        a = jnp.exp(loga_ref[0, row, :].astype(jnp.float32))   # (1, bw)
        h = a * h + b_ref[0, row, :].astype(jnp.float32)
        y_ref[0, row, :] = h.astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, chunk, body, h_ref[...])
    h_ref[...] = h


def rg_lru_scan(log_a: jax.Array, b: jax.Array, *, chunk: int = 128,
                block_w: int = 512, interpret: bool = False) -> jax.Array:
    """log_a, b: (B, S, W) -> h: (B, S, W)."""
    bsz, s, w = log_a.shape
    chunk = min(chunk, s)
    block_w = min(block_w, w)
    assert s % chunk == 0 and w % block_w == 0, (s, chunk, w, block_w)
    nc, nw = s // chunk, w // block_w
    kernel = functools.partial(_rg_lru_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bsz, nw, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_w), lambda b__, wi, j: (b__, j, wi)),
            pl.BlockSpec((1, chunk, block_w), lambda b__, wi, j: (b__, j, wi)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_w),
                               lambda b__, wi, j: (b__, j, wi)),
        out_shape=jax.ShapeDtypeStruct((bsz, s, w), b.dtype),
        scratch_shapes=[pltpu.VMEM((1, block_w), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(log_a, b)
