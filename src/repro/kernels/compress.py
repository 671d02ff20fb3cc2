"""Pallas TPU kernels for update-path communication compression.

Three memory-bound elementwise hot loops over the stacked (N, M) client
updates (leaves are flattened by ``ops``/``repro.compress``):

* ``quantize_stochastic_2d`` — symmetric stochastic rounding to
  ``levels`` integer levels per row: q = clip(⌊x·(levels/scale) + u⌋,
  −levels, levels) with u ~ U[0,1).  The per-row scale (max |x|) and the
  uniform noise are computed *outside* the kernel (a jax.random stream —
  deterministic, identical in interpret mode and on TPU), so the kernel is
  a pure fused scale-round-clip pass over HBM.
* ``dequantize_2d`` — q · (scale/levels) per row.
* ``topk_mask_2d`` — magnitude top-k sparsification given a per-row
  threshold: where(|x| ≥ t_row, x, 0).  The threshold (the k-th largest
  |x|, k dynamic) comes from a sort outside the kernel; the kernel is the
  bandwidth-bound masking pass that touches every byte.

``levels`` is a *traced* fp32 scalar shipped as a (1,) input, so int8
(levels=127) and int4 (levels=7) share one compiled executable — the same
one-executable invariant as ``AsyncParams``/``AggParams``.

Grid/BlockSpec layout mirrors ``wavg.py``: 1-D grid over M tiles, full N
rows per tile, zero-padded remainder tile sliced off after the call.
Degenerate ``m == 0`` leaves return empty outputs without invoking
``pallas_call`` (a zero-size grid is a zero-division).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pad_m(x: jax.Array, block_m: int):
    m = x.shape[-1]
    pad = (-m) % block_m
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x, m + pad


def _quant_kernel(lv_ref, inv_ref, x_ref, u_ref, o_ref):
    lv = lv_ref[0]                                   # traced level count
    x = x_ref[...].astype(jnp.float32)               # (N, bm)
    u = u_ref[...].astype(jnp.float32)
    inv = inv_ref[...].astype(jnp.float32)           # (N,) levels/scale
    q = jnp.floor(x * inv[:, None] + u)
    o_ref[...] = jnp.clip(q, -lv, lv).astype(jnp.int8)


def quantize_stochastic_2d(x: jax.Array, u: jax.Array, inv_step: jax.Array,
                           levels: jax.Array, *, block_m: int = 2048,
                           interpret: bool = False) -> jax.Array:
    """x, u: (N, M); inv_step: (N,) = levels/scale (0 for all-zero rows);
    levels: fp32 scalar -> int8 codes (N, M) in [-levels, levels]."""
    n, m = x.shape
    if m == 0:
        return jnp.zeros((n, 0), jnp.int8)
    block_m = min(block_m, m)
    x, mp = _pad_m(x, block_m)
    u, _ = _pad_m(u, block_m)
    lv = jnp.reshape(jnp.asarray(levels, jnp.float32), (1,))
    out = pl.pallas_call(
        _quant_kernel,
        grid=(mp // block_m,),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, block_m), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, mp), jnp.int8),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(lv, inv_step, x, u)
    return out[:, :m] if mp != m else out


def _dequant_kernel(step_ref, q_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    step = step_ref[...].astype(jnp.float32)         # (N,) scale/levels
    o_ref[...] = q * step[:, None]


def dequantize_2d(q: jax.Array, step: jax.Array, *, block_m: int = 2048,
                  interpret: bool = False) -> jax.Array:
    """q: (N, M) int8 codes; step: (N,) = scale/levels -> fp32 (N, M)."""
    n, m = q.shape
    if m == 0:
        return jnp.zeros((n, 0), jnp.float32)
    block_m = min(block_m, m)
    q, mp = _pad_m(q, block_m)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(mp // block_m,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, block_m), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, mp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(step, q)
    return out[:, :m] if mp != m else out


def _topk_kernel(t_ref, x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    t = t_ref[...].astype(jnp.float32)               # (N,) per-row threshold
    o_ref[...] = jnp.where(jnp.abs(x) >= t[:, None], x,
                           jnp.zeros_like(x)).astype(o_ref.dtype)


def topk_mask_2d(x: jax.Array, thresh: jax.Array, *, block_m: int = 2048,
                 interpret: bool = False) -> jax.Array:
    """x: (N, M); thresh: (N,) -> x with sub-threshold entries zeroed.

    The pad value 0 never survives: |0| >= t only when t == 0, and the
    padded region is sliced off before returning either way."""
    n, m = x.shape
    if m == 0:
        return jnp.zeros((n, 0), x.dtype)
    block_m = min(block_m, m)
    xp, mp = _pad_m(x, block_m)
    out = pl.pallas_call(
        _topk_kernel,
        grid=(mp // block_m,),
        in_specs=[
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, block_m), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, mp), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(thresh, xp)
    return out[:, :m] if mp != m else out
