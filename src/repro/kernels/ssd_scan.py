"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

Grid: (batch, head blocks, chunks) with the chunk dimension sequential;
the running inter-chunk state (block_h, N, P) lives in VMEM scratch and
is re-zeroed at the start of each batch row.  Within a chunk every
contraction is a 2-D dot (MXU-friendly): the intra-chunk "attention"
C·Bᵀ⊙L, shared by the block's heads, and each head's state
update/readout.

Inputs are pre-projected/pre-conv'd (the block's matmuls run outside):
  x  (B, S, H, P)   head inputs
  dt (B, S, H)      positive step sizes (fp32)
  a  (H,)           negative decay rates  (fp32)
  b_ (B, S, N)      input projections (shared across heads)
  c_ (B, S, N)      output projections
Output: y (B, S, H, P).

The wrapper lays x and dt out head-major, so each head's chunk is a
whole (Q, P) tile and its step sizes a (1, Q) row; ``a`` rides in SMEM
and is read per head as a scalar.  The chunk's cumulative decay is the
lower-triangular product of that row, summed on the VPU in fp32 (Mosaic
has no cumsum); the same masked sums over the identity turn a row into a
column without a transpose.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _dot(a, b, contract=((1,), (0,))):
    # fp32 contractions: decays and the carried state are not bf16 values
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref, *,
                chunk: int, block_h: int):
    hi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _reset():
        state_ref[...] = jnp.zeros_like(state_ref)

    b = b_ref[0].astype(jnp.float32)          # (Q, N)
    c = c_ref[0].astype(jnp.float32)          # (Q, N)
    cb = _dot(c, b, ((1,), (1,)))             # (Qi, Qj), shared by heads
    qi = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    qj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tri = qj <= qi
    eye = qj == qi

    for h in range(block_h):
        a = a_ref[hi * block_h + h]                     # scalar, < 0
        x = x_ref[0, h].astype(jnp.float32)             # (Q, P)
        dt_row = dt_ref[0, h].astype(jnp.float32)       # (1, Q)
        da_row = dt_row * a                             # <= 0
        # cum_i = sum_{k<=i} da_k: the lower-triangular product
        cum = jnp.sum(jnp.where(tri, da_row, 0.0), axis=1, keepdims=True)
        cum_row = jnp.sum(jnp.where(eye, cum, 0.0), axis=0, keepdims=True)
        dt_col = jnp.sum(jnp.where(eye, dt_row, 0.0), axis=1, keepdims=True)
        cum_end = jnp.sum(da_row, axis=1, keepdims=True)          # (1, 1)

        # intra-chunk; mask before exp (upper-triangle diffs are > 0)
        L = jnp.exp(jnp.where(tri, cum - cum_row, -jnp.inf))      # (Qi, Qj)
        y_intra = _dot(cb * L * dt_row, x)                        # (Q, P)

        # inter-chunk: read the previous state
        prev = state_ref[h]                                       # (N, P)
        y_inter = _dot(c, prev) * jnp.exp(cum)

        # state update: sum_q b_q (exp(cum_end - cum_q) dt_q) x_q
        w = jnp.exp(cum_end - cum) * dt_col                       # (Q, 1)
        s_new = _dot(b, w * x, ((0,), (0,)))                      # (N, P)
        state_ref[h] = jnp.exp(cum_end) * prev + s_new

        y_ref[0, h] = (y_intra + y_inter).astype(y_ref.dtype)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b_: jax.Array,
             c_: jax.Array, *, chunk: int = 128, block_h: int = 8,
             interpret: bool = False) -> jax.Array:
    bsz, s, h, p = x.shape
    n = b_.shape[-1]
    chunk = min(chunk, s)
    block_h = min(block_h, h)
    assert s % chunk == 0 and h % block_h == 0, (s, chunk, h, block_h)
    nc, nh = s // chunk, h // block_h
    xh = x.transpose(0, 2, 1, 3)                              # (B, H, S, P)
    dth = dt.astype(jnp.float32).transpose(0, 2, 1)[:, :, None, :]  # (B,H,1,S)

    kernel = functools.partial(_ssd_kernel, chunk=chunk, block_h=block_h)
    y = pl.pallas_call(
        kernel,
        grid=(bsz, nh, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, block_h, chunk, p),
                         lambda b__, hi, j: (b__, hi, j, 0)),
            pl.BlockSpec((1, block_h, 1, chunk),
                         lambda b__, hi, j: (b__, hi, 0, j)),
            pl.BlockSpec((1, chunk, n), lambda b__, hi, j: (b__, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda b__, hi, j: (b__, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_h, chunk, p),
                               lambda b__, hi, j: (b__, hi, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bsz, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((block_h, n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a.astype(jnp.float32), xh, dth, b_, c_)
    return y.transpose(0, 2, 1, 3)
