"""Pallas block-table paged decode attention (flash-decoding style).

One-token attention computed *directly against the paged KV pool*: the
``(B, nb)`` block table and the per-row positions ride in as
scalar-prefetch operands, the grid walks ``(batch, logical block)``,
and each step DMAs exactly one pool block ``(bs, Hkv, hd)`` through the
table indirection into per-KV-head online-softmax accumulators (running
max/sum rescaling, with the ``ppos`` validity mask fused in).  The block
spans whole trailing dimensions, which is what Mosaic's tiling accepts
for any head count and head size; one head's ``(bs, hd)`` slice is read
from it inside the kernel.  The
gather path this replaces (``attention.paged_decode_attention``)
materializes the full ``(B, nb*bs, H, hd)`` logical K and V views in HBM
every decode step; here no logical view ever exists.

Work is bounded by the live prefix, not ``max_len``: a logical block
``j`` with ``j * bs > pos[b]`` holds only future positions, so its grid
step is predicated out AND its index map clamps to the last live block —
the revisited block index means the pipeline issues no new DMA for the
dead tail.

Semantics match the masked-softmax gather path bit-for-bit in all
*reachable* pool states (every entry of a block past ``pos[b]`` is
invalid: admission wipes them to -1 and speculative rollback re-wipes
rejected writes) up to the floating-point reduction order of the online
softmax — the engine-level parity band is documented in
docs/serving.md.  One deliberate refinement over the gather path: a row
with *no* valid entries returns 0 instead of a uniform average over
garbage (unreachable in the engine, which always writes the current
token's K/V before attending).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(pos_ref, table_ref, q_ref, k_ref, v_ref, ppos_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, scale: float,
                  softcap: Optional[float], block_size: int,
                  num_blocks: int, num_kv_heads: int):
    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    pos_b = pos_ref[b]

    # logical block j covers positions [j*bs, (j+1)*bs): entirely in the
    # future once j*bs > pos[b] — skip the math (the index map already
    # re-points the DMA at the last live block, so nothing new moved)
    @pl.when(j * block_size <= pos_b)
    def _accumulate():
        pp = ppos_ref[0]                              # (1, bs)
        valid = (pp >= 0) & (pp <= pos_b)
        for h in range(num_kv_heads):
            q = q_ref[0, h].astype(jnp.float32)       # (G, hd)
            k = k_ref[0, :, h, :].astype(jnp.float32)  # (bs, hd)
            v = v_ref[0, :, h, :].astype(jnp.float32)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = s * scale
            if softcap is not None:
                s = softcap * jnp.tanh(s / softcap)   # (G, bs)
            s = jnp.where(valid, s, NEG_INF)
            m_prev = m_ref[h]                         # (G, 1)
            l_prev = l_ref[h]
            m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            # zero (not exp-of-huge-negative) the invalid lanes: an
            # all-invalid prefix keeps l == 0 and finalizes to 0 instead
            # of a garbage mean
            p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
            l_ref[h] = l_prev * corr + p.sum(axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

    @pl.when(j == num_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q: jax.Array, pk: jax.Array, pv: jax.Array,
                           ppos: jax.Array, table: jax.Array,
                           pos: jax.Array, *, scale: Optional[float] = None,
                           logit_softcap: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """q: (B, Hq, hd); pk/pv: (NB, bs, Hkv, hd) pool; ppos: (NB, bs);
    table: (B, nb) int32 logical→physical block map; pos: (B,) int32
    current absolute position per row -> (B, Hq, hd)."""
    b, hq, hd = q.shape
    num_pool, bs, hkv, _ = pk.shape
    nb = table.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    qg = q.reshape(b, hkv, g, hd)
    # one (1, bs) row of positions per block: a whole-dimension block
    ppos = ppos.reshape(num_pool, 1, bs)
    pos = jnp.asarray(pos, jnp.int32)
    table = jnp.asarray(table, jnp.int32)

    def kv_map(b_, j, pos_ref, table_ref):
        # clamp the dead tail to the last live block: the repeated block
        # index makes the pipeline skip the DMA instead of streaming
        # max_len - live dead blocks per row
        jl = jnp.minimum(j, pos_ref[b_] // bs)
        return (table_ref[b_, jl], 0, 0, 0)

    def ppos_map(b_, j, pos_ref, table_ref):
        jl = jnp.minimum(j, pos_ref[b_] // bs)
        return (table_ref[b_, jl], 0, 0)

    kernel = functools.partial(
        _paged_kernel, scale=scale, softcap=logit_softcap, block_size=bs,
        num_blocks=nb, num_kv_heads=hkv)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                       # pos, table
        grid=(b, nb),
        in_specs=[
            pl.BlockSpec((1, hkv, g, hd), lambda b_, j, p_, t_: (b_, 0, 0, 0)),
            pl.BlockSpec((1, bs, hkv, hd), kv_map),
            pl.BlockSpec((1, bs, hkv, hd), kv_map),
            pl.BlockSpec((1, 1, bs), ppos_map),
        ],
        out_specs=pl.BlockSpec((1, hkv, g, hd),
                               lambda b_, j, p_, t_: (b_, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 1), jnp.float32),    # m
            pltpu.VMEM((hkv, g, 1), jnp.float32),    # l
            pltpu.VMEM((hkv, g, hd), jnp.float32),   # acc
        ],
    )

    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(pos, table, qg, pk, pv, ppos)
    return out.reshape(b, hq, hd)
