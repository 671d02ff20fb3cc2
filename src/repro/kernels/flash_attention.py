"""Pallas TPU flash attention (blocked online softmax).

Grid: (batch, q_heads, q_blocks, kv_blocks) with the kv dimension sequential
("arbitrary") so the (m, l, acc) running statistics live in VMEM scratch
across kv steps.  Supports causal masking, sliding windows, GQA (q head h
reads kv head h // group), and logit soft-capping.

Block sizes default to 128 (MXU-aligned); head_dim up to 256 keeps the
working set (q/k/v blocks + scores + acc ≈ 0.5 MB fp32) comfortably inside
VMEM with double buffering.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, window: Optional[int],
                  softcap: Optional[float], block_q: int, block_k: int,
                  num_kv_blocks: int):
    j = pl.program_id(3)
    i = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)       # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)       # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale  # (bq,bk)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)

    q_pos = i * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = j * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    l_prev = l_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_prev * corr + p.sum(axis=-1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == num_kv_blocks - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         causal: bool = True, window: Optional[int] = None,
                         scale: Optional[float] = None,
                         logit_softcap: Optional[float] = None,
                         block_q: int = 128, block_k: int = 128,
                         interpret: bool = False) -> jax.Array:
    """q: (B, Hq, Sq, hd); k, v: (B, Hkv, Sk, hd) -> (B, Hq, Sq, hd)."""
    b, hq, sq, hd = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(hd)
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    assert sq % block_q == 0 and sk % block_k == 0, (sq, sk, block_q, block_k)
    nq, nk = sq // block_q, sk // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=logit_softcap, block_q=block_q, block_k=block_k,
        num_kv_blocks=nk)

    return pl.pallas_call(
        kernel,
        grid=(b, hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, hd),
                         lambda b_, h, i, j: (b_, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b_, h, i, j: (b_, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda b_, h, i, j: (b_, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda b_, h, i, j: (b_, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, hq, sq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m
            pltpu.VMEM((block_q,), jnp.float32),      # l
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
