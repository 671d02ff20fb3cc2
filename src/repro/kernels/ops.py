"""jit'd dispatch wrappers for the Pallas kernels.

On the CPU backend (the tests) kernels execute in the Pallas interpreter;
on a TPU the same calls compile to Mosaic.  Any other backend is an error,
never a silent fall-back to the interpreter.  Every wrapper accepts the model-layer layouts
(e.g. (B,S,H,hd) attention tensors) and handles the transposes/padding.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import compress as _compress
from repro.kernels import flash_attention as _fa
from repro.kernels import fused_adam as _fadam
from repro.kernels import paged_attention as _pa
from repro.kernels import rg_lru as _lru
from repro.kernels import ssd_scan as _ssd
from repro.kernels import wavg as _wavg


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(
            f"Pallas kernels run on a TPU or, for tests, in the CPU "
            f"interpreter; the default backend is {backend!r}")
    return backend == "cpu"


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    scale: Optional[float] = None,
                    logit_softcap: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128) -> jax.Array:
    """Model layout: q (B,S,Hq,hd); k,v (B,S,Hkv,hd) -> (B,S,Hq,hd)."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _fa.flash_attention_bhsd(
        qt, kt, vt, causal=causal, window=window, scale=scale,
        logit_softcap=logit_softcap, block_q=block_q, block_k=block_k,
        interpret=_interpret())
    return out.transpose(0, 2, 1, 3)


def paged_decode_attention(q: jax.Array, pk: jax.Array, pv: jax.Array,
                           ppos: jax.Array, table: jax.Array,
                           pos: jax.Array, *, scale: Optional[float] = None,
                           logit_softcap: Optional[float] = None
                           ) -> jax.Array:
    """One-token paged attention straight off the (NB, bs, Hkv, hd) pool:
    q (B,Hq,hd), table (B,nb), pos (B,) -> (B,Hq,hd).  No gathered
    logical view is ever materialized (see kernels/paged_attention.py)."""
    return _pa.paged_decode_attention(
        q, pk, pv, ppos, table, pos, scale=scale,
        logit_softcap=logit_softcap, interpret=_interpret())


def ssd_scan(x, dt, a, b_, c_, *, chunk: int = 128, block_h: int = 8):
    return _ssd.ssd_scan(x, dt, a, b_, c_, chunk=chunk, block_h=block_h,
                         interpret=_interpret())


def rg_lru_scan(log_a, b, *, chunk: int = 128, block_w: int = 512):
    return _lru.rg_lru_scan(log_a, b, chunk=chunk, block_w=block_w,
                            interpret=_interpret())


def weighted_average(stacked: jax.Array, weights: jax.Array,
                     *, block_m: int = 2048) -> jax.Array:
    """Any-rank stacked leaf (N, ...) -> (...).  Empty leaves (zero-size
    trailing shape) short-circuit: nothing to reduce, and the kernel's grid
    math cannot divide by a zero block."""
    n = stacked.shape[0]
    flat = stacked.reshape(n, -1)
    if flat.shape[1] == 0:
        return jnp.zeros(stacked.shape[1:], stacked.dtype)
    out = _wavg.weighted_average_2d(flat, weights, block_m=block_m,
                                    interpret=_interpret())
    return out.reshape(stacked.shape[1:])


def fused_adamw(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                mask: Optional[jax.Array], scalars: jax.Array,
                *, block_m: int = 2048):
    """Fused masked-AdamW step over one leaf -> (p', m', v').

    Any-rank leaves.  With ``mask`` (per-client stacked stage, leaves
    (N, ...)) the leading axis is the client axis and masked rows keep
    p/m/v bit-identical; with ``mask=None`` (shared server/edge stage)
    the leaf flattens to a single always-on row.  ``scalars`` is the
    (9,) fp32 hyper vector (see kernels/fused_adam.py) — a traced input,
    so lr/wd/step changes never recompile.  Empty leaves short-circuit:
    nothing to step, and the kernel's grid math cannot divide by a zero
    block."""
    if mask is not None:
        n = p.shape[0]
        rows = mask.astype(jnp.float32)
    else:
        n = 1
        rows = jnp.ones((1,), jnp.float32)
    pf, gf = p.reshape(n, -1), g.reshape(n, -1)
    mf, vf = m.reshape(n, -1), v.reshape(n, -1)
    if pf.shape[1] == 0:
        return p, m.astype(jnp.float32), v.astype(jnp.float32)
    po, mo, vo = _fadam.fused_adamw_2d(pf, gf, mf, vf, rows, scalars,
                                       block_m=block_m,
                                       interpret=_interpret())
    return po.reshape(p.shape), mo.reshape(m.shape), vo.reshape(v.shape)


def quantize_stochastic(x: jax.Array, u: jax.Array, inv_step: jax.Array,
                        levels, *, block_m: int = 2048) -> jax.Array:
    """(N, M) fp -> (N, M) int8 codes in [-levels, levels]."""
    return _compress.quantize_stochastic_2d(x, u, inv_step, levels,
                                            block_m=block_m,
                                            interpret=_interpret())


def dequantize(q: jax.Array, step: jax.Array,
               *, block_m: int = 2048) -> jax.Array:
    """(N, M) int8 codes -> (N, M) fp32 reconstruction."""
    return _compress.dequantize_2d(q, step, block_m=block_m,
                                   interpret=_interpret())


def topk_mask(x: jax.Array, thresh: jax.Array,
              *, block_m: int = 2048) -> jax.Array:
    """(N, M) fp -> same with |x| < per-row threshold zeroed."""
    return _compress.topk_mask_2d(x, thresh, block_m=block_m,
                                  interpret=_interpret())
