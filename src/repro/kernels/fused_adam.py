"""Pallas TPU kernel for the fused masked-AdamW update.

The hand-rolled ``optim/optimizers.py::adamw_update`` is an unfused
elementwise chain: each primitive (moment EMAs, bias correction, the
rsqrt step, weight decay, the freeze-mask blend) streams the full
(N clients × M params) working set HBM → VMEM → HBM again, ~8 round
trips per leaf per round.  This kernel folds the whole update into ONE
streaming tile pass: each grid step loads a ``(N, bm)`` tile of
``(p, g, m, v)`` plus the ``(N,)`` participation mask, applies

    m' = β₁·m + (1−β₁)·g
    v' = β₂·v + (1−β₂)·g²
    p' = p − lr·( (m'/bc₁) / (√(v'/bc₂) + ε) + wd·p )

with the per-client freeze mask blended in (masked-out rows keep p, m
and v bit-identical — the paper's non-participant semantics), and writes
``(p', m', v')`` back exactly once.

Every hyper-parameter rides in a ``(9,)`` fp32 scalar vector
``[lr, β₁, β₂, 1−β₁, 1−β₂, ε, wd, bc₁, bc₂]`` — a *traced* input, so one
compiled executable serves every lr / weight-decay / step setting, the
same dynamic-scalar discipline as the wavg/compress kernels.  (1−β) and
the bias corrections are computed by the dispatcher, outside the kernel,
with the same op order as the tree-map path, which keeps the fp32 update
bit-exact against both the ref oracle and the unfused path.

All math is fp32 regardless of the param dtype (moments are stored
fp32, matching ``adamw_init``); ``p'`` is cast back to the param dtype
on the single write — the documented bf16 band.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _fused_adamw_kernel(s_ref, k_ref, p_ref, g_ref, m_ref, v_ref,
                        po_ref, mo_ref, vo_ref):
    s = s_ref[...].astype(jnp.float32)            # (9,) hyper scalars
    lr, omb1, omb2 = s[0], s[3], s[4]
    b1, b2 = s[1], s[2]
    eps, wd, bc1, bc2 = s[5], s[6], s[7], s[8]
    p32 = p_ref[...].astype(jnp.float32)          # (N, bm)
    g32 = g_ref[...].astype(jnp.float32)
    m = m_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    m_new = b1 * m + omb1 * g32
    v_new = b2 * v + omb2 * jnp.square(g32)
    mhat = m_new / bc1
    vhat = v_new / bc2
    p_new = p32 - lr * (mhat / (jnp.sqrt(vhat) + eps) + wd * p32)
    mk = k_ref[...].astype(jnp.float32)[:, None]  # (N, 1) freeze mask
    po_ref[...] = (mk * p_new + (1 - mk) * p32).astype(po_ref.dtype)
    mo_ref[...] = mk * m_new + (1 - mk) * m
    vo_ref[...] = mk * v_new + (1 - mk) * v


def fused_adamw_2d(p: jax.Array, g: jax.Array, m: jax.Array, v: jax.Array,
                   mask: jax.Array, scalars: jax.Array, *,
                   block_m: int = 2048, interpret: bool = False
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """p, g: (N, M) fp-any; m, v: (N, M) fp32; mask: (N,) fp32;
    scalars: (9,) fp32 = [lr, β₁, β₂, 1−β₁, 1−β₂, ε, wd, bc₁, bc₂]
    -> (p' in p.dtype, m' fp32, v' fp32)."""
    n, msz = p.shape
    if msz == 0:
        # degenerate empty leaf — nothing to step, and a zero block would
        # divide the grid by zero
        return p, m.astype(jnp.float32), v.astype(jnp.float32)
    block_m = min(block_m, msz)
    pad = (-msz) % block_m
    if pad:
        padw = ((0, 0), (0, pad))
        p, g = jnp.pad(p, padw), jnp.pad(g, padw)
        m, v = jnp.pad(m, padw), jnp.pad(v, padw)
    mp = msz + pad
    outs = pl.pallas_call(
        _fused_adamw_kernel,
        grid=(mp // block_m,),
        in_specs=[
            pl.BlockSpec((9,), lambda i: (0,)),
            pl.BlockSpec((n,), lambda i: (0,)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
        ],
        out_specs=(
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
            pl.BlockSpec((n, block_m), lambda i: (0, i)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((n, mp), p.dtype),
            jax.ShapeDtypeStruct((n, mp), jnp.float32),
            jax.ShapeDtypeStruct((n, mp), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(scalars, mask, p, g, m, v)
    if pad:
        outs = tuple(o[:, :msz] for o in outs)
    return outs
