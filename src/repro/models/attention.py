"""Attention: GQA/MQA/MHA with causal, sliding-window, decode-with-cache.

Implementations (``attn_impl``):

* ``dense``      — materialize (Sq, Sk) scores; reference, small shapes.
* ``chunked``    — lax.scan over KV blocks with online softmax: O(S·Bk)
                   memory, rectangle FLOPs (2x the causal triangle).
* ``triangular`` — lax.scan over the lower-triangular (q-block, kv-block)
                   pair grid: exact causal FLOPs, O(S·Bk) memory.  Used by
                   the perf-optimized configs (EXPERIMENTS.md §Perf).
* ``banded``     — sliding-window attention computed on a 2w-wide band:
                   exact O(S·2w) FLOPs for local layers.
* ``pallas``     — the Pallas flash kernel (kernels/flash_attention.py);
                   TPU target, interpret-mode on CPU.

Decode (single new token vs. a cache) is a separate, always-dense-over-KV
path — it is O(S) per step.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig
from repro.models.layers import apply_rope, dense_param, softcap, split_rng
from repro.sharding import shard_activation

Params = Dict[str, Any]

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------


def attention_init(rng, cfg: ModelConfig):
    d, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    rngs = split_rng(rng, 4)
    params: Params = {}
    axes: Dict[str, Any] = {}
    # "attn_din"/"attn_dout" default to the fsdp axis but rebind to the
    # model axis when the head count cannot shard it (qwen2.5's 40 heads on
    # a 16-wide axis) — attention weights then shard on d_model instead of
    # replicating (launch/specs.py:build_rules).
    params["wq"], axes["wq"] = dense_param(rngs[0], (d, hq, hd),
                                           ("attn_din", "heads", None))
    params["wk"], axes["wk"] = dense_param(rngs[1], (d, hkv, hd),
                                           ("attn_din", "kv_heads", None))
    params["wv"], axes["wv"] = dense_param(rngs[2], (d, hkv, hd),
                                           ("attn_din", "kv_heads", None))
    params["wo"], axes["wo"] = dense_param(
        rngs[3], (hq, hd, d), ("heads", None, "attn_dout"),
        scale=1.0 / math.sqrt(hq * hd)
    )
    if cfg.qkv_bias:
        params["bq"] = jnp.zeros((hq, hd), jnp.float32)
        params["bk"] = jnp.zeros((hkv, hd), jnp.float32)
        params["bv"] = jnp.zeros((hkv, hd), jnp.float32)
        axes["bq"] = ("heads", None)
        axes["bk"] = ("kv_heads", None)
        axes["bv"] = ("kv_heads", None)
    return params, axes


def _project_qkv(cfg: ModelConfig, p: Params, x: jax.Array, positions):
    dtype = x.dtype
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        q = q + p["bq"].astype(dtype)
        k = k + p["bk"].astype(dtype)
        v = v + p["bv"].astype(dtype)
    q = apply_rope(cfg, q, positions)
    k = apply_rope(cfg, k, positions)
    # "attn_seq"/"act_heads": sequence-parallel vs head-parallel attention
    # ACTIVATIONS (params always shard on "heads" when divisible).
    q = shard_activation(q, "batch", "attn_seq", "act_heads", None)
    k = shard_activation(k, "batch", None, "kv_heads", None)
    v = shard_activation(v, "batch", None, "kv_heads", None)
    return q, k, v


def _group(cfg: ModelConfig, q: jax.Array) -> jax.Array:
    """(B,S,Hq,hd) -> (B,S,Hkv,G,hd)."""
    b, s, hq, hd = q.shape
    g = hq // cfg.num_kv_heads
    return q.reshape(b, s, cfg.num_kv_heads, g, hd)


def _scale(cfg: ModelConfig) -> float:
    return cfg.query_scale or 1.0 / math.sqrt(cfg.head_dim)


# ---------------------------------------------------------------------------
# Full-sequence implementations
# ---------------------------------------------------------------------------


def _attn_dense(cfg: ModelConfig, q, k, v, q_pos, k_pos, window: Optional[int]):
    qg = _group(cfg, q)  # (B,Sq,K,G,hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k) * _scale(cfg)
    s = softcap(s, cfg.attn_logit_softcap)
    mask = k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    if window is not None:
        mask &= (q_pos[:, None, None, :, None] - k_pos[:, None, None, None, :]) < window
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v)
    return out.reshape(q.shape)


# ---------------------------------------------------------------------------
# Flash attention with a recomputing (flash-style) backward pass.
#
# A plain autodiff through the online-softmax scan saves the (Sq, Bk)
# probability tiles of every KV step for the backward pass — O(S²) residual
# memory per layer, exactly what sinks multi-GiB train steps.  The custom
# VJP below saves only (q, k, v, out, m, l) and *recomputes* the tiles
# blockwise on the way back (dq accumulated across KV blocks; dk/dv emitted
# per block), the standard flash-attention backward.
# ---------------------------------------------------------------------------


def _flash_blocks(x, block, axis=1):
    """(B, S, ...) -> (nk, B, block, ...) scan-major blocking."""
    b = x.shape[0]
    nk = x.shape[axis] // block
    shape = x.shape[:axis] + (nk, block) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


def _flash_mask(pj, q_pos, window):
    mask = pj[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    if window is not None:
        mask &= (q_pos[:, None, None, :, None]
                 - pj[:, None, None, None, :]) < window
    return mask


def _flash_fwd_core(qg, k, v, q_pos, k_pos, scale, cap, window, block):
    b, sq, kh, g, hd = qg.shape
    sk = k.shape[1]
    nk = sk // block
    kb = _flash_blocks(k, block)
    vb = _flash_blocks(v, block)
    pb = _flash_blocks(k_pos[..., None], block)[..., 0]

    def step(carry, blk):
        m, l, acc = carry
        kj, vj, pj = blk
        z = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj).astype(jnp.float32) * scale
        s = cap * jnp.tanh(z / cap) if cap is not None else z
        s = jnp.where(_flash_mask(pj, q_pos, window), s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(qg.dtype), vj
                        ).astype(jnp.float32)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, kh, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros_like(m0)
    acc0 = jnp.zeros(qg.shape, jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, pb))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l.transpose(0, 3, 1, 2)[..., None]).astype(qg.dtype)
    return out, m, l


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _flash(qg, k, v, q_pos, k_pos, scale, cap, window, block):
    out, _, _ = _flash_fwd_core(qg, k, v, q_pos, k_pos, scale, cap, window,
                                block)
    return out


def _flash_fwd(qg, k, v, q_pos, k_pos, scale, cap, window, block):
    out, m, l = _flash_fwd_core(qg, k, v, q_pos, k_pos, scale, cap, window,
                                block)
    return out, (qg, k, v, q_pos, k_pos, out, m, l)


def _flash_bwd(scale, cap, window, block, res, dout):
    qg, k, v, q_pos, k_pos, out, m, l = res
    kb = _flash_blocks(k, block)
    vb = _flash_blocks(v, block)
    pb = _flash_blocks(k_pos[..., None], block)[..., 0]
    dout32 = dout.astype(jnp.float32)
    # delta_i = sum_d dout_i * out_i  (B,K,G,Sq)
    delta = jnp.einsum("bqkgd,bqkgd->bkgq", dout32, out.astype(jnp.float32))

    def step(dq_acc, blk):
        kj, vj, pj = blk
        z = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj).astype(jnp.float32) * scale
        if cap is not None:
            s = cap * jnp.tanh(z / cap)
            dsdz = 1.0 - jnp.square(s / cap)
        else:
            s, dsdz = z, None
        mask = _flash_mask(pj, q_pos, window)
        s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - m[..., None]) / l[..., None]          # normalized
        dv = jnp.einsum("bkgqs,bqkgd->bskd", p.astype(dout.dtype), dout)
        dp = jnp.einsum("bqkgd,bskd->bkgqs", dout32,
                        vj.astype(jnp.float32))
        ds = p * (dp - delta[..., None])
        if dsdz is not None:
            ds = ds * dsdz
        ds = jnp.where(mask, ds, 0.0) * scale
        dq_acc = dq_acc + jnp.einsum("bkgqs,bskd->bqkgd",
                                     ds.astype(qg.dtype), kj
                                     ).astype(jnp.float32)
        dk = jnp.einsum("bkgqs,bqkgd->bskd", ds.astype(qg.dtype), qg)
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros(qg.shape, jnp.float32)
    dq, (dkb, dvb) = jax.lax.scan(step, dq0, (kb, vb, pb))
    dk = jnp.moveaxis(dkb, 0, 1).reshape(k.shape)
    dv = jnp.moveaxis(dvb, 0, 1).reshape(v.shape)
    return (dq.astype(qg.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_flash.defvjp(_flash_fwd, _flash_bwd)


def _attn_flash(cfg: ModelConfig, q, k, v, q_pos, k_pos,
                window: Optional[int], block: int = 256):
    """Memory-bounded attention with a flash (recomputing) backward."""
    sk = k.shape[1]
    block = min(block, sk)
    if sk % block:
        return _attn_dense(cfg, q, k, v, q_pos, k_pos, window)
    qg = _group(cfg, q)
    out = _flash(qg, k, v, q_pos, k_pos, _scale(cfg),
                 cfg.attn_logit_softcap, window, block)
    return out.reshape(q.shape)


def _attn_chunked(cfg: ModelConfig, q, k, v, q_pos, k_pos,
                  window: Optional[int], block: int = 1024):
    """Online-softmax scan over KV blocks (rectangle FLOPs, bounded memory)."""
    b, sq, hq, hd = q.shape
    sk = k.shape[1]
    block = min(block, sk)
    if sk % block:
        return _attn_dense(cfg, q, k, v, q_pos, k_pos, window)
    nk = sk // block
    qg = _group(cfg, q)  # (B,Sq,K,G,hd)
    kb = k.reshape(b, nk, block, cfg.num_kv_heads, hd).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(b, nk, block, cfg.num_kv_heads, hd).transpose(1, 0, 2, 3, 4)
    pb = k_pos.reshape(b, nk, block).transpose(1, 0, 2)
    scale = _scale(cfg)

    def step(carry, blk):
        m, l, acc = carry
        kj, vj, pj = blk
        s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kj) * scale
        s = softcap(s, cfg.attn_logit_softcap)
        mask = pj[:, None, None, None, :] <= q_pos[:, None, None, :, None]
        if window is not None:
            mask &= (q_pos[:, None, None, :, None] - pj[:, None, None, None, :]) < window
        s = jnp.where(mask, s.astype(jnp.float32), NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(q.dtype), vj
                        ).astype(jnp.float32)
        acc_new = acc * corr.transpose(0, 3, 1, 2)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, cfg.num_kv_heads, hq // cfg.num_kv_heads, sq), NEG_INF,
                  jnp.float32)
    l0 = jnp.zeros_like(m0)
    acc0 = jnp.zeros(qg.shape, jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kb, vb, pb))
    l = jnp.maximum(l, 1e-30)
    out = (acc / l.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)
    return out.reshape(q.shape)


def _attn_triangular(cfg: ModelConfig, q, k, v, q_pos, k_pos,
                     window: Optional[int], block: int = 1024):
    """Exact-causal-FLOPs blocked attention: scan over the lower-triangular
    (q-block, kv-block) pair grid, skipping the fully-masked upper triangle
    that ``chunked`` pays for.  Requires aligned q/k positions (self-attn).

    CAVEAT (EXPERIMENTS.md §Perf P10): only use with head-sharded attention
    activations — under sequence-parallel sharding the per-pair dynamic
    slices cross the sequence shards and every scan step re-gathers q/acc
    (measured 114x collective blow-up on qwen2.5 prefill_32k)."""
    b, sq, hq, hd = q.shape
    sk = k.shape[1]
    block = min(block, sq, sk)
    if sq != sk or sq % block:
        return _attn_chunked(cfg, q, k, v, q_pos, k_pos, window)
    n = sq // block
    pairs = jnp.array([(i, j) for i in range(n) for j in range(i + 1)],
                      dtype=jnp.int32)
    qg = _group(cfg, q)
    g = hq // cfg.num_kv_heads
    scale = _scale(cfg)

    def step(carry, pair):
        m, l, acc = carry  # (B,K,G,Sq), (B,K,G,Sq), (B,Sq,K,G,hd)
        i, j = pair[0], pair[1]
        qi = jax.lax.dynamic_slice_in_dim(qg, i * block, block, axis=1)
        pq = jax.lax.dynamic_slice_in_dim(q_pos, i * block, block, axis=1)
        kj = jax.lax.dynamic_slice_in_dim(k, j * block, block, axis=1)
        vj = jax.lax.dynamic_slice_in_dim(v, j * block, block, axis=1)
        pk = jax.lax.dynamic_slice_in_dim(k_pos, j * block, block, axis=1)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qi, kj) * scale
        s = softcap(s, cfg.attn_logit_softcap)
        mask = pk[:, None, None, None, :] <= pq[:, None, None, :, None]
        if window is not None:
            mask &= (pq[:, None, None, :, None] - pk[:, None, None, None, :]) < window
        s = jnp.where(mask, s.astype(jnp.float32), NEG_INF)
        mi = jax.lax.dynamic_slice_in_dim(m, i * block, block, axis=3)
        li = jax.lax.dynamic_slice_in_dim(l, i * block, block, axis=3)
        ai = jax.lax.dynamic_slice_in_dim(acc, i * block, block, axis=1)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        corr = jnp.exp(mi - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = li * corr + p.sum(axis=-1)
        pv = jnp.einsum("bkgqs,bskd->bqkgd", p.astype(q.dtype), vj
                        ).astype(jnp.float32)
        a_new = ai * corr.transpose(0, 3, 1, 2)[..., None] + pv
        m = jax.lax.dynamic_update_slice_in_dim(m, m_new, i * block, axis=3)
        l = jax.lax.dynamic_update_slice_in_dim(l, l_new, i * block, axis=3)
        acc = jax.lax.dynamic_update_slice_in_dim(acc, a_new, i * block, axis=1)
        return (m, l, acc), None

    m0 = jnp.full((b, cfg.num_kv_heads, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros_like(m0)
    acc0 = jnp.zeros(qg.shape, jnp.float32)
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), pairs)
    l = jnp.maximum(l, 1e-30)
    out = (acc / l.transpose(0, 3, 1, 2)[..., None]).astype(q.dtype)
    return out.reshape(q.shape)


def _attn_banded(cfg: ModelConfig, q, k, v, q_pos, k_pos, window: int):
    """Sliding-window attention on a 2w band: q block i attends kv blocks
    {i-1, i} with block size == window.  Exact O(S·2w) FLOPs."""
    b, s, hq, hd = q.shape
    w = window
    if s % w or s <= w:
        return _attn_dense(cfg, q, k, v, q_pos, k_pos, window)
    n = s // w
    qg = _group(cfg, q)
    g = hq // cfg.num_kv_heads
    kv_h = cfg.num_kv_heads

    def blocks(x):  # (B,S,...) -> (B,n,w,...)
        return x.reshape((b, n, w) + x.shape[2:])

    qb, kb, vb = blocks(qg), blocks(k), blocks(v)
    pqb, pkb = q_pos.reshape(b, n, w), k_pos.reshape(b, n, w)
    zk = jnp.zeros_like(kb[:, :1])
    kprev = jnp.concatenate([zk, kb[:, :-1]], axis=1)
    vprev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    pprev = jnp.concatenate([jnp.full_like(pkb[:, :1], -(10 ** 9)), pkb[:, :-1]], 1)
    k2 = jnp.concatenate([kprev, kb], axis=2)   # (B,n,2w,K,hd)
    v2 = jnp.concatenate([vprev, vb], axis=2)
    p2 = jnp.concatenate([pprev, pkb], axis=2)  # (B,n,2w)
    s_ = jnp.einsum("bnqkgd,bnskd->bnkgqs", qb, k2) * _scale(cfg)
    s_ = softcap(s_, cfg.attn_logit_softcap)
    mask = (p2[:, :, None, None, None, :] <= pqb[:, :, None, None, :, None]) & (
        pqb[:, :, None, None, :, None] - p2[:, :, None, None, None, :] < w)
    s_ = jnp.where(mask, s_.astype(jnp.float32), NEG_INF)
    p = jax.nn.softmax(s_, axis=-1).astype(q.dtype)
    out = jnp.einsum("bnkgqs,bnskd->bnqkgd", p, v2)
    return out.reshape(b, s, hq, hd)


def _attn_pallas(cfg: ModelConfig, q, k, v, q_pos, k_pos, window):
    from repro.kernels import ops
    return ops.flash_attention(
        q, k, v,
        causal=True,
        window=window,
        scale=_scale(cfg),
        logit_softcap=cfg.attn_logit_softcap,
    )


def multihead_attention(cfg: ModelConfig, p: Params, x: jax.Array,
                        positions: jax.Array, *, window: Optional[int],
                        impl: str = "chunked") -> jax.Array:
    """Full-sequence self-attention (train / prefill)."""
    q, k, v = _project_qkv(cfg, p, x, positions)
    if positions.ndim == 3:  # mrope: mask by temporal stream
        pos1d = positions[..., 0]
    else:
        pos1d = positions
    # scores, mask, softmax and AV under one name a profiler trace can
    # attribute device time by; the projections stay outside it
    with jax.named_scope("attn_core"):
        if impl == "banded" and window is not None:
            out = _attn_banded(cfg, q, k, v, pos1d, pos1d, window)
        elif impl == "dense":
            out = _attn_dense(cfg, q, k, v, pos1d, pos1d, window)
        elif impl in ("chunked", "banded", "flash"):
            # flash custom-vjp core: memory-bounded forward AND backward
            out = _attn_flash(cfg, q, k, v, pos1d, pos1d, window)
        elif impl == "triangular":
            out = _attn_triangular(cfg, q, k, v, pos1d, pos1d, window)
        elif impl == "pallas":
            out = _attn_pallas(cfg, q, k, v, pos1d, pos1d, window)
        else:
            raise ValueError(f"unknown attn impl {impl!r}")
    out = shard_activation(out, "batch", "attn_seq", "act_heads", None)
    dtype = x.dtype
    return jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(dtype))


# ---------------------------------------------------------------------------
# KV cache (prefill + decode)
# ---------------------------------------------------------------------------


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int,
                  window: Optional[int], dtype) -> Params:
    """Global layers keep full KV; local layers keep a ring of size window.

    ``pos`` is tracked per batch row so serving slots can sit at different
    absolute positions (continuous batching joins requests of mixed prompt
    lengths into one decode executable)."""
    size = max_len if window is None else min(window, max_len)
    shape = (batch, size, cfg.num_kv_heads, cfg.head_dim)
    cache = {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
        "pos": jnp.full((batch, size), -1, jnp.int32),  # abs pos per row slot
    }
    return cache


def kv_cache_axes(window: Optional[int]) -> Dict[str, Tuple]:
    return {
        "k": ("batch", "kv_seq", "kv_heads", None),
        "v": ("batch", "kv_seq", "kv_heads", None),
        "pos": ("batch", None),
    }


def cache_write(cache: Params, k: jax.Array, v: jax.Array, pos: jax.Array):
    """Write S new KV entries starting at absolute position ``pos``.

    ``pos`` is a scalar (all rows at the same position — prefill and
    lockstep decode) or a ``(B,)`` vector of per-row positions (serving
    slots at different depths; single-token writes only).  For ring (local)
    caches the write wraps modulo the ring size.
    """
    size = cache["k"].shape[1]
    b, s = k.shape[0], k.shape[1]
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        assert s == 1, "per-row cache writes are single-token (decode) only"
        rows = jnp.arange(b)
        idx = pos % size
        return {
            "k": cache["k"].at[rows, idx].set(k[:, 0]),
            "v": cache["v"].at[rows, idx].set(v[:, 0]),
            "pos": cache["pos"].at[rows, idx].set(pos),
        }
    if s >= size:
        # keep the last `size` entries
        kk, vv = k[:, -size:], v[:, -size:]
        newpos = pos + s - size + jnp.arange(size, dtype=jnp.int32)
        # rotate so slot = abs_pos % size  (keeps decode-side indexing uniform)
        slots = newpos % size
        order = jnp.argsort(slots)
        return {
            "k": jnp.take(kk, order, axis=1),
            "v": jnp.take(vv, order, axis=1),
            "pos": jnp.broadcast_to(jnp.take(newpos, order), (b, size)),
        }
    start = pos % size
    idx = (start + jnp.arange(s, dtype=jnp.int32)) % size
    newpos = pos + jnp.arange(s, dtype=jnp.int32)
    return {
        "k": cache["k"].at[:, idx].set(k),
        "v": cache["v"].at[:, idx].set(v),
        "pos": cache["pos"].at[:, idx].set(newpos),
    }


def prefill_attention(cfg: ModelConfig, p: Params, x: jax.Array,
                      positions: jax.Array, cache: Params, *,
                      window: Optional[int], impl: str = "chunked"
                      ) -> Tuple[jax.Array, Params]:
    """Full-sequence attention that also fills the KV cache."""
    q, k, v = _project_qkv(cfg, p, x, positions)
    pos1d = positions[..., 0] if positions.ndim == 3 else positions
    if window is not None and impl in ("banded", "chunked", "triangular"):
        out = _attn_banded(cfg, q, k, v, pos1d, pos1d, window)
    elif impl == "dense":
        out = _attn_dense(cfg, q, k, v, pos1d, pos1d, window)
    elif impl == "triangular":
        out = _attn_triangular(cfg, q, k, v, pos1d, pos1d, window)
    elif impl == "pallas":
        out = _attn_pallas(cfg, q, k, v, pos1d, pos1d, window)
    else:
        out = _attn_chunked(cfg, q, k, v, pos1d, pos1d, window)
    cache = cache_write(cache, k, v, jnp.asarray(0, jnp.int32))
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(x.dtype))
    return y, cache


def init_paged_kv_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
                        dtype) -> Params:
    """Pooled (paged) KV storage for global-attention layers.

    Instead of a private ``(B, max_len)`` region per decode slot, the pool
    holds ``num_blocks`` blocks of ``block_size`` entries shared by every
    slot; a per-slot block table (held by the engine's ``BatchState``) maps
    logical block ``pos // block_size`` to a pool block.  ``ppos`` mirrors
    the contiguous cache's per-entry absolute position (-1 = empty) so the
    decode-side validity mask is unchanged after the gather.
    """
    shape = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    return {
        "pk": jnp.zeros(shape, dtype),
        "pv": jnp.zeros(shape, dtype),
        "ppos": jnp.full((num_blocks, block_size), -1, jnp.int32),
    }


def paged_decode_attention(cfg: ModelConfig, p: Params, x: jax.Array,
                           cache: Params, pos: jax.Array,
                           table: jax.Array, *,
                           kernel: bool = False) -> Tuple[jax.Array, Params]:
    """One-token attention against a paged (pooled) global KV cache.

    ``table`` is ``(B, nb)`` int32 mapping each row's logical blocks to pool
    blocks, in logical order, with ``nb * block_size == max_len``.  Unmapped
    logical blocks point at the row's scratch block, so the gathered
    ``(B, nb * block_size)`` view is value-identical to the contiguous
    ``(B, max_len)`` cache for live rows — the masked softmax that follows
    is the same XLA computation and the result is bit-for-bit equal.

    ``kernel=True`` replaces the gather with the Pallas block-table kernel
    (kernels/paged_attention.py): attention runs directly against the
    ``(NB, bs, H, hd)`` pool with the table as a scalar-prefetch operand,
    skipping blocks past ``pos[b]``, and no ``(B, nb*bs, ...)`` logical
    view is ever materialized.  Same tokens, online-softmax fp band (see
    docs/serving.md); the gather path below stays as the documented
    fallback and the parity reference.

    Paged layers are always effectively global (``window is None``): local
    ring layers already bound their cache at ``window`` entries and gain
    nothing from paging.
    """
    b = x.shape[0]
    dtype = x.dtype
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = jnp.broadcast_to(pos, (b,))
    positions = pos_b[:, None]
    if cfg.rope_kind == "mrope":
        positions = positions[..., None].repeat(3, axis=-1)
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(dtype), k + p["bk"].astype(dtype), v + p["bv"].astype(dtype)
    q = apply_rope(cfg, q, positions)
    k = apply_rope(cfg, k, positions)
    bs = cache["pk"].shape[1]
    nb = table.shape[1]
    rows = jnp.arange(b)
    # physical write target: distinct across live rows (slots own disjoint
    # blocks; scratch block b appears only in row b's table)
    phys = table[rows, (pos_b // bs) % nb]
    off = pos_b % bs
    cache = {
        "pk": cache["pk"].at[phys, off].set(k[:, 0]),
        "pv": cache["pv"].at[phys, off].set(v[:, 0]),
        "ppos": cache["ppos"].at[phys, off].set(pos_b),
    }
    if kernel:
        from repro.kernels import ops
        out = ops.paged_decode_attention(
            q[:, 0], cache["pk"], cache["pv"], cache["ppos"], table, pos_b,
            scale=_scale(cfg), logit_softcap=cfg.attn_logit_softcap)
        y = jnp.einsum("bshe,hed->bsd", out[:, None].astype(dtype),
                       p["wo"].astype(dtype))
        return y, cache
    # gather the logical view: table rows are in logical order, so entry
    # (b, l) of the view is absolute position l — same layout as contiguous
    kc = cache["pk"][table].reshape(b, nb * bs, cfg.num_kv_heads, cfg.head_dim)
    vc = cache["pv"][table].reshape(b, nb * bs, cfg.num_kv_heads, cfg.head_dim)
    pc = cache["ppos"][table].reshape(b, nb * bs)
    kc = shard_activation(kc, "batch", "kv_seq", "kv_heads", None)
    vc = shard_activation(vc, "batch", "kv_seq", "kv_heads", None)
    qg = _group(cfg, q)  # (B,1,K,G,hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc) * _scale(cfg)
    s = softcap(s, cfg.attn_logit_softcap)
    valid = (pc >= 0) & (pc <= pos_b[:, None])           # (B, nb*bs)
    s = jnp.where(valid[:, None, None, None, :], s.astype(jnp.float32), NEG_INF)
    s = shard_activation(s, "batch", "kv_heads", None, None, "kv_seq")
    pr = jax.nn.softmax(s, axis=-1).astype(dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", pr, vc).reshape(q.shape)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(dtype))
    return y, cache


def decode_attention(cfg: ModelConfig, p: Params, x: jax.Array,
                     cache: Params, pos: jax.Array, *,
                     window: Optional[int]) -> Tuple[jax.Array, Params]:
    """One-token attention against the cache.  x: (B,1,D).

    ``pos`` is a scalar (lockstep decode) or a ``(B,)`` vector of per-row
    absolute positions (serving slots at different depths)."""
    b = x.shape[0]
    dtype = x.dtype
    pos = jnp.asarray(pos, jnp.int32)
    pos_b = jnp.broadcast_to(pos, (b,))
    positions = pos_b[:, None]
    if cfg.rope_kind == "mrope":
        positions = positions[..., None].repeat(3, axis=-1)
    q = jnp.einsum("bsd,dhe->bshe", x, p["wq"].astype(dtype))
    k = jnp.einsum("bsd,dhe->bshe", x, p["wk"].astype(dtype))
    v = jnp.einsum("bsd,dhe->bshe", x, p["wv"].astype(dtype))
    if cfg.qkv_bias:
        q, k, v = q + p["bq"].astype(dtype), k + p["bk"].astype(dtype), v + p["bv"].astype(dtype)
    q = apply_rope(cfg, q, positions)
    k = apply_rope(cfg, k, positions)
    cache = cache_write(cache, k, v, pos_b if pos.ndim == 1 else pos)
    kc, vc, pc = cache["k"], cache["v"], cache["pos"]
    kc = shard_activation(kc, "batch", "kv_seq", "kv_heads", None)
    vc = shard_activation(vc, "batch", "kv_seq", "kv_heads", None)
    qg = _group(cfg, q)  # (B,1,K,G,hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, kc) * _scale(cfg)
    s = softcap(s, cfg.attn_logit_softcap)
    valid = (pc >= 0) & (pc <= pos_b[:, None])           # (B, size)
    if window is not None:
        valid &= (pos_b[:, None] - pc) < window
    s = jnp.where(valid[:, None, None, None, :], s.astype(jnp.float32), NEG_INF)
    s = shard_activation(s, "batch", "kv_heads", None, None, "kv_seq")
    pr = jax.nn.softmax(s, axis=-1).astype(dtype)
    out = jnp.einsum("bkgqs,bskd->bqkgd", pr, vc).reshape(q.shape)
    y = jnp.einsum("bshe,hed->bsd", out, p["wo"].astype(dtype))
    return y, cache
