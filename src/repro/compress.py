"""Update-path communication compression with error feedback.

The §III-E efficiency claim, *reduced* instead of merely measured: the
client updates uploaded for aggregation (the post-optimizer stage deltas —
the `sync` column of the CommLog) are compressed before they cross the
wire and reconstructed in front of ``aggregation.aggregate_clients``, so
every registry rule (importance through Krum / geometric-median) runs on
the decompressed updates.

Schemes (``CompressionConfig.scheme``):

* ``topk`` — per-leaf magnitude top-k: each client row keeps the ``rate``
  fraction of largest-|x| coordinates; the wire carries (fp32 value,
  int32 index) pairs → ``8·k`` bytes per row vs ``4·m`` raw.
* ``int8`` / ``int4`` — stochastic symmetric quantization at
  ``levels = 2^(bits-1) − 1`` integer levels per client row with a per-leaf
  fp32 scale (max |x|) → ``m·bits/8 + 4`` bytes per row.  Stochastic
  rounding makes the reconstruction unbiased: E[deq(q)] = x.

Both knobs are **dynamic**: the top-k ``rate`` and the quantization
``levels``/``bits`` reach the jit'd round only as traced fp32 scalars
(:class:`CompressionParams`), so one compiled executable serves every
compression level of a scheme *kind* — int8 and int4 are literally the
same executable (``CompressionConfig.kind == "quant"``), exactly like
``AsyncParams`` serves every deadline.

**Error feedback** (``error_feedback=True``, the default) keeps a
per-client fp32 residual ``e`` the shape of the stacked client stage
(``WSSLState.ef_residual``):

    x       = Δ + e                      (the update it *wants* to send)
    sent    = decompress(compress(x))
    e'      = x − sent                   (the part the wire dropped)

Participating clients send ``sent`` and carry ``e'``; masked clients send
exactly 0 and carry ``e`` unchanged, so the memory of a skipped round is
not lost.  The invariant Σ sent + e_final = Σ Δ (per client, exactly for
top-k, in expectation for stochastic quantization) is what lets biased
compressors converge (EF-SGD / EF21).

The hot loops are Pallas TPU kernels (``kernels/compress.py``, interpret
mode on CPU) with pure-jnp oracles in ``kernels/ref.py``; the per-row
reductions that feed them (sort for the top-k threshold, max |x| for the
quantization scale) are plain XLA.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import CompressionConfig
from repro.kernels import ops

Params = Any


class CompressionParams(NamedTuple):
    """Dynamic (traced) scalars of a CompressionConfig — the jit input.

    Only the scheme *kind* (none | topk | quant) is a static branch; the
    sparsification rate and the quantization level count / wire bits are
    traced, so one executable serves every compression level."""

    rate: jax.Array      # topk: kept fraction of coordinates per row
    levels: jax.Array    # quant: integer levels per side (127=int8, 7=int4)
    bits: jax.Array      # quant: wire bits per element (for byte accounting)


def compression_params(cfg: CompressionConfig) -> CompressionParams:
    """Lower the config block to dynamic fp32 scalars."""
    f = lambda v: jnp.asarray(v, jnp.float32)
    levels = float(2 ** (cfg.bits - 1) - 1) if cfg.kind == "quant" else 1.0
    return CompressionParams(rate=f(cfg.rate), levels=f(levels),
                             bits=f(cfg.bits))


def topk_threshold(x2: jax.Array, rate) -> jax.Array:
    """Per-row magnitude threshold: the k-th largest |x|, k = ⌈rate·m⌉
    clipped to [1, m].  ``rate`` may be a traced scalar — the sort is
    static-shape and the cut index is a dynamic gather."""
    n, m = x2.shape
    if m == 0:
        return jnp.zeros((n,), jnp.float32)
    a = jnp.sort(jnp.abs(x2.astype(jnp.float32)), axis=1)   # ascending
    k = jnp.clip(jnp.round(jnp.asarray(rate, jnp.float32) * m), 1.0, float(m))
    idx = jnp.clip(m - k, 0.0, float(m - 1)).astype(jnp.int32)
    idx2 = jnp.broadcast_to(jnp.reshape(idx, (1, 1)), (n, 1))
    return jnp.take_along_axis(a, idx2, axis=1)[:, 0]


def _compress_leaf(x2: jax.Array, rng: jax.Array, kind: str,
                   params: CompressionParams) -> jax.Array:
    """fp32 (N, M) -> its wire reconstruction decompress(compress(x))."""
    if kind == "topk":
        # ties at the threshold may keep a few extra coordinates; the wire
        # format (and the byte accounting) carries exactly k pairs
        return ops.topk_mask(x2, topk_threshold(x2, params.rate))
    if kind == "quant":
        scale = jnp.max(jnp.abs(x2), axis=1)
        step = jnp.where(scale > 0, scale / params.levels, 0.0)
        inv_step = jnp.where(scale > 0, params.levels / scale, 0.0)
        u = jax.random.uniform(rng, x2.shape, jnp.float32)
        q = ops.quantize_stochastic(x2, u, inv_step, params.levels)
        return ops.dequantize(q, step)
    raise ValueError(f"unknown compression kind {kind!r}")


def init_ef_residual(client_stack: Params) -> Params:
    """Zero fp32 residual accumulators mirroring the stacked client stage."""
    return jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32),
                        client_stack)


def apply_compression(delta: Params, residual: Params, mask: jax.Array,
                      rng: jax.Array, cfg: CompressionConfig,
                      params: Optional[CompressionParams] = None
                      ) -> Tuple[Params, Params]:
    """Compress the stacked client updates, with error feedback.

    delta: stacked update pytree, leaves (N, ...); residual: matching fp32
    pytree (or ``()`` when error feedback is off); mask: (N,) participation
    (fractional staleness-discounted masks count as participating where
    ``mask > 0``).  Returns ``(sent, new_residual)`` — ``sent`` is what the
    wire reconstructs (masked clients send exactly 0), ``new_residual``
    carries what the wire dropped (masked clients carry theirs unchanged).
    """
    if params is None:
        params = compression_params(cfg)
    kind = cfg.kind
    if kind == "none":
        return delta, residual
    ef = bool(jax.tree.leaves(residual))
    leaves_d, treedef = jax.tree.flatten(delta)
    leaves_r = (jax.tree.leaves(residual) if ef
                else [None] * len(leaves_d))
    sent_leaves, res_leaves = [], []
    for i, (d, r) in enumerate(zip(leaves_d, leaves_r)):
        n = d.shape[0]
        x2 = d.reshape(n, -1).astype(jnp.float32)
        if x2.shape[1] == 0:    # empty leaf: nothing to send or accumulate
            sent_leaves.append(jnp.zeros_like(d))
            if ef:
                res_leaves.append(r)
            continue
        if ef:
            x2 = x2 + r.reshape(n, -1)
        rec = _compress_leaf(x2, jax.random.fold_in(rng, i), kind, params)
        on = (mask > 0).reshape(n, *([1] * (rec.ndim - 1)))
        sent2 = jnp.where(on, rec, jnp.zeros_like(rec))
        sent_leaves.append(sent2.reshape(d.shape).astype(d.dtype))
        if ef:
            r2 = r.reshape(n, -1)
            new_r = jnp.where(on, x2 - rec, r2)
            res_leaves.append(new_r.reshape(r.shape))
    sent = jax.tree.unflatten(treedef, sent_leaves)
    new_res = jax.tree.unflatten(treedef, res_leaves) if ef else residual
    return sent, new_res


def compressed_stage_bytes(client_stack: Params, cfg: CompressionConfig,
                           params: Optional[CompressionParams] = None):
    """Traced wire bytes of ONE client's compressed stage upload.

    Must agree exactly with the concrete ``protocol.compressed_update_bytes``
    (tested): topk carries k (fp32 value, int32 index) pairs per leaf row;
    quant carries m·bits/8 payload + one fp32 scale per leaf row."""
    if params is None:
        params = compression_params(cfg)
    kind = cfg.kind
    total = jnp.zeros((), jnp.float32)
    for l in jax.tree.leaves(client_stack):
        # per-client elements from the leaf's own leading axis: in the
        # sharded round the stack holds N/S clients, and bytes are per
        # client either way
        m = l.size // l.shape[0]
        if m == 0:
            continue
        if kind == "none":
            total = total + m * l.dtype.itemsize
        elif kind == "topk":
            k = jnp.clip(jnp.round(params.rate * m), 1.0, float(m))
            total = total + k * 8.0
        else:   # quant — whole wire bytes (odd-m int4 pads a nibble)
            total = total + jnp.ceil(m * params.bits / 8.0) + 4.0
    return total


def compress_activations(a: jax.Array, rng: jax.Array,
                         cfg: CompressionConfig,
                         params: Optional[CompressionParams] = None
                         ) -> jax.Array:
    """Wire reconstruction of an activation tensor crossing a split hop.

    ``a`` is any (..., d) activation (or activation-cotangent — the round
    chains this into its manual vjp relay, which makes the backward pass
    the straight-through estimate of the compressed forward).  Rows are
    the flattened leading dims: each d-vector is compressed independently
    with the same scheme/params as the update path.  No error feedback —
    activations are transient, there is nothing to accumulate into."""
    if params is None:
        params = compression_params(cfg)
    if cfg.kind == "none":
        return a
    d = a.shape[-1]
    if d == 0 or a.size == 0:
        return a
    x2 = a.reshape(-1, d).astype(jnp.float32)
    rec = _compress_leaf(x2, rng, cfg.kind, params)
    return rec.reshape(a.shape).astype(a.dtype)


def activation_wire_bytes(rows: int, d: int, cfg: CompressionConfig,
                          params: Optional[CompressionParams] = None):
    """Traced wire bytes of ONE client's compressed activation crossing a
    hop: ``rows`` d-vectors (rows = per-client batch·seq).  Mirrors the
    per-row wire format of :func:`compressed_stage_bytes`."""
    if params is None:
        params = compression_params(cfg)
    kind = cfg.kind
    if kind == "none":
        return jnp.asarray(rows * d * 4.0, jnp.float32)
    if kind == "topk":
        k = jnp.clip(jnp.round(params.rate * d), 1.0, float(d))
        return rows * k * 8.0
    return rows * (jnp.ceil(d * params.bits / 8.0) + 4.0)   # quant
