"""The scan-fused decode engine.

The legacy serving path re-jitted a fresh ``decode_step`` lambda inside
every ``generate()`` call and stepped it from a host-side Python loop —
one dispatch (and on the first call one *compile*) per generated token.
``DecodeEngine`` replaces that with a single ``lax.scan`` over the decode
step, AOT-compiled (``jit(...).lower(...).compile()``) exactly once per
(arch, batch, chunk, cache-size) shape and reused across requests,
scenarios, and replicas.  The engine is **params-free**: model parameters
enter the compiled executable as arguments, so R serving replicas (and
repeated ``generate`` calls) all share one executable.

Three shape families of executables exist:

* **prefill** — full-sequence forward filling the unified KV/state cache
  (dense KV, SSM state, RG-LRU state — one pytree), one per distinct
  (batch, prompt_len, cache_size).  Prompt lengths are exact; there is no
  padding, so recurrent (SSM / RG-LRU) states are never contaminated.
* **chunk**  — ``lax.scan`` over T decode steps with *per-slot* absolute
  positions (``pos`` is a ``(B,)`` vector; the KV cache tracks positions
  per row) and a forced-token lane: ``forced``/``force_len`` teacher-force
  the first ``force_len[b]`` steps of slot *b*, which is how a re-routed
  request replays its already-emitted tokens through the SAME executable
  instead of compiling a re-prefill at an arbitrary length.  Temperature
  is a dynamic scalar (0 = greedy argmax).
* **split**  — the same chunk, decoding through the client→edge→server
  pipeline (``transformer.split_decode_step``) at the WSSL cuts instead of
  the merged model; bit-for-bit identical logits, but every decode step
  crosses ``len(cuts)`` activation hops (accounted by the router).

``decode_compiles`` / ``prefill_compiles`` count actual XLA compilations
(AOT executables cannot retrace), which is what the serving tests pin.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.config import ModelConfig, WSSLConfig
from repro.models import transformer as tf

Params = Any


@dataclasses.dataclass
class BatchState:
    """Mutable per-replica decode state: the batched cache plus each
    slot's current token and next absolute position.

    In paged mode (``block_size > 0``) the global-attention KV lives in a
    shared block pool and ``table`` maps each slot's logical blocks to pool
    blocks.  The table is host-side numpy — the scheduler rewrites rows at
    admission/release, which must go through (or be followed by)
    :meth:`mark_table_dirty`; the engine reads :meth:`device_table`, which
    re-uploads host→device only when a row actually changed and otherwise
    reuses the cached device copy across chunks (``table_uploads`` counts
    the uploads — pinned by a regression test)."""

    cache: Params
    tok: jax.Array      # (B, 1) int32 — last token per slot
    pos: jax.Array      # (B,)   int32 — next absolute position per slot
    max_len: int
    table: Optional[np.ndarray] = None   # (B, nb) int32 block table
    block_size: int = 0
    table_uploads: int = 0               # host→device table transfers
    _table_dev: Optional[jax.Array] = dataclasses.field(
        default=None, repr=False)
    _table_dirty: bool = True

    def mark_table_dirty(self) -> None:
        """Host-side ``table`` rows changed; next chunk re-uploads."""
        self._table_dirty = True

    def device_table(self) -> Optional[jax.Array]:
        if self.table is None:
            return None
        if self._table_dev is None or self._table_dirty:
            self._table_dev = jnp.asarray(self.table, jnp.int32)
            self._table_dirty = False
            self.table_uploads += 1
        return self._table_dev


def _scatter_slot(dst: Params, src: Params, slot: int) -> Params:
    """Write a batch-1 cache into row ``slot`` of a batched cache.

    Stacked super-block leaves carry the scan axis first (batch at axis 1);
    remainder-layer leaves have batch at axis 0.  The whole row is
    replaced, which also wipes any stale validity from the slot's previous
    occupant (fresh caches mark every position -1)."""
    stack = jax.tree.map(lambda d, s: d.at[:, slot].set(s[:, 0]),
                         dst["stack"], src["stack"])
    rem = jax.tree.map(lambda d, s: d.at[slot].set(s[0]),
                       dst["rem"], src["rem"])
    return {"stack": stack, "rem": rem}


def _walk_cache(fn, cache, *rest):
    """Apply ``fn(layer_cache, stacked, *companions)`` to every per-layer
    cache dict of a merged cache, one stage cache, or a list of stage
    caches, preserving structure.  ``stacked`` tells ``fn`` whether leaves
    carry the leading super-block scan axis (batch at axis 1) or not.
    Companion trees may hold ``None`` where a layer was skipped."""
    if isinstance(cache, (list, tuple)) and cache and \
            isinstance(cache[0], dict) and "stack" in cache[0]:
        return [_walk_cache(fn, c, *(r[i] for r in rest))
                for i, c in enumerate(cache)]
    out = {"stack": [fn(d, True, *(r["stack"][j] for r in rest))
                     for j, d in enumerate(cache["stack"])]}
    if "rem" in cache:
        out["rem"] = [fn(d, False, *(r["rem"][j] for r in rest))
                      for j, d in enumerate(cache["rem"])]
    return out


def _is_recurrent(d) -> bool:
    """SSM / RG-LRU layer caches are cumulative state (incl. conv windows)."""
    return isinstance(d, dict) and ("state" in d or "h" in d)


@functools.partial(jax.jit, donate_argnums=(0,), static_argnums=(4,))
def _scatter_slot_paged_jit(dst: Params, src: Params, slot: jax.Array,
                            blocks: jax.Array, block_size: int) -> Params:
    """Paged-mode admission: write a batch-1 *contiguous* prefill cache
    into a pooled batched cache — touching O(reserved blocks), not O(pool).

    Non-paged layers (local rings, SSM/RG-LRU state) keep the contiguous
    per-row layout and get the usual whole-row replace.  Paged layers
    reshape the contiguous ``(1, max_len, ...)`` region into blocks and
    scatter ONLY the ``len(blocks)`` reserved pool rows (reservations
    cover the prompt; tail blocks within the reservation carry fresh -1
    entries, wiping whatever their previous owner left).  The slot's
    scratch block — mapped by every logical block past the reservation —
    gets its ``ppos`` row wiped to -1 instead of a full K/V rewrite: stale
    K/V under an invalid position is never read, but a stale *position*
    from the slot's empty-phase garbage decode would pass the validity
    mask.  The donated ``dst`` makes the whole scatter an in-place pool
    update (regression-tested via the executable's cost analysis)."""
    nr = blocks.shape[0]

    def put(pool, vals, axis):
        # one in-place dynamic_update_slice per reserved block: a scatter
        # along the block axis of a layer-stacked pool makes XLA transpose
        # (copy) the whole pool to bring that axis to the front
        def body(i, pool):
            row = jax.lax.dynamic_index_in_dim(vals, i, axis)
            return jax.lax.dynamic_update_slice_in_dim(pool, row, blocks[i],
                                                       axis)
        return jax.lax.fori_loop(0, nr, body, pool)

    def write(d, stacked, s):
        ax = 1 if stacked else 0
        if "pk" in d:
            def resh(a):  # (.., 1, max_len, ...) -> (.., nr, bs, ...)
                nb = a.shape[ax + 1] // block_size
                a = a.reshape(a.shape[:ax] + (nb, block_size)
                              + a.shape[ax + 2:])
                return jax.lax.slice_in_dim(a, 0, nr, axis=ax)
            ppos = put(d["ppos"], resh(s["pos"]), ax)
            wipe = jnp.full(ppos.shape[:ax] + (1,) + ppos.shape[ax + 1:], -1,
                            ppos.dtype)
            return {"pk": put(d["pk"], resh(s["k"]), ax),
                    "pv": put(d["pv"], resh(s["v"]), ax),
                    "ppos": jax.lax.dynamic_update_slice_in_dim(
                        ppos, wipe, slot, ax)}
        if stacked:
            return jax.tree.map(lambda dd, ss: dd.at[:, slot].set(ss[:, 0]),
                                d, s)
        return jax.tree.map(lambda dd, ss: dd.at[slot].set(ss[0]), d, s)

    return _walk_cache(write, dst, src)


def _scatter_slot_paged(dst: Params, src: Params, slot: int,
                        blocks: np.ndarray, block_size: int) -> Params:
    """See :func:`_scatter_slot_paged_jit` — this wrapper normalizes the
    host-side ``slot``/``blocks`` so jit retraces only per distinct
    (cache shapes, reserved-count) pair, never per slot id."""
    return _scatter_slot_paged_jit(
        dst, src, jnp.asarray(slot, jnp.int32),
        jnp.asarray(np.asarray(blocks), jnp.int32), block_size)


class DecodeEngine:
    """Compile-once decode engine for one architecture.

    ``cuts=None`` serves the merged WSSL global model; a cut tuple serves
    through the client→edge→server pipeline stages (same logits, per-hop
    activation crossings).  All compiled executables take ``params`` as an
    argument — replicas with synced params share every executable."""

    def __init__(self, cfg: ModelConfig, *, impl: str = "dense",
                 cuts: Optional[Sequence[int]] = None,
                 decode_window_override: Optional[int] = None,
                 spec_cut: Optional[int] = None,
                 paged_kernel: bool = False):
        self.cfg = cfg
        self.impl = impl
        self.cuts = tuple(int(c) for c in cuts) if cuts else None
        self.decode_window_override = decode_window_override
        # paged decode attention via the Pallas block-table kernel instead
        # of the gather path (kernels/paged_attention.py); contiguous
        # caches are unaffected
        self.paged_kernel = bool(paged_kernel)
        if spec_cut is None:
            # the draft model is the client stage: in split mode that stage
            # already exists at cuts[0]; merged mode drafts at the WSSL
            # default cut (cut 0 = embedding-only draft is legal)
            spec_cut = self.cuts[0] if self.cuts else \
                WSSLConfig().resolve_split(cfg)
        self.spec_cut = int(tf._check_cuts(cfg, (spec_cut,))[0])
        self._executables: Dict[Tuple, Any] = {}
        self.decode_compiles = 0
        self.prefill_compiles = 0
        self.draft_compiles = 0
        self.verify_compiles = 0

    # -- topology ----------------------------------------------------------

    @property
    def num_stages(self) -> int:
        return len(self.cuts) + 1 if self.cuts else 1

    @property
    def num_hops(self) -> int:
        """Activation crossings per decode step (0 for the merged model)."""
        return len(self.cuts) if self.cuts else 0

    @property
    def draft_fraction(self) -> float:
        """Cost of one draft step relative to a full decode step: layers up
        to the spec cut plus the early-exit readout (counted as one layer).
        The router prices the speculative clock with this."""
        return (self.spec_cut + 1) / (self.cfg.num_layers + 1)

    # -- compiled primitives ----------------------------------------------

    def _prefill_exec(self, params, prompts, cache):
        b, s0 = prompts.shape
        key = ("prefill", b, s0) + tuple(
            l.shape for l in jax.tree.leaves(cache))
        if key not in self._executables:
            def run(params, prompts, cache):
                logits, cache = tf.prefill(params, self.cfg, prompts,
                                           cache=cache, impl=self.impl)
                tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
                return tok.astype(jnp.int32), cache

            # the fresh cache is consumed — donate it so XLA fills the
            # buffer in place instead of allocating a second copy
            self._executables[key] = (
                jax.jit(run, donate_argnums=(2,))
                .lower(params, prompts, cache).compile())
            self.prefill_compiles += 1
        return self._executables[key]

    def _chunk_exec(self, params, tok, cache, pos, forced, force_len, rng,
                    temperature, table=None):
        b, t_chunk = forced.shape
        key = ("chunk", b, t_chunk, table is not None) + tuple(
            l.shape for l in jax.tree.leaves(cache))
        if key not in self._executables:
            def run(params, tok, cache, pos, forced, force_len, rng,
                    temperature, *t_args):
                table = t_args[0] if t_args else None
                # split mode: partition params/cache ONCE per chunk and
                # carry the per-stage caches through the scan (a
                # partition/join pair inside the loop body would cross the
                # carry and re-materialize every cache leaf per token)
                if self.cuts is not None:
                    stages = tf.partition_params(params, self.cfg,
                                                 self.cuts)
                    cache = tf.partition_cache(cache, self.cfg, self.cuts)

                def step(carry, xs):
                    t, forced_t = xs
                    tok, cache, pos, rng = carry
                    if self.cuts is None:
                        logits, cache = tf.decode_step(
                            params, self.cfg, tok, cache, pos,
                            decode_window_override=self.decode_window_override,
                            table=table, paged_kernel=self.paged_kernel)
                    else:
                        logits, cache = tf.split_decode_step(
                            stages, self.cfg, tok, cache, pos,
                            decode_window_override=self.decode_window_override,
                            table=table, paged_kernel=self.paged_kernel)
                    lg = logits[:, 0]
                    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    rng, sub = jax.random.split(rng)
                    sampled = jax.random.categorical(
                        sub, lg / jnp.maximum(temperature, 1e-6)
                    ).astype(jnp.int32)
                    nxt = jnp.where(temperature > 0, sampled, greedy)
                    nxt = jnp.where(t < force_len, forced_t, nxt)
                    return (nxt[:, None], cache, pos + 1, rng), nxt

                n = forced.shape[1]
                (tok, cache, pos, rng), ys = jax.lax.scan(
                    step, (tok, cache, pos, rng),
                    (jnp.arange(n), jnp.swapaxes(forced, 0, 1)))
                if self.cuts is not None:
                    cache = tf.join_cache_stages(cache)
                return jnp.swapaxes(ys, 0, 1), tok, cache, pos

            args = (params, tok, cache, pos, forced, force_len, rng,
                    temperature) + (() if table is None else (table,))
            # donate the cache: the caller always replaces state.cache with
            # the chunk's output, so the (multi-GB, in paged mode pooled)
            # input buffer is dead on entry — donation updates it in place
            # and peak live memory holds ONE pool copy, not two
            self._executables[key] = (
                jax.jit(run, donate_argnums=(2,)).lower(*args).compile())
            self.decode_compiles += 1
        return self._executables[key]

    def _draft_exec(self, params, tok, cache, pos, k, table=None):
        """AOT draft: K greedy tokens from the client stage alone.

        The client stage (params + cache truncated at ``spec_cut``) scans K
        decode steps, reading each next token out through the early-exit
        head.  The mutated client cache is *discarded* — the caller's cache
        is rolled forward by the verify pass, which rewrites the same
        positions with teacher-forced draft tokens."""
        b = tok.shape[0]
        key = ("draft", b, k, table is not None) + tuple(
            l.shape for l in jax.tree.leaves(cache))
        if key not in self._executables:
            def run(params, tok, cache, pos, *t_args):
                table = t_args[0] if t_args else None
                client = tf.partition_params(params, self.cfg,
                                             (self.spec_cut,))[0]
                ccache = tf.partition_cache(cache, self.cfg,
                                            (self.spec_cut,))[0]

                def step(carry, _):
                    tok, ccache, pos = carry
                    x, ccache = tf.stage_decode_step(
                        client, self.cfg, tok, ccache, pos, 0, 2,
                        decode_window_override=self.decode_window_override,
                        table=table, paged_kernel=self.paged_kernel)
                    logits = tf.early_exit_logits(params, self.cfg, x)
                    nxt = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
                    return (nxt[:, None], ccache, pos + 1), nxt

                _, drafts = jax.lax.scan(step, (tok, ccache, pos), None,
                                         length=k)
                return jnp.swapaxes(drafts, 0, 1)    # (B, K)

            # NO cache donation here: the draft discards its mutated client
            # cache and the caller passes the SAME cache straight into the
            # verify executable — donating would invalidate it
            args = (params, tok, cache, pos) + (
                () if table is None else (table,))
            self._executables[key] = jax.jit(run).lower(*args).compile()
            self.draft_compiles += 1
        return self._executables[key]

    def _verify_exec(self, params, tok, cache, pos, draft, max_len,
                     table=None):
        """AOT verify: one fused chunk that teacher-forces the K draft
        tokens through the full pipeline, accepts the longest matching
        prefix + the first correction, and rolls the cache back to exactly
        the state sequential greedy decoding would have produced.

        Rollback is exact per cache family: recurrent layers (SSM/RG-LRU,
        incl. their conv windows) restore a per-step snapshot; full-length
        KV caches invalidate the rejected positions (their writes never
        wrap, so nothing valid was evicted); ring KV caches (size <
        max_len) restore the per-step overwritten lines, because a rejected
        write may have wrapped onto a still-visible entry."""
        b, k = draft.shape
        key = ("verify", b, k, max_len, table is not None) + tuple(
            l.shape for l in jax.tree.leaves(cache))
        if key not in self._executables:
            def run(params, tok, cache, pos, draft, *t_args):
                table = t_args[0] if t_args else None
                if self.cuts is not None:
                    stages = tf.partition_params(params, self.cfg, self.cuts)
                    cache = tf.partition_cache(cache, self.cfg, self.cuts)
                rows = jnp.arange(b)
                pos0 = pos

                def snap_lines(d, stacked, pos_c):
                    # pre-write snapshot of the ring line this step will hit
                    if "pos" not in d or d["pos"].shape[-1] >= max_len:
                        return None
                    idx = pos_c % d["pos"].shape[-1]
                    if stacked:
                        return {kk: d[kk][:, rows, idx]
                                for kk in ("k", "v", "pos")}
                    return {kk: d[kk][rows, idx] for kk in ("k", "v", "pos")}

                def step(carry, d_t):
                    tok, cache, pos = carry
                    lines = _walk_cache(
                        lambda d, st: snap_lines(d, st, pos), cache)
                    if self.cuts is None:
                        logits, cache = tf.decode_step(
                            params, self.cfg, tok, cache, pos,
                            decode_window_override=self.decode_window_override,
                            table=table, paged_kernel=self.paged_kernel)
                    else:
                        logits, cache = tf.split_decode_step(
                            stages, self.cfg, tok, cache, pos,
                            decode_window_override=self.decode_window_override,
                            table=table, paged_kernel=self.paged_kernel)
                    greedy = jnp.argmax(logits[:, 0], axis=-1
                                        ).astype(jnp.int32)
                    recs = _walk_cache(
                        lambda d, st: d if _is_recurrent(d) else None, cache)
                    return (d_t[:, None], cache, pos + 1), (greedy, recs,
                                                            lines)

                (_, cache, _), (greedy, recs, lines) = jax.lax.scan(
                    step, (tok, cache, pos), jnp.swapaxes(draft, 0, 1))
                greedy = jnp.swapaxes(greedy, 0, 1)          # (B, K)
                match = (greedy == draft).astype(jnp.int32)
                acc = jnp.cumprod(match, axis=1).sum(axis=1)  # drafts accepted
                n = jnp.minimum(acc + 1, k)                   # tokens emitted
                thr = pos0 + n - 1                            # last valid pos

                def fix(d, stacked, rec, line):
                    if rec is not None:
                        # state after step n-1 == after emitting n tokens
                        if stacked:
                            return jax.tree.map(
                                lambda s: jnp.moveaxis(s, 1, 0)[:, n - 1,
                                                                rows], rec)
                        return jax.tree.map(lambda s: s[n - 1, rows], rec)
                    if "pk" in d:
                        pl = d["ppos"]
                        if stacked:
                            view = pl[:, table]   # (n_full, B, nb, bs)
                            view = jnp.where(
                                view > thr[None, :, None, None], -1, view)
                            return {**d, "ppos": pl.at[:, table].set(view)}
                        view = pl[table]          # (B, nb, bs)
                        view = jnp.where(view > thr[:, None, None], -1, view)
                        return {**d, "ppos": pl.at[table].set(view)}
                    if line is None:
                        # full-length contiguous KV: mask rejected entries
                        pl = d["pos"]
                        t = thr[None, :, None] if stacked else thr[:, None]
                        return {**d, "pos": jnp.where(pl > t, -1, pl)}
                    # ring KV: restore the overwritten line of every
                    # rejected step (distinct ring indices since k <= size)
                    size = d["pos"].shape[-1]
                    kc, vc, pc = d["k"], d["v"], d["pos"]
                    for j in range(k):
                        rej = j >= n                        # (B,)
                        idx = (pos0 + j) % size             # (B,)
                        lk, lv, lp = (line[kk][j] for kk in ("k", "v", "pos"))
                        if stacked:
                            sel = rej[None, :, None, None]
                            kc = kc.at[:, rows, idx].set(
                                jnp.where(sel, lk, kc[:, rows, idx]))
                            vc = vc.at[:, rows, idx].set(
                                jnp.where(sel, lv, vc[:, rows, idx]))
                            pc = pc.at[:, rows, idx].set(
                                jnp.where(rej[None, :], lp, pc[:, rows, idx]))
                        else:
                            sel = rej[:, None, None]
                            kc = kc.at[rows, idx].set(
                                jnp.where(sel, lk, kc[rows, idx]))
                            vc = vc.at[rows, idx].set(
                                jnp.where(sel, lv, vc[rows, idx]))
                            pc = pc.at[rows, idx].set(
                                jnp.where(rej, lp, pc[rows, idx]))
                    return {"k": kc, "v": vc, "pos": pc}

                cache = _walk_cache(fix, cache, recs, lines)
                if self.cuts is not None:
                    cache = tf.join_cache_stages(cache)
                new_tok = jnp.take_along_axis(greedy, (n - 1)[:, None],
                                              axis=1)
                return greedy, acc, n, new_tok, cache, pos0 + n

            # the verify pass is the cache's last reader in a speculative
            # round (the draft ran first) — donate it like the chunk exec
            args = (params, tok, cache, pos, draft) + (
                () if table is None else (table,))
            self._executables[key] = (
                jax.jit(run, donate_argnums=(2,)).lower(*args).compile())
            self.verify_compiles += 1
        return self._executables[key]

    # -- cache / state -----------------------------------------------------

    def init_cache(self, batch: int, max_len: int,
                   paged: Optional[Tuple[int, int]] = None) -> Params:
        return tf.init_cache(
            self.cfg, batch, max_len,
            decode_window_override=self.decode_window_override,
            paged=paged)

    def new_batch_state(self, slots: int, max_len: int, *,
                        block_size: int = 0,
                        pool_blocks: int = 0) -> BatchState:
        """Empty slots decode garbage in lockstep with the live ones
        (slot-granularity admission) — safely, because ``decode_attention``
        writes each row's K/V at its current position *before* building
        the validity mask, so even an all-empty row attends to at least
        its own fresh entry.  Admission replaces the whole row.

        ``block_size > 0`` switches the global-attention KV to a paged pool
        of ``pool_blocks`` blocks (default: full residency — every slot can
        hold ``max_len`` — plus one scratch block per slot).  Fresh table
        rows point every logical block at the slot's scratch block, so the
        garbage lockstep stays confined to the slot's own storage."""
        if not block_size:
            return BatchState(cache=self.init_cache(slots, max_len),
                              tok=jnp.zeros((slots, 1), jnp.int32),
                              pos=jnp.ones((slots,), jnp.int32),
                              max_len=max_len)
        if max_len % block_size:
            raise ValueError(
                f"max_len {max_len} must be a multiple of block_size "
                f"{block_size} (the table maps whole blocks)")
        nb = max_len // block_size
        if not pool_blocks:
            pool_blocks = slots * (nb + 1)
        if pool_blocks <= slots:
            raise ValueError(
                f"pool_blocks {pool_blocks} leaves no allocatable blocks "
                f"after {slots} per-slot scratch blocks")
        cache = self.init_cache(slots, max_len, paged=(pool_blocks,
                                                       block_size))
        table = np.repeat(np.arange(slots, dtype=np.int32)[:, None], nb,
                          axis=1)
        return BatchState(cache=cache,
                          tok=jnp.zeros((slots, 1), jnp.int32),
                          pos=jnp.ones((slots,), jnp.int32),
                          max_len=max_len, table=table,
                          block_size=block_size)

    # -- serving primitives ------------------------------------------------

    def admit(self, state: BatchState, params: Params,
              prompt: np.ndarray, slot: int,
              blocks: Optional[Sequence[int]] = None) -> int:
        """Prefill one request at its exact prompt length into ``slot``.

        Returns the request's first generated token (greedy over the last
        prompt position — re-admissions after a replica drop re-derive the
        same token deterministically and replay the rest).

        Paged mode: ``blocks`` are the pool blocks reserved for this
        request (allocator order == logical order); the table row maps the
        unreserved logical tail to the slot's scratch block.  Prefill runs
        on a contiguous batch-1 cache — the same executable as unpaged —
        then scatters block-wise into the pool."""
        prompt = jnp.asarray(np.asarray(prompt), jnp.int32)[None]
        length = prompt.shape[1]
        if length >= state.max_len:
            raise ValueError(
                f"prompt of length {length} does not fit a max_len="
                f"{state.max_len} cache with room to decode; global KV "
                f"entries past max_len would silently wrap and overwrite "
                f"the prompt")
        cache1 = self.init_cache(1, state.max_len)
        exe = self._prefill_exec(params, prompt, cache1)
        tok, cache1 = exe(params, prompt, cache1)
        if state.table is not None:
            if blocks is None:
                raise ValueError(
                    "paged admission needs the request's reserved blocks "
                    "(BlockAllocator.allocate)")
            nb = state.table.shape[1]
            row = np.full((nb,), slot, np.int32)
            row[:len(blocks)] = np.asarray(blocks, np.int32)
            state.table[slot] = row
            state.mark_table_dirty()
            state.cache = _scatter_slot_paged(state.cache, cache1, slot,
                                              np.asarray(blocks, np.int32),
                                              state.block_size)
        else:
            state.cache = _scatter_slot(state.cache, cache1, slot)
        state.tok = state.tok.at[slot].set(tok[0])
        state.pos = state.pos.at[slot].set(length)
        return int(tok[0, 0])

    def decode_chunk(self, state: BatchState, params: Params,
                     forced: np.ndarray, force_len: np.ndarray,
                     rng: jax.Array, temperature: float = 0.0) -> np.ndarray:
        """Advance every slot by ``forced.shape[1]`` tokens (one fused
        executable).  Returns the (B, T) emitted tokens."""
        forced = jnp.asarray(np.asarray(forced), jnp.int32)
        force_len = jnp.asarray(np.asarray(force_len), jnp.int32)
        temp = jnp.asarray(temperature, jnp.float32)
        table = state.device_table()
        exe = self._chunk_exec(params, state.tok, state.cache, state.pos,
                               forced, force_len, rng, temp, table)
        args = (params, state.tok, state.cache, state.pos, forced,
                force_len, rng, temp) + (() if table is None else (table,))
        toks, tok, cache, pos = exe(*args)
        state.tok, state.cache, state.pos = tok, cache, pos
        return np.asarray(toks)

    def spec_chunk(self, state: BatchState, params: Params,
                   draft_k: int) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """One speculative round: draft ``draft_k`` tokens with the client
        stage, verify them in one fused full-pipeline chunk, accept the
        longest matching prefix plus the verifier's first correction.

        Advances each slot by ``n[b] ∈ [1, draft_k]`` positions and returns
        ``(tokens (B, K), accepted_drafts (B,), emitted (B,))`` — the first
        ``emitted[b]`` entries of row ``b`` are exactly the tokens greedy
        decoding would produce (verified, bit-for-bit)."""
        table = state.device_table()
        t_args = () if table is None else (table,)
        dexe = self._draft_exec(params, state.tok, state.cache, state.pos,
                                draft_k, table)
        draft = dexe(params, state.tok, state.cache, state.pos, *t_args)
        vexe = self._verify_exec(params, state.tok, state.cache, state.pos,
                                 draft, state.max_len, table)
        greedy, acc, n, tok, cache, pos = vexe(
            params, state.tok, state.cache, state.pos, draft, *t_args)
        state.tok, state.cache, state.pos = tok, cache, pos
        return np.asarray(greedy), np.asarray(acc), np.asarray(n)

    # -- one-shot batched generation --------------------------------------

    def generate(self, params: Params, prompts: jax.Array, gen: int, *,
                 temperature: float = 0.0,
                 rng: Optional[jax.Array] = None) -> jax.Array:
        """Batched generation, compiled once per (batch, prompt, gen) shape
        — the drop-in replacement for the legacy host-side decode loop
        (bit-for-bit identical greedy tokens, golden-tested)."""
        prompts = jnp.asarray(prompts, jnp.int32)
        b, s0 = prompts.shape
        cache = self.init_cache(b, s0 + gen)
        exe = self._prefill_exec(params, prompts, cache)
        tok, cache = exe(params, prompts, cache)
        out = [tok]
        if gen > 1:
            if temperature > 0 and rng is None:
                raise ValueError("temperature > 0 requires an rng key")
            rng = jax.random.PRNGKey(0) if rng is None else rng
            forced = jnp.zeros((b, gen - 1), jnp.int32)
            force_len = jnp.zeros((b,), jnp.int32)
            pos = jnp.full((b,), s0, jnp.int32)
            temp = jnp.asarray(temperature, jnp.float32)
            cexe = self._chunk_exec(params, tok, cache, pos, forced,
                                    force_len, rng, temp)
            ys, _, _, _ = cexe(params, tok, cache, pos, forced, force_len,
                               rng, temp)
            out.append(ys)
        return jnp.concatenate(out, axis=1)


_ENGINES: Dict[Tuple, DecodeEngine] = {}


def get_engine(cfg: ModelConfig, *, impl: str = "dense",
               cuts: Optional[Sequence[int]] = None,
               decode_window_override: Optional[int] = None,
               spec_cut: Optional[int] = None,
               paged_kernel: bool = False) -> DecodeEngine:
    """Process-wide engine cache: repeated ``generate()`` calls (and all
    replicas of a served model) reuse one engine and its executables."""
    key = (cfg, impl, tuple(cuts) if cuts else None, decode_window_override,
           spec_cut, paged_kernel)
    if key not in _ENGINES:
        _ENGINES[key] = DecodeEngine(
            cfg, impl=impl, cuts=cuts,
            decode_window_override=decode_window_override,
            spec_cut=spec_cut, paged_kernel=paged_kernel)
    return _ENGINES[key]
