"""Bounded-staleness asynchronous WSSL rounds.

The synchronous ``core/round.py::wssl_round`` is a barrier: every selected
client's update lands in the round it was computed, and stragglers are
modeled as partial progress.  This module replaces the barrier with a
**round deadline** measured in simulated client latencies
(``repro.sim.faults.client_latencies``): a clean client finishes at t=1.0,
a 4×-slowdown straggler at t=4.0.  Per round:

* clients that finish by the ``deadline`` contribute exactly as in the
  synchronous round;
* clients past the deadline are **buffered**, not dropped — their
  post-optimizer update (Δ = θ_new − θ_old) is parked in ``AsyncState`` and
  lands ``d = ceil(latency / deadline) − 1`` rounds later, applied to the
  then-current global stage and discounted by a staleness weight
  (``wssl.staleness_weights``, FedAsync/FedBuff-style) that is fused into
  the aggregation coefficients via ``wssl.safe_aggregation_weights``;
* updates whose staleness would reach ``max_staleness`` (and updates that
  would overflow ``buffer_size``) are **evicted**: the client contributes
  exactly zero and is resynced, accounted as ``bytes_sync``.

Everything is jit-safe over the fixed client axis: the deadline,
``max_staleness``, ``buffer_size``, and the staleness-decay ``alpha`` reach
the traced round only as dynamic fp32 scalars (:class:`AsyncParams`), so
one compiled executable serves every same-shape latency / deadline /
staleness configuration — the same one-executable invariant as the fault
system (PR 1) and the multi-hop pipeline (PR 2).

Every async op is an exact identity at ``deadline = inf`` (multiplication
by an all-ones on-time mask, ``jnp.where`` on all-false buffer masks, +0
contributions), so the async-off round is **bit-for-bit identical** to
``wssl_round`` — golden-tested in ``tests/test_round_regression.py``
against ``tests/golden/round_async_off.npz``.  With a *finite* deadline the
latency signal is reinterpreted: slow clients arrive late instead of
contributing a scaled update (the straggler partial-progress scale is
neutralized under ``jnp.where(isinf(deadline), ...)``); Byzantine
amplification (``byz_scale``) still applies to whatever they send.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import (AsyncRoundsConfig, ModelConfig, TrainConfig,
                          WSSLConfig)
from repro import compress as compress_mod
from repro.core import aggregation, wssl
from repro.core.protocol import hierarchical_sync_bytes, sync_round_bytes
from repro.core.round import (RoundMetrics, ShardCtx, WSSLState,
                              _client_stage_bytes, _gather, _loc,
                              _local_plan, _opt_kwargs, _split_grads,
                              _validate)
from repro.optim import clip_by_global_norm, make_optimizer
from repro.sim import faults as sim_faults
from repro.sharding import shard_activation

Params = Any


class AsyncParams(NamedTuple):
    """Dynamic (traced) scalars of an AsyncRoundsConfig — the jit input.

    Passing these as arguments (instead of closing over the config) keeps
    every same-shape deadline / staleness setting on ONE compiled
    executable; only the ``staleness_weighting`` *kind* is a static branch
    (closed over by ``make_async_round_fn``)."""

    deadline: jax.Array        # round deadline in client-latency units; inf = sync
    max_staleness: jax.Array   # staleness bound (evict + resync at/above it)
    buffer_size: jax.Array     # max concurrently buffered late updates
    staleness_alpha: jax.Array # decay rate of the staleness weighting


def async_params(cfg: AsyncRoundsConfig, num_clients: int) -> AsyncParams:
    """Lower the config block to dynamic fp32 scalars."""
    f = lambda v: jnp.asarray(v, jnp.float32)
    size = num_clients if cfg.buffer_size is None else cfg.buffer_size
    return AsyncParams(
        deadline=f(cfg.deadline),
        max_staleness=f(cfg.max_staleness),
        buffer_size=f(size),
        staleness_alpha=f(cfg.staleness_alpha),
    )


class AsyncState(NamedTuple):
    """Per-client staleness bookkeeping + the stale-update buffer.

    ``pending[i] == 0``  — idle (eligible for fresh work);
    ``pending[i] == k>0`` — a buffered update lands k rounds from now
    (``k == 1`` means it arrives *this* round and the slot frees after).
    ``staleness[i]`` is the age the buffered update will have at arrival
    (constant while parked — it equals the admission delay d).
    ``buffer`` mirrors the stacked client stage and holds the parked
    post-optimizer deltas; slots are zero whenever ``pending == 0``."""

    pending: jax.Array      # (N,) int32
    staleness: jax.Array    # (N,) int32
    buffer: Params          # client-stack-shaped deltas, leaves (N, ...)


class AsyncRoundMetrics(NamedTuple):
    base: RoundMetrics          # the synchronous metrics (mask = fresh work)
    on_time: jax.Array          # fresh clients that beat the deadline
    buffered: jax.Array         # late clients newly admitted to the buffer
    arrived: jax.Array          # stale updates applied this round
    evicted: jax.Array          # too-stale / overflow clients (resynced)
    mean_staleness: jax.Array   # mean staleness of this round's arrivals
    bytes_resync: jax.Array     # eviction resync traffic (inside bytes_sync)


def init_async_state(state: WSSLState) -> AsyncState:
    """Empty buffer: every client idle, every slot zero."""
    n = jax.tree.leaves(state.client_stack)[0].shape[0]
    return AsyncState(
        pending=jnp.zeros((n,), jnp.int32),
        staleness=jnp.zeros((n,), jnp.int32),
        buffer=jax.tree.map(jnp.zeros_like, state.client_stack),
    )


def _pc(vec: jax.Array, ref: jax.Array) -> jax.Array:
    """Broadcast a (N,) vector against a (N, ...) leaf."""
    return vec.reshape((-1,) + (1,) * (ref.ndim - 1))


def async_wssl_round(state: WSSLState, astate: AsyncState,
                     batch: Dict[str, jax.Array],
                     val_batch: Optional[Dict[str, jax.Array]] = None,
                     scenario: Optional["sim_faults.ScenarioParams"] = None,
                     async_p: Optional[AsyncParams] = None,
                     agg_p: Optional["aggregation.AggParams"] = None,
                     comp_p: Optional["compress_mod.CompressionParams"] = None,
                     *,
                     model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
                     train_cfg: TrainConfig, schedule,
                     impl: str = "chunked",
                     shard_ctx: Optional[ShardCtx] = None
                     ) -> Tuple[WSSLState, AsyncState, AsyncRoundMetrics]:
    """One bounded-staleness communication round.

    Mirrors ``wssl_round`` op-for-op (same batch/val contract, same fault
    composition, same RNG streams — the async logic consumes no
    randomness), inserting the deadline/buffer machinery as exact
    identities at ``deadline = inf``.  Returns the new
    ``(WSSLState, AsyncState)`` plus :class:`AsyncRoundMetrics`.

    shard_ctx follows the same contract as ``wssl_round``: None is the
    flat golden trace; under ``make_sharded_async_round_fn`` the stacked
    leaves (client stack, optimizer slots, EF residuals, stale-update
    buffer) arrive sliced to (N/S, ...) while ``AsyncState.pending`` /
    ``staleness`` and every admission-control vector stay full and
    replicated, so the deadline/buffer bookkeeping is bit-identical to
    flat on every shard."""
    ctx = shard_ctx
    n = wssl_cfg.num_clients
    n_loc = n // ctx.num_shards if ctx is not None else n
    num_edges = len(state.edge_stages)
    kind = wssl_cfg.async_rounds.staleness_weighting
    if async_p is None:
        async_p = async_params(wssl_cfg.async_rounds, n)
    rng, rng_sel = jax.random.split(state.rng)
    comp_cfg = wssl_cfg.compression
    if comp_cfg.enabled and comp_p is None:
        comp_p = compress_mod.compression_params(comp_cfg)
    compress_acts = comp_cfg.enabled and comp_cfg.activations

    # ---- fault injection (repro.sim): sampled first so the latency
    # signal can reach the selection draw (fold_in keeps the Gumbel draw
    # untouched) ----------------------------------------------------------
    plan = None
    if scenario is not None:
        plan = sim_faults.sample_fault_plan(
            jax.random.fold_in(rng_sel, 0x0DD), scenario, n,
            num_hops=num_edges, hop_replicas=wssl_cfg.hop_replicas)

    # ---- Algorithm 1: selection.  select_staleness_beta > 0 folds a
    # busy/slow penalty into the Gumbel-top-k logits — in-flight clients
    # (pending rounds) and high-latency clients lose priority at the draw
    # instead of being masked after it. ----------------------------------
    penalty = None
    if wssl_cfg.select_staleness_beta:
        penalty = (sim_faults.client_latencies(plan, n) - 1.0
                   + astate.pending.astype(jnp.float32))
    idx = wssl.weighted_sample(rng_sel, state.importance,
                               wssl_cfg.num_selected(), penalty=penalty,
                               beta=wssl_cfg.select_staleness_beta)
    mask = wssl.participation_mask(rng_sel, state.importance, wssl_cfg,
                                   state.round_index, idx=idx)

    # dropout ⇒ zero-mask
    if plan is not None:
        mask = mask * plan.keep

    # ---- deadline admission control -------------------------------------
    # latency → rounds of delay before the update can land (0 = on time);
    # at deadline = inf every delay is exactly 0 and all of this is the
    # synchronous round, bit-for-bit.
    lat = sim_faults.client_latencies(plan, n)
    delay = jnp.maximum(jnp.ceil(lat / async_p.deadline) - 1.0, 0.0)
    arriving = (astate.pending == 1).astype(jnp.float32)
    idle = (astate.pending == 0).astype(jnp.float32)
    mask = mask * idle                    # busy clients take no fresh work
    on_time = mask * (delay == 0)
    late = mask * (delay > 0)
    # too stale to ever matter: evict at admission (w(s)=0 at s>=max)
    evict_late = late * (delay >= async_p.max_staleness)
    admit = late - evict_late
    # bounded buffer: arrivals free their slot as the round begins
    slots = (astate.pending > 1).sum().astype(jnp.float32)
    order = jnp.cumsum(admit) - admit     # admitted strictly before i
    overflow = admit * ((slots + order) >= async_p.buffer_size)
    admit = admit - overflow
    evicted = evict_late + overflow
    part = on_time + admit                # fresh work this round

    agg_w = wssl.aggregation_weights(state.importance, part, wssl_cfg)

    # local views (identity when flat): the admission-control vectors above
    # are all full + replicated — computed from the replicated rng/latency
    # signal, so every shard agrees bit-for-bit on who is on time, admitted,
    # or evicted; the per-client tensor work below touches local rows only
    plan_loc = _local_plan(plan, ctx, n_loc)
    part_loc = _loc(part, ctx, n_loc)
    agg_w_loc = _loc(agg_w, ctx, n_loc)
    arriving_loc = _loc(arriving, ctx, n_loc)
    admit_loc = _loc(admit, ctx, n_loc)
    pending_loc = _loc(astate.pending, ctx, n_loc)

    tokens = shard_activation(batch["tokens"], "client", None, None)
    labels = shard_activation(batch["labels"], "client", None, None)
    if plan is not None:
        labels = sim_faults.corrupt_labels(plan_loc, labels,
                                           model_cfg.vocab_size)
    embeds = batch.get("embeds")

    # ---- split fwd / chained N-phase backward (shared with wssl_round):
    # the async round's CE weight is agg_w·part instead of agg_w·mask ----
    (loss, pcl, g_client, g_server, g_edges, hop_bytes, act_wire_bytes,
     work_rows) = _split_grads(
        state, tokens, labels, embeds, agg_w_loc * part_loc, idx,
        model_cfg=model_cfg, wssl_cfg=wssl_cfg, train_cfg=train_cfg,
        impl=impl, n=n, n_loc=n_loc, ctx=ctx, comp_cfg=comp_cfg,
        comp_p=comp_p, compress_acts=compress_acts, rng_sel=rng_sel)

    if train_cfg.grad_clip:
        g_client, _ = clip_by_global_norm(
            g_client, train_cfg.grad_clip,
            axis_name=ctx.axis if ctx is not None else None)
        g_server, _ = clip_by_global_norm(g_server, train_cfg.grad_clip)
        g_edges = [clip_by_global_norm(g, train_cfg.grad_clip)[0]
                   for g in g_edges]

    if plan is not None:
        g_client = sim_faults.corrupt_client_grads(
            plan_loc, g_client,
            jax.random.fold_in(rng_sel, 0xBAD) if ctx is None
            else jax.random.fold_in(jax.random.fold_in(rng_sel, 0xBAD),
                                    ctx.index))

    # ---- optimizer (masked to this round's fresh workers) ---------------
    _, opt_update = make_optimizer(train_cfg.optimizer)
    okw = _opt_kwargs(train_cfg)
    lr = schedule(state.round_index)
    new_cstack, new_opt_c = opt_update(
        state.client_stack, g_client, state.opt_client, lr=lr,
        weight_decay=train_cfg.weight_decay, mask=part_loc, **okw)
    new_server, new_opt_s = opt_update(
        state.server_params, g_server, state.opt_server, lr=lr,
        weight_decay=train_cfg.weight_decay, **okw)
    new_edges, new_opt_e = [], []
    for ep, ge, oe in zip(state.edge_stages, g_edges, state.opt_edge):
        ne, no = opt_update(ep, ge, oe, lr=lr,
                            weight_decay=train_cfg.weight_decay, **okw)
        new_edges.append(ne)
        new_opt_e.append(no)
    if plan is not None:
        # with a finite deadline the latency signal is modeled as *when*
        # the update lands, not how much of it — neutralize the straggler
        # partial-progress scale (Byzantine amplification still applies)
        eff_scale = jnp.where(jnp.isinf(async_p.deadline),
                              plan_loc.grad_scale,
                              jnp.ones_like(plan_loc.grad_scale))
        new_cstack = sim_faults.scale_client_updates(
            plan_loc._replace(grad_scale=eff_scale), new_cstack,
            state.client_stack)
        # adaptive adversaries craft mean(honest) − z·std(honest) from this
        # round's fresh workers (exact identity when no client is adaptive)
        new_cstack = sim_faults.adaptive_scale_updates(
            plan_loc, new_cstack, state.client_stack, part_loc,
            axis_name=ctx.axis if ctx is not None else None)
    # a round in which every client missed the deadline (or dropped) must
    # leave the shared stages untouched — no CE signal, and the aux term +
    # weight decay must not step them.  Unlike the sync round this guard is
    # unconditional: a tight deadline can empty the round without any
    # fault plan, and at deadline=inf the where() is an exact identity.
    alive = part.sum() > 0
    keep_old = lambda new, old: jax.tree.map(
        lambda a, b: jnp.where(alive, a, b), new, old)
    new_server = keep_old(new_server, state.server_params)
    new_opt_s = keep_old(new_opt_s, state.opt_server)
    new_edges = tuple(keep_old(ne, oe)
                      for ne, oe in zip(new_edges, state.edge_stages))
    new_opt_e = tuple(keep_old(no, oo)
                      for no, oo in zip(new_opt_e, state.opt_edge))

    # ---- validation on the server-held ζ → importance ------------------
    val_losses, val_rows = _validate(
        new_cstack, new_edges, new_server, val_batch, idx, state.round_index,
        model_cfg=model_cfg, train_cfg=train_cfg, impl=impl, n=n, ctx=ctx,
        faults=plan is not None)
    importance = (state.importance if val_batch is None else
                  wssl.compute_importance(val_losses, wssl_cfg,
                                          prev=state.importance))

    # ---- stale-update delivery + weighted aggregation --------------------
    # an arriving client applies its parked delta to the *current* global
    # stage (classic stale-gradient application); its coefficient carries
    # the staleness discount, fused into the aggregation weights
    contrib = wssl.async_contribution(on_time, arriving, astate.staleness,
                                      async_p.max_staleness, kind=kind,
                                      alpha=async_p.staleness_alpha)
    contrib_loc = _loc(contrib, ctx, n_loc)

    def _deliver(new, old, buf):
        arr = _pc(arriving_loc, new) > 0
        stale = (old.astype(jnp.float32)
                 + buf.astype(jnp.float32)).astype(new.dtype)
        return jnp.where(arr, stale, new)

    agg_stack = jax.tree.map(_deliver, new_cstack, state.client_stack,
                             astate.buffer)

    # ---- update-path compression (repro.compress) -----------------------
    # compression happens at *delivery*: a stale client's parked raw delta
    # is compressed the round it lands, so the wire carries compressed
    # bytes for fresh and stale uploads alike and the staleness discount
    # (already fused into `contrib`) composes with the reconstruction.
    # scheme="none" traces no op — the async-off golden stays bit-for-bit.
    ef_residual = state.ef_residual
    if comp_cfg.enabled:
        delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                             - b.astype(jnp.float32),
                             agg_stack, state.client_stack)
        rng_comp = jax.random.fold_in(rng_sel, 0xC09)
        if ctx is not None:
            rng_comp = jax.random.fold_in(rng_comp, ctx.index)
        sent, ef_residual = compress_mod.apply_compression(
            delta, ef_residual, contrib_loc, rng_comp, comp_cfg, comp_p)
        agg_stack = jax.tree.map(
            lambda old, s: (old.astype(jnp.float32) + s).astype(old.dtype),
            state.client_stack, sent)

    # registry dispatch (core/aggregation.py): weighted rules fuse the
    # fractional staleness discount into their coefficients; robust rules
    # (trimmed_mean/median/krum/...) binarize membership internally — a
    # stale vote counts fully or not at all, never at a fraction
    if ctx is None:
        global_client = aggregation.aggregate_clients(
            agg_stack, importance, contrib, wssl_cfg, safe=True,
            params=agg_p)
    else:
        global_client = aggregation.shard_aggregate_clients(
            agg_stack, importance, contrib, wssl_cfg, axis_name=ctx.axis,
            shard_index=ctx.index, num_shards=ctx.num_shards, safe=True,
            params=agg_p)
    presync_cstack = new_cstack     # the round's actual local updates
    new_cstack = wssl.broadcast_global(new_cstack, global_client)

    # ---- buffer / counter update ----------------------------------------
    # parked deltas are measured on the *pre-sync* stacks — the local
    # update the late client actually computed, before broadcast_global
    # reset every stack to the aggregated global
    def _park(new, old, buf):
        delta = (new.astype(jnp.float32)
                 - old.astype(jnp.float32)).astype(buf.dtype)
        keep = _pc((pending_loc > 1).astype(jnp.float32), buf) > 0
        parked = jnp.where(keep, buf, jnp.zeros_like(buf))
        return jnp.where(_pc(admit_loc, buf) > 0, delta, parked)

    new_buffer = jax.tree.map(_park, presync_cstack, state.client_stack,
                              astate.buffer)
    d_i32 = delay.astype(jnp.int32)
    new_pending = jnp.where(admit > 0, d_i32,
                            jnp.maximum(astate.pending - 1, 0))
    new_staleness = jnp.where(admit > 0, d_i32,
                              jnp.where(astate.pending > 1,
                                        astate.staleness, 0))

    # ---- communication accounting --------------------------------------
    sel = part.sum()
    n_arrived = arriving.sum()
    n_evicted = evicted.sum()
    bytes_per_hop = sel * jnp.asarray(hop_bytes, jnp.float32)
    stage_bytes = jnp.asarray(_client_stage_bytes(state.client_stack, n),
                              jnp.float32)
    bytes_resync = n_evicted * stage_bytes
    uploads = on_time.sum() + n_arrived
    update_raw = uploads * stage_bytes
    if comp_cfg.enabled:
        comp_stage = compress_mod.compressed_stage_bytes(
            state.client_stack, n, comp_cfg, comp_p)
        update_comp = uploads * comp_stage
        bytes_sync = (uploads * comp_stage + n * stage_bytes + bytes_resync)
    else:
        update_comp = update_raw
        bytes_sync = sync_round_bytes(uploads, n, stage_bytes) + bytes_resync
    if ctx is not None:
        cross, intra = hierarchical_sync_bytes(
            uploads, n, ctx.num_shards, stage_bytes,
            aggregation.rule_decomposes(wssl_cfg))
    else:
        cross = intra = jnp.zeros((), jnp.float32)
    if compress_acts:
        act_raw = sel * 2.0 * jnp.asarray(hop_bytes, jnp.float32).sum()
        act_comp = sel * 2.0 * sum(act_wire_bytes)
    else:
        act_raw = act_comp = jnp.zeros((), jnp.float32)
    metrics = RoundMetrics(
        loss=loss, per_client_loss=_gather(pcl, ctx) * part,
        val_loss=val_losses,
        mask=part, importance=importance,
        bytes_up=bytes_per_hop.sum(), bytes_down=bytes_per_hop.sum(),
        bytes_per_hop=bytes_per_hop,
        bytes_sync=bytes_sync,
        bytes_update_raw=update_raw,
        bytes_update_comp=update_comp,
        bytes_cross_shard=cross, bytes_intra_shard=intra,
        bytes_act_raw=act_raw, bytes_act_comp=act_comp,
        work_rows=work_rows, val_rows=val_rows,
    )
    amet = AsyncRoundMetrics(
        base=metrics,
        on_time=on_time.sum(),
        buffered=admit.sum(),
        arrived=n_arrived,
        evicted=n_evicted,
        mean_staleness=((arriving * astate.staleness).sum()
                        / jnp.maximum(n_arrived, 1.0)),
        bytes_resync=bytes_resync,
    )
    new_state = WSSLState(
        client_stack=new_cstack, server_params=new_server,
        edge_stages=new_edges, opt_client=new_opt_c, opt_server=new_opt_s,
        opt_edge=new_opt_e, importance=importance,
        round_index=state.round_index + 1, rng=rng,
        ef_residual=ef_residual)
    new_astate = AsyncState(pending=new_pending, staleness=new_staleness,
                            buffer=new_buffer)
    return new_state, new_astate, amet


def make_async_round_fn(model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
                        train_cfg: TrainConfig, impl: str = "chunked", *,
                        donate: bool = False):
    """jit-ready async round with static configs closed over.

    The returned function takes ``(state, astate, batch, val_batch,
    scenario_params, async_params, agg_params, comp_params)`` — all four
    params pytrees are dynamic, so one compiled executable serves every
    same-shape latency scenario, every deadline / staleness bound, every
    aggregation trim/f/m setting, and every compression rate / bit
    width of a scheme kind.

    ``donate=False`` returns the legacy un-jitted partial;
    ``donate=True`` returns the jitted round with BOTH the incoming
    :class:`WSSLState` and :class:`AsyncState` donated
    (``donate_argnums=(0, 1)``) — params, optimizer slots, EF residuals
    and the stale-update buffer all alias their outputs.  Same
    nested-jit caveat as ``make_round_fn``: never re-wrap the donating
    fn in ``jax.jit``."""
    from repro.optim.schedule import make_schedule
    schedule = make_schedule(train_cfg.schedule, train_cfg.learning_rate,
                             train_cfg.warmup_steps, train_cfg.rounds)
    fn = functools.partial(async_wssl_round, model_cfg=model_cfg,
                           wssl_cfg=wssl_cfg, train_cfg=train_cfg,
                           schedule=schedule, impl=impl)
    if not donate:
        return fn
    jitted = jax.jit(fn, donate_argnums=(0, 1))

    def round_fn(state, astate, batch, val_batch=None, scenario=None,
                 async_p=None, agg_p=None, comp_p=None):
        return jitted(state, astate, batch, val_batch, scenario, async_p,
                      agg_p, comp_p)

    round_fn.cache_size = lambda: jitted._cache_size()
    round_fn._jitted = jitted
    return round_fn


def make_sharded_async_round_fn(model_cfg: ModelConfig,
                                wssl_cfg: WSSLConfig,
                                train_cfg: TrainConfig, mesh, *,
                                impl: str = "chunked",
                                donate: bool = True):
    """Client-axis scale-out of :func:`async_wssl_round` — the async twin
    of ``core.round.make_sharded_round_fn`` (same mesh contract, same
    spec rules, same psum/all_gather crossings).  The stale-update buffer
    shards with the client stack; ``pending``/``staleness`` stay
    replicated so admission control is bit-identical on every shard.

    Returns ``round_fn(state, astate, batch, val_batch=None,
    scenario=None, async_p=None, agg_p=None, comp_p=None)`` with the same
    ``cache_size()`` / ``num_shards`` / ``mesh`` attributes.  Because the
    deadline is a traced scalar in ``AsyncParams``, a host-side
    :class:`DeadlineController` can retune it every round without
    recompiling."""
    from contextlib import nullcontext
    from jax.sharding import PartitionSpec
    from repro import sharding as shardlib
    from repro.core.round import _linear_shard_index, abstract_state
    from repro.optim.schedule import make_schedule

    dp = shardlib.data_axes_of(mesh)
    if not dp:
        raise ValueError("make_sharded_async_round_fn: mesh has no data "
                         f"axis (axes: {mesh.axis_names})")
    num_shards = 1
    for a in dp:
        num_shards *= mesh.shape[a]
    n = wssl_cfg.num_clients
    if n % num_shards != 0:
        raise ValueError(
            f"num_clients={n} must divide evenly over {num_shards} client "
            f"shards (mesh data axes {dp})")
    axis = dp if len(dp) > 1 else dp[0]
    auto = shardlib.auto_axes_of(mesh)
    arules = shardlib.auto_rules(mesh) if auto else {}
    schedule = make_schedule(train_cfg.schedule, train_cfg.learning_rate,
                             train_cfg.warmup_steps, train_cfg.rounds)
    _, state_axes = abstract_state(model_cfg, wssl_cfg, train_cfg)
    st_specs = shardlib.round_state_specs(mesh, state_axes)
    client_spec = shardlib.client_axis_spec(mesh)
    rep = PartitionSpec()
    # buffer leaves shard with the stack; the (N,) counters replicate
    astate_specs = AsyncState(pending=rep, staleness=rep,
                              buffer=client_spec)

    def body(state, astate, batch, val_batch, scenario, async_p, agg_p,
             comp_p):
        ctx = ShardCtx(axis=axis, num_shards=num_shards,
                       index=_linear_shard_index(dp, mesh))
        bind = (shardlib.use_sharding_rules(mesh, arules) if arules
                else nullcontext())
        with bind:
            return async_wssl_round(
                state, astate, batch, val_batch, scenario, async_p, agg_p,
                comp_p, model_cfg=model_cfg, wssl_cfg=wssl_cfg,
                train_cfg=train_cfg, schedule=schedule, impl=impl,
                shard_ctx=ctx)

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(st_specs, astate_specs, client_spec, rep, rep, rep, rep,
                  rep),
        out_specs=(st_specs, astate_specs, rep),
        check_vma=False, axis_names=frozenset(dp))
    # donate state + astate (default on): the sharded stacks, optimizer
    # slots and the stale-update buffer alias their outputs — one copy
    # live at peak.  place_state/place_astate device_put copies, so
    # host-built inputs survive the first donated call.
    jitted = jax.jit(mapped, donate_argnums=(0, 1) if donate else ())

    def round_fn(state, astate, batch, val_batch=None, scenario=None,
                 async_p=None, agg_p=None, comp_p=None):
        return jitted(state, astate, batch, val_batch, scenario, async_p,
                      agg_p, comp_p)

    round_fn.place_state = lambda state: jax.device_put(
        state, shardlib.named_shardings_like(mesh, st_specs, state))
    round_fn.place_astate = lambda astate: jax.device_put(
        astate, shardlib.named_shardings_like(mesh, astate_specs, astate))
    round_fn.place_batch = lambda batch: jax.device_put(
        batch, shardlib.named_shardings_like(mesh, client_spec, batch))
    round_fn.mesh = mesh
    round_fn.num_shards = num_shards
    round_fn.cache_size = lambda: jitted._cache_size()
    round_fn._jitted = jitted
    return round_fn


class DeadlineController:
    """Host-side adaptive round deadline → a target mean-staleness budget.

    Multiplicative-exponential control on the observed per-round mean
    staleness of arriving stale updates (``AsyncRoundMetrics.
    mean_staleness``):

        deadline ← clip(deadline · exp(gain · (staleness − target)),
                        min_deadline, max_deadline)

    A *larger* deadline admits more clients on time, so staleness above
    budget raises the deadline and staleness below budget tightens it —
    trading round wall-clock (the deadline is the round's simulated
    duration) against staleness-discounted contribution quality.  Rounds
    with no arrivals carry no staleness observation and leave the
    deadline unchanged.

    The deadline reaches the executable only as the traced
    ``AsyncParams.deadline`` scalar, so retuning every round costs zero
    recompiles — the knob the one-executable invariant exists for.  Used
    by the scale sweep (``benchmarks/robustness.py --staleness-target``)."""

    def __init__(self, target_staleness: float, deadline: float = 1.0,
                 gain: float = 0.25, min_deadline: float = 0.25,
                 max_deadline: float = 64.0):
        if target_staleness < 0:
            raise ValueError("target_staleness must be >= 0")
        if not 0 < min_deadline <= max_deadline:
            raise ValueError("need 0 < min_deadline <= max_deadline")
        self.target = float(target_staleness)
        self.gain = float(gain)
        self.min_deadline = float(min_deadline)
        self.max_deadline = float(max_deadline)
        self.deadline = float(min(max(deadline, min_deadline),
                                  max_deadline))

    def update(self, mean_staleness, arrived=1) -> float:
        """Observe one round; returns the deadline for the next round."""
        if float(arrived) > 0:
            err = float(mean_staleness) - self.target
            self.deadline = min(self.max_deadline,
                                max(self.min_deadline,
                                    self.deadline * math.exp(
                                        self.gain * err)))
        return self.deadline

    def params(self, cfg: AsyncRoundsConfig,
               num_clients: int) -> AsyncParams:
        """Current-deadline AsyncParams (other scalars from ``cfg``)."""
        return async_params(cfg, num_clients)._replace(
            deadline=jnp.asarray(self.deadline, jnp.float32))
