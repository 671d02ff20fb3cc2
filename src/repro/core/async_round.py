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

The round is the synchronous round's body (``core/round.py::_round``)
with three steps added — admission, delivery and parking — which that
body traces only when it is given an ``AsyncState``.  Every async op is an
exact identity at ``deadline = inf`` (multiplication by an all-ones
on-time mask, ``jnp.where`` on all-false buffer masks, +0 contributions),
so the async-off round is **bit-for-bit identical** to ``wssl_round`` —
golden-tested in ``tests/test_round_regression.py`` against
``tests/golden/round_async_off.npz``.  With a *finite* deadline the
latency signal is reinterpreted: slow clients arrive late instead of
contributing a scaled update (the straggler partial-progress scale is
neutralized under ``jnp.where(isinf(deadline), ...)``); Byzantine
amplification (``byz_scale``) still applies to whatever they send.
"""

from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import (AsyncRoundsConfig, ModelConfig, TrainConfig,
                          WSSLConfig)
from repro import compress as compress_mod
from repro.core import aggregation
from repro.core.round import (RoundMetrics, ShardCtx, WSSLState,
                              _make_round_fn, _make_sharded_round_fn, _round)
from repro.sim import faults as sim_faults

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
    """One bounded-staleness communication round -> the new ``(WSSLState,
    AsyncState)`` and :class:`AsyncRoundMetrics`.

    The synchronous round's body (``core/round.py::_round``: same
    batch/val contract, same fault composition, same RNG streams — the
    async logic consumes no randomness) with deadline admission,
    stale-update delivery and parking added.  ``shard_ctx`` as in
    ``wssl_round``: under ``make_sharded_async_round_fn`` the stale-update
    buffer arrives sliced to (N/S, ...) with the client stack, while
    ``pending``/``staleness`` and every admission vector stay full and
    replicated, so the bookkeeping is bit-identical to flat on every
    shard."""
    if async_p is None:
        async_p = async_params(wssl_cfg.async_rounds, wssl_cfg.num_clients)
    new_state, metrics, (pending, staleness, buffer, fields) = _round(
        state, batch, val_batch, scenario, agg_p, comp_p,
        model_cfg=model_cfg, wssl_cfg=wssl_cfg, train_cfg=train_cfg,
        schedule=schedule, impl=impl, shard_ctx=shard_ctx,
        stale=(astate, async_p))
    return (new_state, AsyncState(pending, staleness, buffer),
            AsyncRoundMetrics(base=metrics, **fields))


def make_async_round_fn(model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
                        train_cfg: TrainConfig, impl: str = "chunked", *,
                        donate: bool = False):
    """jit-ready async round with static configs closed over, as
    ``core.round.make_round_fn`` (the same ``donate`` contract and
    nested-jit caveat).  The returned function takes ``(state, astate,
    batch, val_batch, scenario_params, async_params, agg_params,
    comp_params)``; all four params pytrees are dynamic, so one compiled
    executable serves every same-shape latency scenario, deadline /
    staleness bound, aggregation setting and compression level.
    ``donate=True`` donates BOTH the incoming :class:`WSSLState` and
    :class:`AsyncState` (``donate_argnums=(0, 1)``): the stale-update
    buffer aliases its output too."""
    return _make_round_fn(async_wssl_round, (0, 1), model_cfg, wssl_cfg,
                          train_cfg, impl, donate)


def make_sharded_async_round_fn(model_cfg: ModelConfig,
                                wssl_cfg: WSSLConfig,
                                train_cfg: TrainConfig, mesh, *,
                                impl: str = "chunked",
                                donate: bool = True):
    """Client-axis scale-out of :func:`async_wssl_round`, as
    ``core.round.make_sharded_round_fn`` (same mesh contract, specs,
    crossings and attributes, plus ``place_astate``).  Returns
    ``round_fn(state, astate, batch, val_batch=None, scenario=None,
    async_p=None, agg_p=None, comp_p=None)``.  The stale-update buffer
    shards with the client stack; ``pending``/``staleness`` stay
    replicated so admission control is bit-identical on every shard.
    Because the deadline is a traced scalar in ``AsyncParams``, a
    host-side :class:`DeadlineController` can retune it every round
    without recompiling."""
    from jax.sharding import PartitionSpec
    from repro import sharding as shardlib

    rep = PartitionSpec()
    # buffer leaves shard with the stack; the (N,) counters replicate
    astate_specs = AsyncState(pending=rep, staleness=rep,
                              buffer=shardlib.client_axis_spec(mesh))
    round_fn = _make_sharded_round_fn(
        async_wssl_round, "make_sharded_async_round_fn", model_cfg, wssl_cfg,
        train_cfg, mesh, impl, donate, carried=(astate_specs,))
    round_fn.place_astate = lambda astate: jax.device_put(
        astate, shardlib.named_shardings_like(mesh, astate_specs, astate))
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
