"""One fused WSSL communication round for the transformer stack.

All of Algorithm 1 + Algorithm 2 as one jit-able body over a fixed client
axis (``_round``, which the bounded-staleness round of ``async_round.py``
shares and adds its own steps to), generalized to an N-stage split
pipeline:

  importance → Gumbel-top-k selection mask → per-client split forward /
  chained N-phase backward (client stages vmapped over the stacked client
  axis, edge + server stages shared) → masked optimizer step → per-client
  validation → importance EMA update → weighted aggregation (+ optional
  client sync).

The pipeline is ``client → edge₀ → … → edge_{H-1} → server``: stage 0 is
replicated per client (leaves carry a leading (N, ...) axis), intermediate
(edge) stages and the server stage are shared single copies that every
client's activation flows through.  A length-1 cut tuple
(``WSSLConfig.resolve_cuts``) has no edge stages and reproduces the classic
two-stage protocol bit-for-bit.

Shapes stay static, so one compiled executable serves every round: k =
``WSSLConfig.num_selected()`` is static, and after round 0 (which selects
everyone) the split forward/backward runs on the k selected clients'
rows alone, gathered from the stack (``_split_grads``).  Where k rows
would change the objective or need routing — the sharded and
client-chunked rounds, activation compression, MoE models — unselected
clients are *masked* instead: they run with loss weight 0 and their exact
zero gradients are ignored by the masked optimizer (docs/scaling.md).
Validation likewise scores k+1 client stages after round 0, the k
selected and one of the unselected clients, which all hold the global
stage (``_validate``).
"""

from __future__ import annotations

import functools
import inspect
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.config import ModelConfig, TrainConfig, WSSLConfig
from repro import compress as compress_mod
from repro.core import aggregation, wssl
from repro.core.protocol import hierarchical_sync_bytes, sync_round_bytes
from repro.models import transformer as tf
from repro.sim import faults as sim_faults
from repro.optim import clip_by_global_norm, make_optimizer, make_schedule
from repro.sharding import bound_axes, current_mesh, shard_activation

Params = Any
_pc = sim_faults._per_client    # (N,) vector against a (N, ...) leaf


class ShardCtx(NamedTuple):
    """Client-axis sharding context of a shard_map-wrapped round.

    ``None`` everywhere a round runs flat — every ctx helper below then
    returns its argument unchanged (zero added ops), so the flat trace
    stays bit-for-bit the golden round.  Inside
    :func:`make_sharded_round_fn` the round runs once per shard with
    client-stacked leaves sliced to (N/S, ...) and all (N,) decision
    vectors (importance, masks, fault plans) kept full + replicated: the
    selection, fault cohorts, and importance EMA are computed identically
    on every shard from the replicated rng, bit-identical to the flat
    round, and only per-client tensor work is local."""

    axis: Any              # shard_map axis name (or tuple) of the client dim
    num_shards: int        # static S = product of the data-axis sizes
    index: jax.Array       # this shard's position, lax.axis_index-derived


def _loc(vec: Optional[jax.Array], ctx: Optional[ShardCtx],
         n_loc: int) -> Optional[jax.Array]:
    """Slice a full (N,) per-client vector to this shard's (N/S,) rows."""
    if ctx is None or vec is None:
        return vec
    return jax.lax.dynamic_slice_in_dim(vec, ctx.index * n_loc, n_loc)


def _local_plan(plan, ctx: Optional[ShardCtx], n_loc: int):
    """A FaultPlan with every (N,) field sliced to the local shard."""
    if ctx is None or plan is None:
        return plan
    return type(plan)(*[_loc(v, ctx, n_loc) for v in plan])


def _psum(x, ctx: Optional[ShardCtx]):
    """Cross-shard sum (identity when flat) — works on pytrees."""
    if ctx is None:
        return x
    return jax.lax.psum(x, ctx.axis)


def _gather(vec: jax.Array, ctx: Optional[ShardCtx]) -> jax.Array:
    """Concatenate a per-shard (N/S, ...) array back to full (N, ...)."""
    if ctx is None:
        return vec
    return jax.lax.all_gather(vec, ctx.axis, axis=0, tiled=True)


class WSSLState(NamedTuple):
    client_stack: Params          # client stages, leaves (N, ...)
    server_params: Params
    edge_stages: Tuple[Params, ...]   # shared intermediate hops (may be ())
    opt_client: Any
    opt_server: Any
    opt_edge: Tuple[Any, ...]
    importance: jax.Array         # (N,) normalized
    round_index: jax.Array        # int32
    rng: jax.Array
    # per-client error-feedback residuals (repro.compress) — the empty
    # tuple (zero pytree leaves) whenever compression/EF is off, so the
    # golden leaf-count regression holds and scheme="none" stays
    # bit-for-bit identical to the pre-compression round
    ef_residual: Params = ()


class RoundMetrics(NamedTuple):
    loss: jax.Array
    per_client_loss: jax.Array    # (N,) train loss (masked clients -> 0)
    val_loss: jax.Array           # (N,) validation loss per client
    mask: jax.Array               # (N,) participation
    importance: jax.Array         # (N,) post-update weights
    bytes_up: jax.Array           # total activation bytes over all hops
    bytes_down: jax.Array         # total returned-gradient bytes
    bytes_per_hop: jax.Array      # (num_hops,) activation bytes per crossing
    bytes_sync: jax.Array         # client-stage aggregation + broadcast
    # update-path compression: raw vs wire bytes of this round's uploaded
    # client updates (equal when compression is off)
    bytes_update_raw: jax.Array = 0.0
    bytes_update_comp: jax.Array = 0.0
    # hierarchical aggregation (sharded rounds only — 0.0 when flat):
    # cross-shard combine-tree traffic vs on-shard client→edge uploads
    bytes_cross_shard: jax.Array = 0.0
    bytes_intra_shard: jax.Array = 0.0
    # activation-path compression (CompressionConfig.activations): raw vs
    # wire bytes of the per-hop crossings, both directions (0.0 when off)
    bytes_act_raw: jax.Array = 0.0
    bytes_act_comp: jax.Array = 0.0
    # client rows whose split forward and backward ran this round: k·b
    # where only the selected clients run, N·b where all are masked
    work_rows: jax.Array = 0.0
    # validation rows of the distinct clients the forward ran on: (k+1)·bv
    # where the unselected clients share one scored stage, N·bv where all
    # are scored (``_validate``)
    val_rows: jax.Array = 0.0


def init_state(rng, model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
               train_cfg: TrainConfig) -> Tuple[WSSLState, WSSLState]:
    """Initialize N client stages (identical start) + edge/server stages.

    Returns (state, state_axes) where state_axes mirrors the state with
    logical sharding-axis tuples at the leaves (client-stage leaves get a
    leading "client" axis).
    """
    cuts = wssl_cfg.resolve_cuts(model_cfg)
    params, axes = tf.init_params(rng, model_cfg)
    stages = tf.partition_params(params, model_cfg, cuts)
    stage_axes = tf.partition_axes(axes, model_cfg, cuts)
    client, server = stages[0], stages[-1]
    edge = tuple(stages[1:-1])
    client_axes, server_axes = stage_axes[0], stage_axes[-1]
    edge_axes = tuple(stage_axes[1:-1])
    n = wssl_cfg.num_clients
    client_stack = jax.tree.map(
        lambda a: jnp.broadcast_to(a[None], (n,) + a.shape).copy(), client)

    def _is_axes_leaf(a):
        return isinstance(a, tuple) and all(
            isinstance(e, (str, type(None), tuple)) for e in a)

    stacked_axes = jax.tree.map(lambda t: ("client",) + tuple(t),
                                client_axes, is_leaf=_is_axes_leaf)
    opt_init, _ = make_optimizer(train_cfg.optimizer)

    def opt_axes(p_axes):
        if train_cfg.optimizer == "adamw":
            from repro.optim.optimizers import AdamState
            return AdamState(step=(), m=p_axes, v=p_axes)
        from repro.optim.optimizers import SgdState
        return SgdState(step=(), mom=p_axes)

    comp = wssl_cfg.compression
    ef = comp.enabled and comp.error_feedback
    state = WSSLState(
        client_stack=client_stack,
        server_params=server,
        edge_stages=edge,
        opt_client=opt_init(client_stack),
        opt_server=opt_init(server),
        opt_edge=tuple(opt_init(e) for e in edge),
        importance=jnp.full((n,), 1.0 / n, jnp.float32),
        round_index=jnp.zeros((), jnp.int32),
        rng=jax.random.fold_in(rng, 1),
        ef_residual=(compress_mod.init_ef_residual(client_stack)
                     if ef else ()),
    )
    state_axes = WSSLState(
        client_stack=stacked_axes,
        server_params=server_axes,
        edge_stages=edge_axes,
        opt_client=opt_axes(stacked_axes),
        opt_server=opt_axes(server_axes),
        opt_edge=tuple(opt_axes(a) for a in edge_axes),
        importance=(None,),
        round_index=(),
        rng=(),
        ef_residual=stacked_axes if ef else (),
    )
    return state, state_axes


def abstract_state(model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
                   train_cfg: TrainConfig) -> Tuple[WSSLState, WSSLState]:
    """(ShapeDtypeStruct state, state axes) without allocating anything."""
    cell = {}

    def f(r):
        st, ax = init_state(r, model_cfg, wssl_cfg, train_cfg)
        cell["axes"] = ax
        return st

    shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
    return shapes, cell["axes"]


def _client_spmd_axes():
    """spmd_axis_name for client-axis vmaps: binds the vmapped (client) dim
    to the data-parallel mesh axes so sharding constraints *inside* the
    per-client computation keep the client dim sharded instead of letting
    SPMD propagation replicate it (decisive for MoE dispatch buffers).

    Consults the bound *rules* (not the raw mesh shape): inside a
    client-sharded shard_map body the data axes are manual — the
    ``sharding.auto_rules`` binding there deliberately drops the "client"
    rule, so the vmap stays plain."""
    mesh = current_mesh()
    if mesh is None:
        return None
    phys, _ = bound_axes("client")
    if phys is None:
        return None
    flat = phys if isinstance(phys, tuple) else (phys,)
    axes = tuple(a for a in flat if a in mesh.shape)
    if not axes:
        return None
    return axes[0] if len(axes) == 1 else axes


def _client_vmap(fn, in_axes=0):
    spmd = _client_spmd_axes()
    if spmd is None:
        return jax.vmap(fn, in_axes=in_axes)
    return jax.vmap(fn, in_axes=in_axes, spmd_axis_name=spmd)


def _per_client_losses(cfg: ModelConfig, server_params: Params,
                       acts: jax.Array, labels: jax.Array, impl: str,
                       remat: bool, remat_span: int = 1
                       ) -> Tuple[jax.Array, jax.Array]:
    """Server stage over stacked activations -> ((N,) losses, aux).

    Uses the chunked cross-entropy so the (N, b, S, V) logits tensor never
    materializes (decisive for 256k-vocab architectures)."""
    def one(a, y):
        return tf.server_loss(server_params, cfg, a, y, impl=impl,
                              remat=remat, remat_span=remat_span)

    losses, auxes = _client_vmap(one)(acts, labels)
    return losses, auxes.mean()


def _client_stage_bytes(client_stack: Params) -> int:
    """Static: bytes of ONE client's stage (the sync/aggregation payload).

    Reads the stacked-client count off the leading leaf dim, so local
    (N/S, ...) shard stacks and full (N, ...) stacks both report the same
    per-client payload."""
    return sum((l.size // l.shape[0]) * l.dtype.itemsize
               for l in jax.tree.leaves(client_stack))


def _opt_kwargs(train_cfg: TrainConfig) -> Dict[str, Any]:
    """Extra optimizer-update kwargs from the config: the fused-AdamW
    kernel dispatch (adamw-only — config-validated)."""
    if train_cfg.fused_adam and train_cfg.optimizer == "adamw":
        return {"use_kernel": True}
    return {}


def _chunked_client_map(fn, cstack, chunk: int):
    """Client-axis map in chunks: vmap ``fn`` over ``chunk`` clients per
    lax.map step instead of all at once (the validation pass's O(chunk)
    activation cap).  Leading leaf dim must divide by ``chunk``."""
    n_loc = jax.tree.leaves(cstack)[0].shape[0]
    k = n_loc // chunk
    chunks = jax.tree.map(
        lambda l: l.reshape((k, chunk) + l.shape[1:]), cstack)
    out = jax.lax.map(lambda cs: _client_vmap(fn)(cs), chunks)
    return out.reshape((n_loc,) + out.shape[2:])


def _client_grads_chunked(client_stack: Params,
                          edge_stages: Tuple[Params, ...],
                          server_params: Params, tokens, labels, embeds,
                          coef_loc: jax.Array, *, model_cfg: ModelConfig,
                          train_cfg: TrainConfig, impl: str, chunk: int,
                          n: int, n_loc: int, ctx: Optional[ShardCtx],
                          comp_cfg, comp_p, compress_acts: bool, rng_sel):
    """The per-client split fwd/bwd as a ``lax.scan`` over client chunks.

    Semantically the flat vmap with the client axis reshaped to
    ``(K, chunk)``: each scan step runs the full N-stage pipeline for
    ``chunk`` clients, accumulating the weighted loss, the per-hop MoE
    aux terms, and the shared server/edge gradients in the carry while
    stacking the per-client outputs (losses, client-stage grads).  Live
    activation memory is O(chunk) instead of O(n_loc); the stacked
    ``g_client`` output is unavoidable either way (the optimizer needs
    every client's gradient).  Differences vs the flat trace, all
    documented in docs/scaling.md:

    * shared-stage gradients and the loss re-associate the client
      reduction per chunk (fp band, same class as the sharded psum);
    * activation-compression rngs fold in the chunk index (the flat
      round draws one (N, ...) tensor per hop; per-chunk draws
      necessarily differ);
    * no per-hop ``shard_activation`` constraint inside the scan —
      chunking targets the per-shard/ single-device activation peak, the
      client-axis layout is already fixed by the surrounding shard_map.

    ``coef_loc`` is the (n_loc,) per-client CE weight (agg_w · mask).
    Returns ``(loss, pcl, g_client, g_server, g_edges, hop_bytes,
    act_wire_bytes)`` matching the flat block's outputs.
    """
    if n_loc % chunk:
        raise ValueError(
            f"client_chunk={chunk} must divide the per-shard client count "
            f"{n_loc} (num_clients"
            f"{'/num_shards' if ctx is not None else ''})")
    k = n_loc // chunk
    remat = train_cfg.remat
    span = train_cfg.remat_span
    num_edges = len(edge_stages)

    def _rechunk(a):
        return a.reshape((k, chunk) + a.shape[1:])

    xs = {"cs": jax.tree.map(_rechunk, client_stack),
          "toks": _rechunk(tokens), "labs": _rechunk(labels),
          "coef": _rechunk(coef_loc), "idx": jnp.arange(k)}
    if embeds is not None:
        xs["emb"] = _rechunk(embeds)

    # per-hop wire/byte shapes are static — recorded as the scan body
    # traces, consumed after (identical to the flat round's accounting)
    recorded: Dict[str, Any] = {}

    def body(carry, xc):
        loss_acc, aux_acc, gs_acc, ge_acc = carry
        cs, toks, labs = xc["cs"], xc["toks"], xc["labs"]
        coef, ci = xc["coef"], xc["idx"]
        emb = xc.get("emb")

        def client_fn(cstack):
            def one(cp, tks, em):
                return tf.client_forward(cp, model_cfg, tks, embeds=em,
                                         impl=impl, remat=remat,
                                         remat_span=span)
            if emb is not None:
                return _client_vmap(one)(cstack, toks, emb)
            return _client_vmap(lambda cp, t: one(cp, t, None))(cstack,
                                                                toks)

        with jax.named_scope("wssl.client"):
            acts, client_vjp = jax.vjp(client_fn, cs)
        hop_b = [acts.size // acts.shape[0] * acts.dtype.itemsize]
        wire_shapes = [(acts.size // acts.shape[0] // acts.shape[-1],
                        acts.shape[-1])]
        if compress_acts:
            acts = compress_mod.compress_activations(
                acts, jax.random.fold_in(
                    jax.random.fold_in(rng_sel, 0xAC0), ci),
                comp_cfg, comp_p)

        x, edge_vjps = acts, []
        aux_sum = jnp.zeros((), jnp.float32)
        for j in range(num_edges):
            def edge_fn(p, a, j=j):
                return _client_vmap(
                    lambda pi, ai: tf.stage_forward(
                        pi, model_cfg, ai, j + 1, impl=impl, remat=remat,
                        remat_span=span, with_aux=True),
                    in_axes=(None, 0))(p, a)
            (x, aux_j), vjp = jax.vjp(edge_fn, edge_stages[j], x)
            aux_sum = aux_sum + aux_j.sum()
            edge_vjps.append(vjp)
            hop_b.append(x.size // x.shape[0] * x.dtype.itemsize)
            wire_shapes.append((x.size // x.shape[0] // x.shape[-1],
                                x.shape[-1]))
            if compress_acts:
                x = compress_mod.compress_activations(
                    x, jax.random.fold_in(
                        jax.random.fold_in(rng_sel, 0xAC1 + j), ci),
                    comp_cfg, comp_p)

        def server_loss(sp, a):
            losses, aux = _per_client_losses(model_cfg, sp, a, labs, impl,
                                             remat, span)
            # the server MoE aux is a mean over the clients in view (here
            # one chunk); chunk/n reweights so the chunk sum completes
            # the global client mean exactly as the flat/psum paths do
            return jnp.sum(coef * losses) + aux * (chunk / n), losses

        with jax.named_scope("wssl.server"):
            (l_c, pcl_c), (gs_c, g_x) = jax.value_and_grad(
                server_loss, argnums=(0, 1), has_aux=True)(server_params, x)

        if compress_acts:
            g_x = compress_mod.compress_activations(
                g_x, jax.random.fold_in(
                    jax.random.fold_in(rng_sel, 0xDC0 + num_edges), ci),
                comp_cfg, comp_p)
        aux_ct = jnp.full((chunk,), 1.0 / n, jnp.float32)
        ge_list = []
        for back_j, vjp in enumerate(reversed(edge_vjps)):
            g_e, g_x = vjp((g_x, aux_ct))
            if compress_acts:
                g_x = compress_mod.compress_activations(
                    g_x, jax.random.fold_in(
                        jax.random.fold_in(
                            rng_sel, 0xDC0 + num_edges - 1 - back_j), ci),
                    comp_cfg, comp_p)
            ge_list.append(g_e)
        ge_list.reverse()
        with jax.named_scope("wssl.client"):
            (g_cs,) = client_vjp(g_x)

        recorded["hop_bytes"] = hop_b
        recorded["wire_shapes"] = wire_shapes
        add32 = lambda a, b: a + b.astype(jnp.float32)
        carry = (loss_acc + l_c, aux_acc + aux_sum,
                 jax.tree.map(add32, gs_acc, gs_c),
                 tuple(jax.tree.map(add32, ga, gc)
                       for ga, gc in zip(ge_acc, ge_list)))
        return carry, (pcl_c, g_cs)

    z32 = lambda p: jnp.zeros(p.shape, jnp.float32)
    carry0 = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
              jax.tree.map(z32, server_params),
              tuple(jax.tree.map(z32, e) for e in edge_stages))
    (loss_local, aux_acc, gs_acc, ge_acc), (pcl_k, gcl_k) = jax.lax.scan(
        body, carry0, xs)

    pcl = pcl_k.reshape((n_loc,) + pcl_k.shape[2:])
    g_client = jax.tree.map(
        lambda l: l.reshape((n_loc,) + l.shape[2:]), gcl_k)
    # the fp32 chunk accumulators cast back to the param dtype the flat
    # vjp would have produced, then complete the cross-shard reduction
    g_server = _psum(jax.tree.map(lambda a, p: a.astype(p.dtype),
                                  gs_acc, server_params), ctx)
    g_edges = [_psum(jax.tree.map(lambda a, p: a.astype(p.dtype), ga, ep),
                     ctx)
               for ga, ep in zip(ge_acc, edge_stages)]
    edge_aux = aux_acc / n_loc
    if ctx is not None:
        loss = jax.lax.psum(loss_local, ctx.axis)
        edge_aux = jax.lax.psum(edge_aux, ctx.axis) / ctx.num_shards
    else:
        loss = loss_local
    loss = loss + edge_aux

    act_wire_bytes = []
    if compress_acts:
        act_wire_bytes = [
            compress_mod.activation_wire_bytes(t, f, comp_cfg, comp_p)
            for t, f in recorded["wire_shapes"]]
    return (loss, pcl, g_client, g_server, g_edges,
            recorded["hop_bytes"], act_wire_bytes)


def _client_grads_flat(client_stack: Params,
                       edge_stages: Tuple[Params, ...],
                       server_params: Params, tokens, labels, embeds,
                       coef: jax.Array, *, model_cfg: ModelConfig,
                       train_cfg: TrainConfig, impl: str, n: int,
                       ctx: Optional[ShardCtx], comp_cfg, comp_p,
                       compress_acts: bool, rng_sel):
    """The per-client split fwd / chained N-phase backward over the
    stacked rows it is given, vmapped in one piece: every local client,
    or the k gathered by :func:`_split_grads`.  A row with CE weight 0
    comes back with exact zero gradients.

    ``coef`` is the per-row CE weight (agg_w · mask); ``n`` the global
    client count.  Returns ``(loss, pcl, g_client, g_server, g_edges,
    hop_bytes, act_wire_bytes)``."""
    remat = train_cfg.remat
    span = train_cfg.remat_span
    num_edges = len(edge_stages)

    def client_fn(cstack):
        def one(cp, toks, emb):
            return tf.client_forward(cp, model_cfg, toks, embeds=emb,
                                     impl=impl, remat=remat,
                                     remat_span=span)
        if embeds is not None:
            return _client_vmap(one)(cstack, tokens, embeds)
        return _client_vmap(lambda cp, t: one(cp, t, None))(cstack, tokens)

    with jax.named_scope("wssl.client"):
        acts, client_vjp = jax.vjp(client_fn, client_stack)
    acts = shard_activation(acts, "client", None, None, None)
    hop_bytes = [acts.size // acts.shape[0] * acts.dtype.itemsize]
    act_wire_bytes = []
    if compress_acts:
        acts = compress_mod.compress_activations(
            acts, jax.random.fold_in(rng_sel, 0xAC0), comp_cfg, comp_p)
        act_wire_bytes.append(compress_mod.activation_wire_bytes(
            acts.size // acts.shape[0] // acts.shape[-1],
            acts.shape[-1], comp_cfg, comp_p))

    # forward relay through the shared edge stages (per-client
    # activations, shared params: vmap over the client axis with
    # in_axes=None params).  Each edge stage also reports its MoE aux
    # loss so the objective stays invariant to where the cuts sit.
    x, edge_vjps = acts, []
    edge_aux = jnp.zeros((), jnp.float32)
    for j in range(num_edges):
        def edge_fn(p, a, j=j):
            return _client_vmap(
                lambda pi, ai: tf.stage_forward(pi, model_cfg, ai, j + 1,
                                                impl=impl, remat=remat,
                                                remat_span=span,
                                                with_aux=True),
                in_axes=(None, 0))(p, a)
        (x, aux_j), vjp = jax.vjp(edge_fn, edge_stages[j], x)
        x = shard_activation(x, "client", None, None, None)
        # aux_j.mean() is the mean over the clients in view; with a ctx
        # that view is local, so psum/S completes the global mean exactly
        # (equal shard sizes)
        edge_aux = edge_aux + (
            _psum(aux_j.mean(), ctx) / ctx.num_shards
            if ctx is not None else aux_j.mean())
        edge_vjps.append(vjp)
        hop_bytes.append(x.size // x.shape[0] * x.dtype.itemsize)
        if compress_acts:
            x = compress_mod.compress_activations(
                x, jax.random.fold_in(rng_sel, 0xAC1 + j), comp_cfg, comp_p)
            act_wire_bytes.append(compress_mod.activation_wire_bytes(
                x.size // x.shape[0] // x.shape[-1], x.shape[-1],
                comp_cfg, comp_p))

    def server_loss(sp, a):
        losses, aux = _per_client_losses(model_cfg, sp, a, labels, impl,
                                         remat, span)
        local = jnp.sum(coef * losses)
        if ctx is not None:
            # the CE term sums over all clients; the MoE aux is a mean
            # over clients, so psum of per-shard means / S completes it
            total = (jax.lax.psum(local, ctx.axis)
                     + jax.lax.psum(aux, ctx.axis) / ctx.num_shards)
        else:
            total = local + aux
        return total, losses

    with jax.named_scope("wssl.server"):
        (loss, pcl), (g_server, g_x) = jax.value_and_grad(
            server_loss, argnums=(0, 1), has_aux=True)(server_params, x)
    loss = loss + edge_aux
    # with a ctx the vjp ran per shard on a replicated server stage — each
    # shard's g_server carries only its local clients' contribution; the
    # psum completes the global gradient (and keeps it replicated)
    g_server = _psum(g_server, ctx)

    # backward relay: inject each hop's cotangent upstream (the mean-aux
    # term contributes 1/N per client alongside the activation cotangent)
    if compress_acts:
        # down-hop wire compression: the returned server→edge gradient is
        # itself a (N, b, s, d) activation-shaped tensor; chaining the
        # lossy reconstruction into the manual vjp relay makes the
        # backward a straight-through estimate of the compressed forward
        g_x = compress_mod.compress_activations(
            g_x, jax.random.fold_in(rng_sel, 0xDC0 + num_edges),
            comp_cfg, comp_p)
    aux_ct = jnp.full((tokens.shape[0],), 1.0 / n, jnp.float32)
    g_edges = []
    for back_j, vjp in enumerate(reversed(edge_vjps)):
        g_e, g_x = vjp((g_x, aux_ct))
        if compress_acts:
            g_x = compress_mod.compress_activations(
                g_x, jax.random.fold_in(rng_sel,
                                        0xDC0 + num_edges - 1 - back_j),
                comp_cfg, comp_p)
        g_edges.append(_psum(g_e, ctx))
    g_edges.reverse()
    with jax.named_scope("wssl.client"):
        (g_client,) = client_vjp(g_x)
    return (loss, pcl, g_client, g_server, g_edges, hop_bytes,
            act_wire_bytes)


def _gathers_selected(model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
                      train_cfg: TrainConfig,
                      ctx: Optional[ShardCtx]) -> bool:
    """Static: do rounds after the first run the split fwd/bwd on the k
    selected clients alone?  Only where k rows give the masked round's
    objective exactly and need no routing, so the masked path stays for
    a sharded round (the selected rows live on other shards), under a
    mesh that shards the client axis (the same, in GSPMD form), for the
    client-chunked scan, for activation compression (its draws are
    (N, ...)-shaped per hop), for MoE models (the aux term is a mean over
    all N clients) and where every client is selected anyway."""
    comp = wssl_cfg.compression
    return (ctx is None and train_cfg.client_chunk is None
            and not (comp.enabled and comp.activations)
            and model_cfg.num_experts == 0
            and _client_spmd_axes() is None
            and wssl_cfg.num_selected() < wssl_cfg.num_clients)


def _split_grads(state: WSSLState, tokens, labels, embeds,
                 coef_loc: jax.Array, idx: jax.Array, *,
                 model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
                 train_cfg: TrainConfig, impl: str, n: int, n_loc: int,
                 ctx: Optional[ShardCtx], comp_cfg, comp_p,
                 compress_acts: bool, rng_sel):
    """Algorithm 2 steps 2-4 for both rounds (sync and async).

    Where :func:`_gathers_selected`, one jitted k-row block (gather k
    clients' stages, rows and CE weights; run the flat block on them)
    serves every round.  Later rounds run it once, on the k selected
    ``idx``.  Round 0 selects everyone and runs it on ceil(N/k) groups of
    k clients (the last padded with clients already covered, at weight
    0): the first group where later rounds run the selection, the others
    under a ``lax.cond`` on ``round_index == 0``.  The block's shapes are
    the same at every call, so it is traced once (a separate N-row block
    for round 0 would trace the fwd/bwd a second time, which dominates a
    warm start); the compiled program still holds it at both call sites.
    Round 0's shared-stage gradients are summed over the groups in fp32,
    a reassociation of the masked block's sum.  Elsewhere the
    client-chunked scan or the flat block runs over every local client.

    ``coef_loc`` is the (n_loc,) CE weight.  The gathered path relies on
    it being zero off ``idx`` after round 0 (the caller's mask, or the
    async round's ``part``, is ``selection_mask(idx)`` times keep/admit
    factors): a weighted client outside ``idx`` would be dropped without
    notice.  Returns ``(loss, pcl, g_client, g_server, g_edges,
    hop_bytes, act_wire_bytes, work_rows)`` shaped as the masked block
    gives them (zeros off the rows that ran); ``work_rows`` counts those
    rows."""
    args = (state.edge_stages, state.server_params)
    kw = dict(model_cfg=model_cfg, train_cfg=train_cfg, impl=impl, n=n,
              ctx=ctx, comp_cfg=comp_cfg, comp_p=comp_p,
              compress_acts=compress_acts, rng_sel=rng_sel)
    b = tokens.shape[1]
    every = jnp.asarray(n * b, jnp.float32)
    if train_cfg.client_chunk is not None:
        # client-chunked scan: O(chunk) activation peak, flat semantics
        # (documented fp band — see _client_grads_chunked)
        return _client_grads_chunked(
            state.client_stack, *args, tokens, labels, embeds, coef_loc,
            chunk=train_cfg.client_chunk, n_loc=n_loc, **kw) + (every,)
    if not _gathers_selected(model_cfg, wssl_cfg, train_cfg, ctx):
        return _client_grads_flat(state.client_stack, *args, tokens, labels,
                                  embeds, coef_loc, **kw) + (every,)

    k = idx.shape[0]
    passes = -(-n // k)
    first = state.round_index == 0
    seats = jnp.arange(passes * k)
    groups = jnp.where(first, (seats % n).reshape(passes, k), jnp.sort(idx))
    live = jnp.where(first, (seats < n).reshape(passes, k), True)
    # the per-hop byte counts are static and per client; recorded as the
    # block traces
    static = {}

    @jax.jit
    def block(rows, ok):
        with jax.named_scope("wssl.client"):
            take = lambda a: None if a is None else jnp.take(a, rows, axis=0)
            rows_in = (jax.tree.map(take, state.client_stack), *args,
                       take(tokens), take(labels), take(embeds),
                       take(coef_loc) * ok)
        out = _client_grads_flat(*rows_in, **kw)
        static["bytes"] = out[5:]
        return out[:5]

    rows, ok = groups[0], live[0]
    l, p, gc, gs, ge = block(rows, ok)

    def placed():
        # the k rows' per-client outputs into the (n_loc, ...) shapes
        with jax.named_scope("wssl.client"):
            return (jnp.zeros((n_loc,), p.dtype).at[rows].set(p * ok),
                    jax.tree.map(
                        lambda a, g: jnp.zeros_like(a).at[rows].set(g),
                        state.client_stack, gc))

    def later_round():
        return (l, *placed(), gs, ge)

    def first_round():
        # the other groups; shared-stage gradients accumulate in fp32
        def one_group(j, carry):
            lo, po, gco, gso, geo = carry
            rj, okj = groups[j], live[j]
            lj, pj, gcj, gsj, gej = block(rj, okj)
            add = lambda a, g: a + g.astype(a.dtype)
            with jax.named_scope("wssl.client"):
                gco = jax.tree.map(lambda a, g: a.at[rj].add(g), gco, gcj)
            return (lo + lj, po.at[rj].add(pj * okj), gco,
                    jax.tree.map(add, gso, gsj),
                    [jax.tree.map(add, a, g) for a, g in zip(geo, gej)])

        f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
        lo, po, gco, gso, geo = jax.lax.fori_loop(
            1, passes, one_group,
            (l, *placed(), f32(gs), [f32(g) for g in ge]))
        cast = lambda t, ref: jax.tree.map(lambda a, r: a.astype(r.dtype),
                                           t, ref)
        return (lo, po, gco, cast(gso, gs),
                [cast(a, r) for a, r in zip(geo, ge)])

    out = jax.lax.cond(first, first_round, later_round)
    work_rows = jnp.where(first, every, jnp.asarray(k * b, jnp.float32))
    return tuple(out) + static["bytes"] + (work_rows,)


def _validate(new_cstack: Params, new_edges: Tuple[Params, ...],
              new_server: Params, val_batch: Optional[Dict[str, jax.Array]],
              idx: jax.Array, round_index: jax.Array, *,
              model_cfg: ModelConfig, train_cfg: TrainConfig, impl: str,
              n: int, ctx: Optional[ShardCtx], faults: bool
              ) -> Tuple[jax.Array, jax.Array]:
    """Each client's loss on the server-held ζ, for both rounds ->
    ``((N,) val_losses, val_rows)``: client stage, edge relay and server
    loss per client stage of ``new_cstack``; ``val_rows`` counts the
    validation rows of the distinct clients the forward ran on (zeros and
    0 without ``val_batch``).

    Precondition: every client holds the same stage at the start of the
    round, which ``init_state`` (identical copies) and ``broadcast_global``
    (the end of every round) guarantee.  A flat round without faults then
    changes only the rows it stepped — the masked optimizer leaves every
    row off ``idx`` bit for bit — so after round 0 the N-k unselected
    clients hold one stage, the global one.  There the forward runs on
    k+1 rows: the k selected and the first unselected client ``j``, whose
    loss every unselected client gets.  Round 0 steps every client and
    scores all N in blocks of k+1 (the last padded with clients already
    covered), through the same loop, so the forward is traced once and
    held at one call site.  ``client_chunk`` caps the block at ``chunk``
    rows.

    All N rows are scored where the precondition may fail or nothing is
    saved: a sharded round or a mesh that shards the client axis, a fault
    scenario (adaptive adversaries rewrite unselected rows, and a
    non-finite corrupted gradient breaks the masked blend), and k+1 >= N.
    """
    if val_batch is None:
        return jnp.zeros((n,), jnp.float32), jnp.zeros((), jnp.float32)
    vt, vl = val_batch["tokens"], val_batch["labels"]
    remat = train_cfg.remat
    chunk = train_cfg.client_chunk
    bv = vt.shape[0]
    k = idx.shape[0]

    def val_one(cp):
        a = tf.client_forward(cp, model_cfg, vt, impl=impl, remat=remat)
        for j in range(len(new_edges)):
            a = tf.stage_forward(new_edges[j], model_cfg, a, j + 1,
                                 impl=impl, remat=remat)
        loss, _ = tf.server_loss(new_server, model_cfg, a, vl, impl=impl,
                                 remat=remat)
        return loss

    if (ctx is not None or faults or _client_spmd_axes() is not None
            or k + 1 >= n):
        if chunk is not None:
            vl_loc = _chunked_client_map(val_one, new_cstack, chunk)
        else:
            vl_loc = _client_vmap(val_one)(new_cstack)
        return _gather(vl_loc, ctx), jnp.asarray(n * bv, jnp.float32)

    m = k + 1 if chunk is None else min(k + 1, chunk)
    passes = -(-n // m)
    first = round_index == 0
    selected = wssl.selection_mask(idx, n) > 0
    j = jnp.argmin(selected)
    rows = jnp.concatenate([idx, j[None]])
    seats = jnp.arange(passes * m)
    groups = jnp.where(first, seats % n, rows[seats % (k + 1)])
    groups = groups.reshape(passes, m)

    def block(i, losses):
        g = groups[i]
        cs = jax.tree.map(lambda a: jnp.take(a, g, axis=0), new_cstack)
        return losses.at[g].set(_client_vmap(val_one)(cs))

    trips = jnp.where(first, passes, -(-(k + 1) // m))
    losses = jax.lax.fori_loop(0, trips, block, jnp.zeros((n,), jnp.float32))
    val_losses = jnp.where(first | selected, losses, losses[j])
    val_rows = jnp.where(first, n * bv, (k + 1) * bv).astype(jnp.float32)
    return val_losses, val_rows


def _admit(mask: jax.Array, plan, astate, async_p, n: int
           ) -> Dict[str, jax.Array]:
    """Deadline admission -> (N,) float vectors, full and replicated:
    ``part`` (this round's fresh work), ``on_time``, ``admit``,
    ``evicted``, ``arriving`` and ``delay``.

    A client's latency (``sim_faults.client_latencies``) sets the rounds of
    delay before its update can land (0 = on time, as every client at
    deadline = inf).  Busy clients (``pending > 0``) take no fresh work.
    A late client is evicted at admission if its delay reaches
    ``max_staleness`` or it would overflow ``buffer_size``; arrivals
    (``pending == 1``) free their slot first."""
    lat = sim_faults.client_latencies(plan, n)
    delay = jnp.maximum(jnp.ceil(lat / async_p.deadline) - 1.0, 0.0)
    arriving = (astate.pending == 1).astype(jnp.float32)
    mask = mask * (astate.pending == 0).astype(jnp.float32)
    on_time = mask * (delay == 0)
    late = mask * (delay > 0)
    evict_late = late * (delay >= async_p.max_staleness)
    admit = late - evict_late
    slots = (astate.pending > 1).sum().astype(jnp.float32)
    order = jnp.cumsum(admit) - admit     # admitted strictly before i
    overflow = admit * ((slots + order) >= async_p.buffer_size)
    admit = admit - overflow
    return dict(evicted=evict_late + overflow, part=on_time + admit,
                on_time=on_time, admit=admit, arriving=arriving, delay=delay)


def _deliver(new_cstack: Params, old_cstack: Params, astate, adm, async_p,
             kind: str, ctx: Optional[ShardCtx], n_loc: int):
    """Stale-update delivery -> (the stack to aggregate, (N,) weights, their
    local view).  An arriving client applies its parked delta to the
    *current* global stage; its weight carries the staleness discount
    (``wssl.async_contribution``), fused into the aggregation."""
    contrib = wssl.async_contribution(adm["on_time"], adm["arriving"],
                                      astate.staleness, async_p.max_staleness,
                                      kind=kind, alpha=async_p.staleness_alpha)
    contrib_loc = _loc(contrib, ctx, n_loc)

    def one(new, old, buf):
        arr = _pc(adm["arriving_loc"], new) > 0
        stale = (old.astype(jnp.float32)
                 + buf.astype(jnp.float32)).astype(new.dtype)
        return jnp.where(arr, stale, new)

    return (jax.tree.map(one, new_cstack, old_cstack, astate.buffer),
            contrib, contrib_loc)


def _park(presync_cstack: Params, old_cstack: Params, astate, adm):
    """The new ``(pending, staleness, buffer)``.  An admitted client parks
    its update for ``delay`` rounds, measured on the *pre-sync* stack (the
    local update it computed, before ``broadcast_global``); a slot whose
    update is still in flight keeps it; every other slot clears."""
    admit, pending = adm["admit"], astate.pending

    def one(new, old, buf):
        delta = (new.astype(jnp.float32)
                 - old.astype(jnp.float32)).astype(buf.dtype)
        keep = _pc((adm["pending_loc"] > 1).astype(jnp.float32), buf) > 0
        parked = jnp.where(keep, buf, jnp.zeros_like(buf))
        return jnp.where(_pc(adm["admit_loc"], buf) > 0, delta, parked)

    buffer = jax.tree.map(one, presync_cstack, old_cstack, astate.buffer)
    d_i32 = adm["delay"].astype(jnp.int32)
    new_pending = jnp.where(admit > 0, d_i32, jnp.maximum(pending - 1, 0))
    staleness = jnp.where(admit > 0, d_i32,
                          jnp.where(pending > 1, astate.staleness, 0))
    return new_pending, staleness, buffer


def _round(state: WSSLState, batch, val_batch, scenario, agg_p, comp_p, *,
           model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
           train_cfg: TrainConfig, schedule, impl: str,
           shard_ctx: Optional[ShardCtx], stale=None):
    """The body of both rounds (see :func:`wssl_round`).

    ``stale`` is None for the synchronous round.  For the bounded-staleness
    round (``core/async_round.py``) it is ``(astate, async_p)``, and
    the body adds admission (:func:`_admit`), delivery (:func:`_deliver`)
    and parking (:func:`_park`), which read ``AsyncState`` and
    ``AsyncParams`` by field.  Every async op sits under ``stale is not
    None``, so the synchronous round traces none of them.  Returns
    ``(WSSLState, RoundMetrics, out)``: ``out`` is None, or ``(pending,
    staleness, buffer, fields)`` with ``fields`` the
    ``AsyncRoundMetrics`` counters."""
    ctx = shard_ctx
    n = wssl_cfg.num_clients
    n_loc = n // ctx.num_shards if ctx is not None else n
    num_edges = len(state.edge_stages)
    rng, rng_sel = jax.random.split(state.rng)
    comp_cfg = wssl_cfg.compression
    if comp_cfg.enabled and comp_p is None:
        comp_p = compress_mod.compression_params(comp_cfg)
    # activation-path compression (CompressionConfig.activations): hop
    # crossings ship a lossy wire reconstruction; off = nothing traced
    compress_acts = comp_cfg.enabled and comp_cfg.activations
    if stale is not None:
        astate, async_p = stale

    # ---- fault injection (repro.sim): sampled first so the latency
    # signal can reach the selection draw; the fold_in stream keeps the
    # Gumbel draw untouched -----------------------------------------------
    plan = None
    if scenario is not None:
        plan = sim_faults.sample_fault_plan(
            jax.random.fold_in(rng_sel, 0x0DD), scenario, n,
            num_hops=num_edges, hop_replicas=wssl_cfg.hop_replicas)

    # ---- Algorithm 1: selection (round 0 selects everyone — the rule
    # lives in wssl.participation_mask).  With select_staleness_beta > 0
    # slow clients, and in the async round busy ones (pending rounds), pay
    # a penalty at the draw itself. --------------------------------------
    penalty = None
    if wssl_cfg.select_staleness_beta and (plan is not None
                                           or stale is not None):
        penalty = sim_faults.client_latencies(plan, n) - 1.0
        if stale is not None:
            penalty = penalty + astate.pending.astype(jnp.float32)
    # the k indices are drawn once: they pick the rows the split fwd/bwd
    # runs on, and the mask is made from them
    idx = wssl.weighted_sample(rng_sel, state.importance,
                               wssl_cfg.num_selected(), penalty=penalty,
                               beta=wssl_cfg.select_staleness_beta)
    mask = wssl.participation_mask(rng_sel, state.importance, wssl_cfg,
                                   state.round_index, idx=idx)

    # dropout ⇒ zero-mask (dropped clients compose like unselected ones)
    if plan is not None:
        mask = mask * plan.keep

    # part: the clients whose fresh work this round steps and counts — the
    # mask, or in the async round the on-time and admitted late clients
    part, adm = mask, None
    if stale is not None:
        adm = _admit(mask, plan, astate, async_p, n)
        part = adm["part"]
    agg_w = wssl.aggregation_weights(state.importance, part, wssl_cfg)

    # local views for the per-client tensor work: everything above (plan,
    # part, agg_w) is a full replicated (N,) decision vector; below, the
    # shard only touches its own N/S client rows.  All are the originals
    # when flat.
    plan_loc = _local_plan(plan, ctx, n_loc)
    part_loc = _loc(part, ctx, n_loc)
    agg_w_loc = _loc(agg_w, ctx, n_loc)
    if stale is not None:
        # the local views _deliver and _park read
        adm.update(arriving_loc=_loc(adm["arriving"], ctx, n_loc),
                   admit_loc=_loc(adm["admit"], ctx, n_loc),
                   pending_loc=_loc(astate.pending, ctx, n_loc))

    tokens = shard_activation(batch["tokens"], "client", None, None)
    labels = shard_activation(batch["labels"], "client", None, None)
    if plan is not None:
        labels = sim_faults.corrupt_labels(plan_loc, labels,
                                           model_cfg.vocab_size)
    embeds = batch.get("embeds")

    # ---- Algorithm 2 steps 2-4: split fwd / chained N-phase backward ----
    (loss, pcl, g_client, g_server, g_edges, hop_bytes, act_wire_bytes,
     work_rows) = _split_grads(
        state, tokens, labels, embeds, agg_w_loc * part_loc, idx,
        model_cfg=model_cfg, wssl_cfg=wssl_cfg, train_cfg=train_cfg,
        impl=impl, n=n, n_loc=n_loc, ctx=ctx, comp_cfg=comp_cfg,
        comp_p=comp_p, compress_acts=compress_acts, rng_sel=rng_sel)

    # ---- optimizer: clip, step (masked for unselected clients), faults ---
    with jax.named_scope("wssl.optimizer"):
        if train_cfg.grad_clip:
            g_client, _ = clip_by_global_norm(
                g_client, train_cfg.grad_clip,
                axis_name=ctx.axis if ctx is not None else None)
            g_server, _ = clip_by_global_norm(g_server, train_cfg.grad_clip)
            g_edges = [clip_by_global_norm(g, train_cfg.grad_clip)[0]
                       for g in g_edges]

        if plan is not None:
            # adversarial corruption models the *sent* client update, so
            # it applies after the shared global-norm clip — otherwise one
            # adversary's noise inflates the joint norm and attenuates
            # every clean client's gradient through the clip factor
            g_client = sim_faults.corrupt_client_grads(
                plan_loc, g_client,
                jax.random.fold_in(rng_sel, 0xBAD) if ctx is None
                else jax.random.fold_in(jax.random.fold_in(rng_sel, 0xBAD),
                                        ctx.index))

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
            # straggler / slow-hop partial progress and Byzantine
            # amplification on the post-optimizer update (a constant
            # gradient scale would be inert under Adam).  With a finite
            # deadline the async round models latency as *when* the update
            # lands, not how much of it: the partial-progress scale is
            # neutralised, Byzantine amplification still applies.
            step_plan = plan_loc
            if stale is not None:
                step_plan = plan_loc._replace(grad_scale=jnp.where(
                    jnp.isinf(async_p.deadline), plan_loc.grad_scale,
                    jnp.ones_like(plan_loc.grad_scale)))
            new_cstack = sim_faults.scale_client_updates(
                step_plan, new_cstack, state.client_stack)
            # adaptive adversaries craft their sent stage from the round's
            # honest updates (mean − z·std) — inside the honest spread, so
            # importance down-weighting cannot catch them
            new_cstack = sim_faults.adaptive_scale_updates(
                plan_loc, new_cstack, state.client_stack, part_loc,
                axis_name=ctx.axis if ctx is not None else None)
        if plan is not None or stale is not None:
            # a round emptied by dropout or a deadline leaves the shared
            # stages untouched too: the aux term and weight decay would
            # still step (and decay) them (an identity at deadline = inf)
            alive = part.sum() > 0
            keep_old = lambda new, old: jax.tree.map(
                lambda a, b: jnp.where(alive, a, b), new, old)
            new_server = keep_old(new_server, state.server_params)
            new_opt_s = keep_old(new_opt_s, state.opt_server)
            new_edges = [keep_old(ne, oe)
                         for ne, oe in zip(new_edges, state.edge_stages)]
            new_opt_e = [keep_old(no, oo)
                         for no, oo in zip(new_opt_e, state.opt_edge)]
        new_edges = tuple(new_edges)
        new_opt_e = tuple(new_opt_e)

    # ---- validation on the server-held ζ → importance ------------------
    with jax.named_scope("wssl.validate"):
        val_losses, val_rows = _validate(
            new_cstack, new_edges, new_server, val_batch, idx,
            state.round_index, model_cfg=model_cfg, train_cfg=train_cfg,
            impl=impl, n=n, ctx=ctx, faults=plan is not None)
        importance = (state.importance if val_batch is None else
                      wssl.compute_importance(val_losses, wssl_cfg,
                                              prev=state.importance))

    with jax.named_scope("wssl.aggregate"):
        # the async round aggregates the stale updates that land, too
        agg_stack, weights, weights_loc = new_cstack, part, part_loc
        if stale is not None:
            agg_stack, weights, weights_loc = _deliver(
                new_cstack, state.client_stack, astate, adm, async_p,
                wssl_cfg.async_rounds.staleness_weighting, ctx, n_loc)

        # ---- update-path compression (repro.compress): the *sent* delta
        # (a stale one the round it lands) is compressed client-side; every
        # registry rule runs on old + decompress(compress(Δ + e)).  With
        # scheme="none" this whole block is absent from the trace.
        ef_residual = state.ef_residual
        if comp_cfg.enabled:
            delta = jax.tree.map(lambda a, b: a.astype(jnp.float32)
                                 - b.astype(jnp.float32),
                                 agg_stack, state.client_stack)
            rng_comp = jax.random.fold_in(rng_sel, 0xC09)
            if ctx is not None:
                # decorrelate the per-coordinate stochastic draws across
                # shards (the flat round draws one (N, m) tensor per leaf;
                # per-shard draws necessarily differ — documented tolerance)
                rng_comp = jax.random.fold_in(rng_comp, ctx.index)
            sent, ef_residual = compress_mod.apply_compression(
                delta, ef_residual, weights_loc, rng_comp, comp_cfg, comp_p)
            agg_stack = jax.tree.map(
                lambda old, s: (old.astype(jnp.float32)
                                + s).astype(old.dtype),
                state.client_stack, sent)

        # ---- Algorithm 2 step 5: registry-dispatched aggregation + sync.
        # Dropout or a deadline can empty the round; `safe` falls back to a
        # no-op sync.  Robust rules binarize a fractional stale weight.
        safe = plan is not None or stale is not None
        if ctx is None:
            global_client = aggregation.aggregate_clients(
                agg_stack, importance, weights, wssl_cfg, safe=safe,
                params=agg_p)
        else:
            # two-level tree: per-shard partial aggregate, psum combine (or
            # the documented all_gather fallback for non-decomposable rules)
            global_client = aggregation.shard_aggregate_clients(
                agg_stack, importance, weights, wssl_cfg, axis_name=ctx.axis,
                shard_index=ctx.index, num_shards=ctx.num_shards,
                safe=safe, params=agg_p)
        presync_cstack = new_cstack
        new_cstack = wssl.broadcast_global(new_cstack, global_client)

    if stale is not None:
        parked = _park(presync_cstack, state.client_stack, astate, adm)

    # ---- communication accounting --------------------------------------
    # the async round uploads its on-time updates and the stale ones that
    # land, and resyncs the evicted clients (inside bytes_sync)
    sel = part.sum()
    if stale is not None:
        n_arrived, n_evicted = adm["arriving"].sum(), adm["evicted"].sum()
    bytes_per_hop = sel * jnp.asarray(hop_bytes, jnp.float32)
    stage_bytes = jnp.asarray(_client_stage_bytes(state.client_stack),
                              jnp.float32)
    uploads = sel
    if stale is not None:
        bytes_resync = n_evicted * stage_bytes
        uploads = adm["on_time"].sum() + n_arrived
    update_raw = uploads * stage_bytes
    if comp_cfg.enabled:
        comp_stage = compress_mod.compressed_stage_bytes(
            state.client_stack, comp_cfg, comp_p)
        update_comp = uploads * comp_stage
        # sync = compressed upload from the selected + raw broadcast to all
        bytes_sync = uploads * comp_stage + n * stage_bytes
    else:
        update_comp = update_raw
        bytes_sync = sync_round_bytes(uploads, n, stage_bytes)
    if stale is not None:
        bytes_sync = bytes_sync + bytes_resync
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
    out = None
    if stale is not None:
        out = (*parked, dict(
            on_time=adm["on_time"].sum(), buffered=adm["admit"].sum(),
            arrived=n_arrived, evicted=n_evicted,
            mean_staleness=((adm["arriving"] * astate.staleness).sum()
                            / jnp.maximum(n_arrived, 1.0)),
            bytes_resync=bytes_resync))
    new_state = WSSLState(
        client_stack=new_cstack, server_params=new_server,
        edge_stages=new_edges, opt_client=new_opt_c, opt_server=new_opt_s,
        opt_edge=new_opt_e, importance=importance,
        round_index=state.round_index + 1, rng=rng,
        ef_residual=ef_residual)
    return new_state, metrics, out


def wssl_round(state: WSSLState, batch: Dict[str, jax.Array],
               val_batch: Optional[Dict[str, jax.Array]] = None,
               scenario: Optional["sim_faults.ScenarioParams"] = None,
               agg_p: Optional["aggregation.AggParams"] = None,
               comp_p: Optional["compress_mod.CompressionParams"] = None, *,
               model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
               train_cfg: TrainConfig, schedule,
               impl: str = "chunked",
               shard_ctx: Optional[ShardCtx] = None
               ) -> Tuple[WSSLState, RoundMetrics]:
    """One communication round.  batch: tokens/labels (N, b, S);
    val_batch: tokens/labels (bv, S) — the server-held ζ.  When val_batch is
    None the validation pass is skipped and importance weights carry over
    (used by the dry-run, which lowers the train step alone; the production
    launcher runs the validation step at a lower cadence).

    scenario: optional dynamic ScenarioParams (repro.sim) — dropped clients
    (and clients routed through dead edge-hop replicas) compose into the
    selection mask as zeros, adversarial clients get label/gradient
    corruption under jnp.where, stragglers and slow hops contribute a
    scaled update.  Shapes never change and the params are traced scalars,
    so one compiled executable serves every same-shape scenario.  The fault
    rngs are fold_in-derived, leaving the selection stream and the carried
    state rng untouched — the all-zero (clean) params reproduce the
    fault-free round bit-for-bit.

    agg_p: optional dynamic AggParams (core/aggregation.py) so one
    executable serves every same-shape trim/f/m setting; None lowers them
    from the (static) config.

    comp_p: optional dynamic CompressionParams (repro.compress) — the
    top-k rate and quantization level count are traced scalars, so one
    executable serves every compression *level* of a scheme kind; only the
    kind itself (none | topk | quant) is a static branch.  With
    scheme="none" no compression op is traced at all and the round is
    bit-for-bit the pre-compression round (golden-tested).

    shard_ctx: None runs the round flat (the golden trace, unchanged op
    for op).  Inside :func:`make_sharded_round_fn` the round body executes
    per shard: ``state.client_stack`` / batch / ef_residual leaves arrive
    sliced to (N/S, ...), every (N,) decision vector is computed full +
    replicated (selection and fault draws bit-identical to flat), losses
    and shared-stage gradients cross shards via psum, validation losses
    via all_gather, and aggregation dispatches through the two-level tree
    (``aggregation.shard_aggregate_clients``).

    The stages run under ``jax.named_scope`` names that reach each
    compiled op's ``op_name`` (a profiler trace attributes device time by
    them; they add no op): ``wssl.client`` (client forward and its
    backward), ``wssl.server`` (server forward, loss and backward),
    ``wssl.optimizer``, ``wssl.validate`` and ``wssl.aggregate``.
    Selection, the edge relay and the byte accounting are unscoped.

    Its body (``_round``) is also the bounded-staleness round's
    (``core/async_round.py``), whose steps it traces for an AsyncState."""
    return _round(state, batch, val_batch, scenario, agg_p, comp_p,
                  model_cfg=model_cfg, wssl_cfg=wssl_cfg, train_cfg=train_cfg,
                  schedule=schedule, impl=impl, shard_ctx=shard_ctx)[:2]


def _round_args(round_body):
    """``round_body``'s positional parameters, the round's arguments (the
    keyword-only ones are its static configs)."""
    return [p for p in inspect.signature(round_body).parameters.values()
            if p.kind is p.POSITIONAL_OR_KEYWORD]


def _positional(jitted, round_body):
    """``round_fn`` taking the round's arguments and calling ``jitted``
    with all of them, defaults filled in: one call signature, so one
    executable (``cache_size()``, for the one-executable regression)."""
    sig = inspect.Signature(_round_args(round_body))

    def round_fn(*args, **kwargs):
        bound = sig.bind(*args, **kwargs)
        bound.apply_defaults()
        return jitted(*bound.args)

    round_fn.__signature__ = sig
    round_fn.cache_size = lambda: jitted._cache_size()
    round_fn._jitted = jitted
    return round_fn


def _make_round_fn(round_body, donate_argnums, model_cfg: ModelConfig,
                   wssl_cfg: WSSLConfig, train_cfg: TrainConfig, impl: str,
                   donate: bool):
    """:func:`make_round_fn` for ``round_body`` (sync or async), donating
    ``donate_argnums`` (the incoming state(s)) when ``donate``."""
    schedule = make_schedule(train_cfg.schedule, train_cfg.learning_rate,
                             train_cfg.warmup_steps, train_cfg.rounds)
    fn = functools.partial(round_body, model_cfg=model_cfg,
                           wssl_cfg=wssl_cfg, train_cfg=train_cfg,
                           schedule=schedule, impl=impl)
    if not donate:
        return fn
    return _positional(jax.jit(fn, donate_argnums=donate_argnums), round_body)


def make_round_fn(model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
                  train_cfg: TrainConfig, impl: str = "chunked", *,
                  donate: bool = False):
    """jit-ready round function with static configs closed over.

    ``donate=False`` (the legacy contract) returns an un-jitted partial —
    callers wrap it in ``jax.jit`` themselves.  ``donate=True`` returns
    the already-jitted round with the incoming :class:`WSSLState`
    donated (``donate_argnums=(0,)``): params, optimizer slots and EF
    residuals alias their outputs, so ONE copy of per-client state is
    live at peak instead of two.  The donating fn must NOT be wrapped in
    another ``jax.jit`` — nested jit silently drops inner donation (no
    warning on CPU) — which is why donation is opt-in here rather than a
    flag on the partial.  Exposes ``cache_size()`` for the
    one-executable regression."""
    return _make_round_fn(wssl_round, (0,), model_cfg, wssl_cfg, train_cfg,
                          impl, donate)


def _linear_shard_index(dp, mesh) -> jax.Array:
    """This device's position along the (possibly multi-axis) client
    sharding, row-major in mesh-axis order — matches both P(dp) block
    layout and all_gather concatenation order."""
    idx = jnp.zeros((), jnp.int32)
    for a in dp:
        idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
    return idx


def _make_sharded_round_fn(round_body, name: str, model_cfg: ModelConfig,
                           wssl_cfg: WSSLConfig, train_cfg: TrainConfig,
                           mesh, impl: str, donate: bool, carried=()):
    """:func:`make_sharded_round_fn` for ``round_body`` (sync or async).
    ``carried`` holds the specs of the ``AsyncState`` the async round
    takes and returns after its :class:`WSSLState`, donated with it."""
    from contextlib import nullcontext
    from jax.sharding import PartitionSpec
    from repro import sharding as shardlib

    dp = shardlib.data_axes_of(mesh)
    if not dp:
        raise ValueError(f"{name}: mesh has no data axis "
                         f"(axes: {mesh.axis_names})")
    num_shards = math.prod(mesh.shape[a] for a in dp)
    n = wssl_cfg.num_clients
    if n % num_shards != 0:
        raise ValueError(
            f"num_clients={n} must divide evenly over {num_shards} client "
            f"shards (mesh data axes {dp})")
    axis = dp if len(dp) > 1 else dp[0]
    arules = shardlib.auto_rules(mesh) if shardlib.auto_axes_of(mesh) else {}
    schedule = make_schedule(train_cfg.schedule, train_cfg.learning_rate,
                             train_cfg.warmup_steps, train_cfg.rounds)
    _, state_axes = abstract_state(model_cfg, wssl_cfg, train_cfg)
    st_specs = shardlib.round_state_specs(mesh, state_axes)
    client_spec = shardlib.client_axis_spec(mesh)
    rep = PartitionSpec()
    states = (st_specs, *carried)
    nargs = len(_round_args(round_body))

    def body(*args):
        ctx = ShardCtx(axis=axis, num_shards=num_shards,
                       index=_linear_shard_index(dp, mesh))
        bind = (shardlib.use_sharding_rules(mesh, arules) if arules
                else nullcontext())
        with bind:
            return round_body(*args, model_cfg=model_cfg, wssl_cfg=wssl_cfg,
                              train_cfg=train_cfg, schedule=schedule,
                              impl=impl, shard_ctx=ctx)

    mapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=states + (client_spec,) + (rep,) * (nargs - len(states) - 1),
        out_specs=states + (rep,),
        check_vma=False, axis_names=frozenset(dp))
    # donate the incoming state(s) (default on): the new state aliases the
    # old, so one copy of the sharded per-client stacks, optimizer slots
    # (and stale-update buffer) is live at peak.  place_state device_puts
    # a *copy*, so the caller's host-built state survives the first
    # donated call.
    jitted = jax.jit(mapped,
                     donate_argnums=tuple(range(len(states))) if donate
                     else ())
    round_fn = _positional(jitted, round_body)
    # commit inputs to the round's own shardings up front: host-built
    # (single-device) state/batch otherwise costs one extra copy-in
    # executable on the first call before the steady-state one takes over
    round_fn.place_state = lambda state: jax.device_put(
        state, shardlib.named_shardings_like(mesh, st_specs, state))
    round_fn.place_batch = lambda batch: jax.device_put(
        batch, shardlib.named_shardings_like(mesh, client_spec, batch))
    round_fn.mesh = mesh
    round_fn.num_shards = num_shards
    return round_fn


def make_sharded_round_fn(model_cfg: ModelConfig, wssl_cfg: WSSLConfig,
                          train_cfg: TrainConfig, mesh, *,
                          impl: str = "chunked", donate: bool = True):
    """Client-axis scale-out: :func:`wssl_round` shard_map-ed over the
    data axes of ``mesh``.

    Each shard holds N/S clients (stack, optimizer slots, EF residuals,
    batch rows sliced by the in_specs); per-client forward/backward and
    compression run fully local, shared-stage gradients and the
    aggregation tree combine via psum, and the (N,) decision vectors stay
    replicated so selection/faults are bit-identical to the flat round.
    Any non-data mesh axis (e.g. "model") is left ``auto`` — the compiler
    partitions the shared server/edge stages over it per
    ``sharding.auto_rules``, which is the heterogeneous per-stage
    placement: client stages manual on data, server stage model-parallel
    (or replicated on a 1-D data mesh).

    Returns ``round_fn(state, batch, val_batch=None, scenario=None,
    agg_p=None, comp_p=None)`` — jit-wrapped, one executable per call
    signature (all scenario/agg/compression knobs stay dynamic scalars).
    ``round_fn.cache_size()`` exposes the compiled-executable count for
    the one-executable regression; ``num_shards``/``mesh`` ride along.
    Matches the flat round within fp32 reassociation tolerance
    (tests/test_sharded_round.py; the psum of per-shard partial sums
    reorders the client reduction)."""
    return _make_sharded_round_fn(wssl_round, "make_sharded_round_fn",
                                  model_cfg, wssl_cfg, train_cfg, mesh, impl,
                                  donate)
