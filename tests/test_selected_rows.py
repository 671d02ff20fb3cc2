"""The split forward/backward on the k selected clients' rows alone.

After round 0 the flat round gathers the k selected clients from the
stack and runs the client stage, the edge relay and the server loss on
those rows only; round 0 runs the same k-row block over ceil(N/k) groups
that cover every client (``core/round.py::_split_grads``).  The masked
path runs every client with loss weight 0 off the selection
(``_client_grads_flat`` over all N rows).  The two must give the same
loss, per-client losses and gradients within the golden tolerances, with
exact zero client gradients and losses off the selection — for a single
cut, a 3-stage edge cut, a frontend model fed ``embeds``, and
label-flipped plus dropped-out clients.  ``RoundMetrics.work_rows``
counts the rows that ran: k·b on rounds after the first, N·b on round 0
and wherever the masked path stays (sharded, client-chunked, activation
compression, MoE).

Validation scores k+1 client stages after round 0, not N: the k selected
and one unselected client, whose loss the other unselected clients share
(``core/round.py::_validate``).  Its losses and importance must match the
N-row vmap over the stack the round scored (read before
``broadcast_global`` resets it), for the sync and the async round under
AdamW and SGD.
``RoundMetrics.val_rows`` counts the scored rows: (k+1)·bv after round 0,
N·bv in round 0 and wherever every row is scored (sharded, a fault
scenario, k+1 >= N).  The shortcut rests on the unselected clients'
stages leaving a clean round unchanged, which an adaptive adversary
breaks.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from test_round_regression import LEAF_ATOL, METRIC_RTOL

from repro.config import (CompressionConfig, ModelConfig, Scenario,
                          TrainConfig, WSSLConfig, get_arch, reduced)
from repro.core import wssl
from repro.core.async_round import init_async_state, make_async_round_fn
from repro.core.round import (_client_grads_flat, _split_grads, _validate,
                              init_state, make_round_fn,
                              make_sharded_round_fn)
from repro.data.synthetic import lm_batch
from repro.sim import faults as sim_faults

TINY = ModelConfig(name="tiny-rows", num_layers=2, d_model=32, num_heads=2,
                   num_kv_heads=2, d_ff=64, vocab_size=64,
                   dtype="float32", param_dtype="float32")
VISION = reduced(get_arch("qwen2-vl-72b")).replace(
    dtype="float32", param_dtype="float32", frontend_tokens=4)
T = TrainConfig(remat=False, learning_rate=1e-3, warmup_steps=0,
                schedule="constant")
N, B, S = 4, 2, 16
BV = 2
IDX = (2, 0)

CASES = {
    "single_cut": (TINY, {}, False, N),
    "edge_3stage": (TINY.replace(num_layers=3),
                    {"split_layers": (1, 2), "hop_replicas": 2}, False, N),
    "frontend_embeds": (VISION, {}, False, N),
    "label_flip_dropout": (TINY, {}, True, N),
    # k = 2 of 5: round 0 runs three groups, the last padded at weight 0
    "padded_groups": (TINY, {}, False, 5),
}


def _batch(model, seed=0, n=N):
    d = lm_batch(n * B, S, model.vocab_size, seed=seed)
    batch = {"tokens": jnp.asarray(d["tokens"]).reshape(n, B, S),
             "labels": jnp.asarray(d["labels"]).reshape(n, B, S)}
    if model.frontend != "none":
        batch["embeds"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 1),
            (n, B, model.frontend_tokens, model.d_model))
    return batch


def _close(got, want, name, leaf=False):
    got, want = np.asarray(got), np.asarray(want)
    atol = LEAF_ATOL * float(np.abs(want).max(initial=0.0)) if leaf else 0
    np.testing.assert_allclose(got, want, rtol=METRIC_RTOL, atol=atol,
                               err_msg=name)


@pytest.mark.parametrize("round_index", [1, 0])
@pytest.mark.parametrize("case", list(CASES))
def test_selected_rows_match_the_masked_path(case, round_index):
    model, wkw, faulty, n = CASES[case]
    w = WSSLConfig(num_clients=n, participation_fraction=0.5, **wkw)
    assert w.num_selected() == len(IDX)
    state, _ = init_state(jax.random.PRNGKey(0), model, w, T)
    state = state._replace(round_index=jnp.asarray(round_index, jnp.int32))
    batch = _batch(model, n=n)
    idx = jnp.asarray(IDX, jnp.int32)
    mask = wssl.participation_mask(None, state.importance, w,
                                   state.round_index, idx=idx)
    labels = batch["labels"]
    if faulty:
        # client 0 flips its labels; client 2 is selected and drops out,
        # so its row runs with weight 0 on both paths
        sc = Scenario(name="flip-drop", label_flip_fraction=0.25)
        plan = sim_faults.sample_fault_plan(
            jax.random.PRNGKey(3), sim_faults.scenario_params(sc), n)
        plan = plan._replace(keep=plan.keep.at[2].set(0.0))
        labels = sim_faults.corrupt_labels(plan, labels, model.vocab_size)
        assert float(plan.flip[0]) == 1.0
        mask = mask * plan.keep
    coef = wssl.aggregation_weights(state.importance, mask, w) * mask
    kw = dict(model_cfg=model, train_cfg=T, impl="dense", n=n, ctx=None,
              comp_cfg=w.compression, comp_p=None, compress_acts=False,
              rng_sel=jax.random.PRNGKey(1))
    every = jax.jit(functools.partial(_client_grads_flat, **kw))(
        state.client_stack, state.edge_stages, state.server_params,
        batch["tokens"], labels, batch.get("embeds"), coef)
    rows = jax.jit(functools.partial(_split_grads, wssl_cfg=w, n_loc=n,
                                     **kw))(
        state, batch["tokens"], labels, batch.get("embeds"), coef, idx)
    assert every[5] == rows[5], "per-client hop bytes must not change"
    ran = np.ones(n, bool) if round_index == 0 else np.isin(np.arange(n),
                                                              IDX)
    assert float(rows[7]) == ran.sum() * B
    _close(rows[0], every[0], "loss")
    _close(rows[1], np.asarray(every[1]) * ran, "pcl")
    for name, got, want in (("g_client", rows[2], every[2]),
                            ("g_server", rows[3], every[3]),
                            ("g_edges", rows[4], every[4])):
        for i, (g, e) in enumerate(zip(jax.tree.leaves(got),
                                       jax.tree.leaves(want))):
            assert g.dtype == e.dtype and g.shape == e.shape, name
            _close(g, e, f"{name} leaf {i}", leaf=True)
    for i, g in enumerate(jax.tree.leaves(rows[2])):
        np.testing.assert_array_equal(np.asarray(g)[~ran], 0.0,
                                      err_msg=f"g_client leaf {i} off idx")
    assert any(float(jnp.abs(g[0]).max()) > 0
               for g in jax.tree.leaves(rows[2]))


def _val(model):
    d = lm_batch(BV, S, model.vocab_size, seed=999)
    return {"tokens": jnp.asarray(d["tokens"]),
            "labels": jnp.asarray(d["labels"])}


def _round(kind, model, w, t):
    """A jitted sync or async round -> ``run(state, batch, val, scenario)
    -> (state, RoundMetrics)``; the async round at deadline = inf."""
    if kind == "sync":
        return jax.jit(make_round_fn(model, w, t, impl="dense"))
    f = jax.jit(make_async_round_fn(model, w, t, impl="dense"))

    def run(state, batch, val, scenario):
        new, _, m = f(state, init_async_state(state), batch, val, scenario)
        return new, m.base
    return run


# the CASES whose rounds run without faults, and a client-chunked round
SCORED = {name: (model, wkw, {}, n)
          for name, (model, wkw, faulty, n) in CASES.items() if not faulty}
SCORED["client_chunk"] = (TINY, {}, {"client_chunk": 2}, N)


@functools.lru_cache(maxsize=None)
def _scored_round(case, kind, optimizer):
    """One jitted round per case, shared by both round indices (a larger
    step than ``T``'s, so that stepped clients score well apart), and the
    validation that scores every row."""
    model, wkw, tkw, n = SCORED[case]
    w = WSSLConfig(num_clients=n, participation_fraction=0.5, **wkw)
    t = dataclasses.replace(T, optimizer=optimizer, learning_rate=1e-2,
                            **tkw)
    every = jax.jit(functools.partial(
        _validate, idx=jnp.arange(w.num_selected()), round_index=0,
        model_cfg=model, train_cfg=t, impl="dense", n=n, ctx=None,
        faults=True))
    return model, w, t, _round(kind, model, w, t), every


@pytest.mark.parametrize("optimizer", ["adamw", "sgd"])
@pytest.mark.parametrize("kind", ["sync", "async"])
@pytest.mark.parametrize("round_index", [1, 0])
@pytest.mark.parametrize("case", list(SCORED))
def test_validation_on_k_plus_one_rows_matches_every_row(
        case, round_index, kind, optimizer, monkeypatch):
    # the round returns the stack it scored, before the reset to the
    # global stage
    monkeypatch.setattr(wssl, "broadcast_global", lambda stack, g: stack)
    model, w, t, run, every = _scored_round(case, kind, optimizer)
    n, k = w.num_clients, w.num_selected()
    state, _ = init_state(jax.random.PRNGKey(0), model, w, t)
    val = _val(model)
    if round_index:
        state, _ = run(state, _batch(model, seed=0, n=n), val, None)
        # every client on one stage, as broadcast_global leaves them
        state = state._replace(client_stack=jax.tree.map(
            lambda a: jnp.broadcast_to(a[:1], a.shape), state.client_stack))
    new, got = run(state, _batch(model, seed=1, n=n), val, None)
    want, rows = every(new.client_stack, new.edge_stages, new.server_params,
                       val)
    assert float(got.val_rows) == (k + 1 if round_index else n) * BV
    assert float(rows) == n * BV
    _close(got.val_loss, want, "val_loss")
    _close(got.importance,
           wssl.compute_importance(want, w, prev=state.importance),
           "importance")
    # the stepped clients score apart from the ones that share a stage
    vl, sel = np.asarray(want), np.asarray(got.mask) > 0
    if round_index:
        assert np.all(vl[~sel] == vl[~sel][0])
        gap = np.abs(vl[sel] - vl[~sel][0]).min() / abs(vl[~sel][0])
        assert gap > 4 * METRIC_RTOL, gap


def _work_rows(model, w, t, rounds=2, sharded=False, val=None,
               scenario=None, counter="work_rows"):
    state, _ = init_state(jax.random.PRNGKey(0), model, w, t)
    if sharded:
        from repro.launch.mesh import make_client_mesh
        rf = make_sharded_round_fn(model, w, t, make_client_mesh(1),
                                   impl="dense")
        state = rf.place_state(state)
    else:
        rf = make_round_fn(model, w, t, impl="dense", donate=True)
    out = []
    for r in range(rounds):
        batch = _batch(model, seed=r, n=w.num_clients)
        if sharded:
            batch = rf.place_batch(batch)
        state, m = rf(state, batch, val, scenario)
        out.append(float(getattr(m, counter)))
    return out


MASKED = {
    "sharded": (TINY, {}, {}, True),
    "client_chunk": (TINY, {}, {"client_chunk": 2}, False),
    "act_compression": (TINY, {"compression": CompressionConfig(
        scheme="int8", activations=True)}, {}, False),
    "moe": (reduced(get_arch("olmoe-1b-7b")).replace(num_layers=2), {}, {},
            False),
}


def test_work_rows_counts_the_selected_rows():
    w = WSSLConfig(num_clients=N, participation_fraction=0.5)
    assert _work_rows(TINY, w, T, rounds=3) == [N * B, 2 * B, 2 * B]


@pytest.mark.parametrize("case", list(MASKED))
def test_work_rows_on_the_masked_path(case):
    model, wkw, tkw, sharded = MASKED[case]
    w = WSSLConfig(num_clients=N, participation_fraction=0.5, **wkw)
    t = dataclasses.replace(T, **tkw)
    assert _work_rows(model, w, t, sharded=sharded) == [N * B, N * B]


# participation, TrainConfig changes, scenario, sharded -> clients scored
# in rounds 0, 1, 2
VAL_ROWS = {
    "flat": (0.5, {}, None, False, [N, 3, 3]),
    "client_chunk": (0.5, {"client_chunk": 2}, None, False, [N, 3, 3]),
    "sharded": (0.5, {}, None, True, [N, N, N]),
    "scenario": (0.5, {}, Scenario(), False, [N, N, N]),
    "k3_of_4": (0.75, {}, None, False, [N, N, N]),
}


@pytest.mark.parametrize("case", list(VAL_ROWS))
def test_val_rows_counts_the_scored_clients(case):
    frac, tkw, sc, sharded, clients = VAL_ROWS[case]
    w = WSSLConfig(num_clients=N, participation_fraction=frac)
    t = dataclasses.replace(T, **tkw)
    sc = None if sc is None else sim_faults.scenario_params(sc)
    got = _work_rows(TINY, w, t, rounds=3, sharded=sharded, val=_val(TINY),
                     scenario=sc, counter="val_rows")
    assert got == [c * BV for c in clients]


@pytest.mark.parametrize("optimizer", ["adamw", "sgd"])
@pytest.mark.parametrize("adaptive", [False, True], ids=["clean", "adaptive"])
def test_unselected_rows_keep_their_stage(adaptive, optimizer, monkeypatch):
    """The precondition of the k+1-row validation: after a clean round
    every client stage off the selection is bit for bit the one it
    started from (read before ``broadcast_global`` resets the stack).
    Adaptive adversaries (clients 0 and 1 here, left unselected) rewrite
    their rows whether selected or not, which is why a round with a fault
    scenario scores every row."""
    monkeypatch.setattr(wssl, "broadcast_global", lambda stack, g: stack)
    w = WSSLConfig(num_clients=N, participation_fraction=0.5)
    t = dataclasses.replace(T, optimizer=optimizer)
    state, _ = init_state(jax.random.PRNGKey(0), TINY, w, t)
    rf = jax.jit(make_round_fn(TINY, w, t, impl="dense"))
    state, _ = rf(state, _batch(TINY, seed=0), None, None)
    # round 1 from the stepped stack, with clients 2 and 3 drawn
    state = state._replace(
        importance=jnp.asarray([1e-9, 1e-9, 0.5, 0.5], jnp.float32))
    sc = (sim_faults.scenario_params(Scenario(name="adaptive",
                                              adaptive_fraction=0.5))
          if adaptive else None)
    new, m = rf(state, _batch(TINY, seed=1), None, sc)
    off = np.asarray(m.mask) == 0
    assert off.tolist() == [True, True, False, False]
    same = [np.array_equal(np.asarray(a)[off], np.asarray(b)[off])
            for a, b in zip(jax.tree.leaves(new.client_stack),
                            jax.tree.leaves(state.client_stack))]
    assert not all(same) if adaptive else all(same)
