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
from repro.core.round import (_client_grads_flat, _split_grads, init_state,
                              make_round_fn, make_sharded_round_fn)
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


def _work_rows(model, w, t, rounds=2, sharded=False):
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
        state, m = rf(state, batch)
        out.append(float(m.work_rows))
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
