"""Golden regressions for the fused round.

1. Single-cut: with a length-1 cut tuple the N-stage ``wssl_round`` must
   reproduce ``tests/golden/round_single_cut.npz`` (two jit'd rounds of a
   tiny fp32 config on CPU): every metric and every state leaf, with the
   same leaf count — the empty edge tuple must add no ops, no leaves, and
   consume no randomness.

2. Async-off: ``async_wssl_round`` with ``deadline = inf`` must reproduce
   ``wssl_round`` — including multi-hop cuts.
   ``tests/golden/round_async_off.npz`` was generated by the *synchronous*
   ``wssl_round`` (same two-round protocol, single-cut + 3-stage configs),
   so the async engine is pinned to the synchronous trajectory, not to a
   copy of itself: every deadline/buffer/staleness op must lower to an
   exact identity and consume no randomness when the deadline is infinite.
   In one process that holds bit for bit, against the sync round run
   alongside.

Against the stored goldens, integer metrics (mask, byte counts) compare
exactly and floats within a written tolerance.  Bit equality with a stored
file pins XLA's op fusion and reduction order, which a JAX release may
change, and the PRNG implementation (``jax_threefry_partitionable``),
which changes the random draws outright.  Across jax 0.4.37 -> 0.9.0 with
the old PRNG forced, the loss, validation losses and importance moved by
less than 4e-7 relative: ``METRIC_RTOL = 1e-5`` covers that.  Parameters
and optimizer moments moved by up to 3e-5 of their leaf's largest
magnitude, and 1e-4 on a few Adam coordinates whose second moment sits
near epsilon (there the step's size flips with the last bits of the
gradient): ``LEAF_ATOL = 2e-4`` of the leaf's scale covers that and stays
ten times below what one Adam step moves a parameter leaf (lr 1e-3 on
weights of magnitude ~0.5).  A changed PRNG or algorithm moves the loss by
far more (about 4% on this config).  Regenerate the goldens after a JAX
upgrade with

    PYTHONPATH=src python tests/test_round_regression.py
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import AsyncRoundsConfig, ModelConfig, TrainConfig, WSSLConfig
from repro.core.async_round import (async_params, init_async_state,
                                    make_async_round_fn)
from repro.core.round import init_state, make_round_fn
from repro.data.synthetic import lm_batch

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "round_single_cut.npz")
GOLDEN_ASYNC_OFF = os.path.join(os.path.dirname(__file__), "golden",
                                "round_async_off.npz")

TINY = ModelConfig(name="tiny-golden", num_layers=2, d_model=32, num_heads=2,
                   num_kv_heads=2, d_ff=64, vocab_size=64,
                   dtype="float32", param_dtype="float32")
TINY3 = TINY.replace(name="tiny-golden-3stage", num_layers=3)
ASYNC_CASES = [
    ("single", TINY, {}),
    ("multihop", TINY3, {"split_layers": (1, 2), "hop_replicas": 2}),
]
# fp32 reassociation across XLA releases (see the module docstring)
METRIC_RTOL = 1e-5
LEAF_ATOL = 2e-4      # times the leaf's largest magnitude


def _close(got, want, err_msg, leaf=False):
    got = np.asarray(got)
    if not np.issubdtype(want.dtype, np.floating):
        np.testing.assert_array_equal(got, want, err_msg=err_msg)
    elif leaf:
        np.testing.assert_allclose(
            got, want, rtol=METRIC_RTOL,
            atol=LEAF_ATOL * float(np.abs(want).max(initial=0.0)),
            err_msg=err_msg)
    else:
        np.testing.assert_allclose(got, want, rtol=METRIC_RTOL, atol=0,
                                   err_msg=err_msg)


def _run_two_rounds(model=TINY, **wkw):
    # donate=True: the goldens must hold with the incoming state donated
    # (aliasing changes buffers, never values)
    w = WSSLConfig(num_clients=4, participation_fraction=0.5, **wkw)
    t = TrainConfig(remat=False, learning_rate=1e-3, warmup_steps=0,
                    schedule="constant")
    state, _ = init_state(jax.random.PRNGKey(0), model, w, t)
    rf = make_round_fn(model, w, t, impl="dense", donate=True)
    vd = lm_batch(4, 16, model.vocab_size, seed=999)
    val = {"tokens": jnp.asarray(vd["tokens"]),
           "labels": jnp.asarray(vd["labels"])}
    for r in range(2):
        d = lm_batch(8, 16, model.vocab_size, seed=r)
        batch = {"tokens": jnp.asarray(d["tokens"]).reshape(4, 2, 16),
                 "labels": jnp.asarray(d["labels"]).reshape(4, 2, 16)}
        state, m = rf(state, batch, val)
    return state, m


SYNC_METRICS = ("loss", "per_client_loss", "val_loss", "mask", "importance",
                "bytes_up", "bytes_down")
HOP_METRICS = SYNC_METRICS + ("bytes_per_hop", "bytes_sync")


def test_single_cut_round_bit_for_bit_vs_prerefactor():
    g = np.load(GOLDEN)
    state, m = _run_two_rounds()
    for name in SYNC_METRICS:
        _close(getattr(m, name), g["metric_" + name], err_msg=name)
    leaves = jax.tree.leaves(state)
    assert len(leaves) == int(g["num_state_leaves"]), \
        "single-cut state must not grow leaves (empty edge tuples only)"
    for i, leaf in enumerate(leaves):
        _close(leaf, g[f"state_{i:03d}"], err_msg=f"state leaf {i}",
               leaf=True)


def _run_two_async_rounds(model, w):
    """Two jit'd async rounds at deadline = inf — the async-off path."""
    t = TrainConfig(remat=False, learning_rate=1e-3, warmup_steps=0,
                    schedule="constant")
    state, _ = init_state(jax.random.PRNGKey(0), model, w, t)
    astate = init_async_state(state)
    rf = make_async_round_fn(model, w, t, impl="dense", donate=True)
    ap = async_params(AsyncRoundsConfig(), w.num_clients)   # deadline = inf
    vd = lm_batch(4, 16, model.vocab_size, seed=999)
    val = {"tokens": jnp.asarray(vd["tokens"]),
           "labels": jnp.asarray(vd["labels"])}
    for r in range(2):
        d = lm_batch(8, 16, model.vocab_size, seed=r)
        batch = {"tokens": jnp.asarray(d["tokens"]).reshape(4, 2, 16),
                 "labels": jnp.asarray(d["labels"]).reshape(4, 2, 16)}
        state, astate, m = rf(state, astate, batch, val)
    return state, astate, m


@pytest.mark.parametrize("prefix,model,wkw", ASYNC_CASES)
def test_async_off_round_bit_for_bit_vs_sync_golden(prefix, model, wkw):
    """deadline=inf must reproduce the synchronous round — single-cut AND
    3-stage multi-hop: bit for bit against ``wssl_round`` run in this
    process, and within the golden tolerance against the stored sync
    trajectory.  Any asymmetry the async machinery introduces (an op that
    is not an exact identity, an extra RNG split, a reordered reduction)
    shows up as a bit mismatch here."""
    g = np.load(GOLDEN_ASYNC_OFF)
    w = WSSLConfig(num_clients=4, participation_fraction=0.5, **wkw)
    state, astate, m = _run_two_async_rounds(model, w)
    sync_state, sync_m = _run_two_rounds(model, **wkw)
    for name in HOP_METRICS:
        got = np.asarray(getattr(m.base, name))
        np.testing.assert_array_equal(got, np.asarray(getattr(sync_m, name)),
                                      err_msg=f"{prefix}:{name} vs sync")
        _close(got, g[f"{prefix}_metric_{name}"], err_msg=f"{prefix}:{name}")
    leaves = jax.tree.leaves(state)
    assert len(leaves) == int(g[f"{prefix}_num_state_leaves"]), \
        "async-off WSSLState must not grow leaves"
    for i, (leaf, sync_leaf) in enumerate(
            zip(leaves, jax.tree.leaves(sync_state))):
        np.testing.assert_array_equal(np.asarray(leaf),
                                      np.asarray(sync_leaf),
                                      err_msg=f"{prefix} leaf {i} vs sync")
        _close(leaf, g[f"{prefix}_state_{i:03d}"],
               err_msg=f"{prefix} state leaf {i}", leaf=True)
    # the async bookkeeping itself must stay inert: no client ever
    # buffered, every slot zero, nothing arrived or evicted
    np.testing.assert_array_equal(np.asarray(astate.pending),
                                  np.zeros(4, np.int32))
    np.testing.assert_array_equal(np.asarray(astate.staleness),
                                  np.zeros(4, np.int32))
    for leaf in jax.tree.leaves(astate.buffer):
        np.testing.assert_array_equal(np.asarray(leaf), 0.0)
    assert float(m.buffered) == 0.0 and float(m.arrived) == 0.0
    assert float(m.evicted) == 0.0 and float(m.bytes_resync) == 0.0
    assert float(m.on_time) == float(np.asarray(m.base.mask).sum())


def test_single_cut_round_new_accounting_fields():
    """The new per-hop/sync columns must be consistent with the legacy
    totals on a single-cut round: one hop crossing carries everything."""
    state, m = _run_two_rounds()
    per_hop = np.asarray(m.bytes_per_hop)
    assert per_hop.shape == (1,)
    np.testing.assert_array_equal(per_hop.sum(), np.asarray(m.bytes_up))
    # sync = (selected + N) × one client stage; 2 selected of 4 here
    n, sel = 4, float(np.asarray(m.mask).sum())
    stage_bytes = sum(np.asarray(l)[0].size * np.asarray(l).dtype.itemsize
                      for l in jax.tree.leaves(state.client_stack))
    assert float(m.bytes_sync) == (sel + n) * stage_bytes


def _regenerate_goldens():
    """Rewrite both golden files from the synchronous round."""
    state, m = _run_two_rounds()
    out = {f"metric_{k}": np.asarray(getattr(m, k)) for k in SYNC_METRICS}
    leaves = jax.tree.leaves(state)
    out["num_state_leaves"] = np.asarray(len(leaves))
    out.update({f"state_{i:03d}": np.asarray(l)
                for i, l in enumerate(leaves)})
    np.savez(GOLDEN, **out)

    out = {}
    for prefix, model, wkw in ASYNC_CASES:
        state, m = _run_two_rounds(model, **wkw)
        out.update({f"{prefix}_metric_{k}": np.asarray(getattr(m, k))
                    for k in HOP_METRICS})
        leaves = jax.tree.leaves(state)
        out[f"{prefix}_num_state_leaves"] = np.asarray(len(leaves))
        out.update({f"{prefix}_state_{i:03d}": np.asarray(l)
                    for i, l in enumerate(leaves)})
    np.savez(GOLDEN_ASYNC_OFF, **out)


if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")
    _regenerate_goldens()
