"""Client-chunked scan (TrainConfig.client_chunk) vs the flat vmap trace.

``client_chunk=k`` runs the per-client forward/backward as a lax.scan
over client chunks, capping activation memory at O(k) instead of O(n).
Contracts:

* chunked == flat within the fp32 reassociation band (the scan
  accumulates shared-stage gradients chunk-by-chunk instead of one big
  reduction; measured max leaf diff ~7e-7 on the tiny config, asserted
  at 1e-4 — docs/scaling.md tolerance table);
* ``client_chunk == n`` is ONE chunk covering every client — the same
  reduction order as flat, so bit-for-bit equal (the whole round with
  every client selected, and the block alone against the flat block);
* ``client_chunk=None`` keeps the flat trace bit-for-bit (covered by
  the goldens in test_round_regression.py, which run with the default);
* a chunk that does not divide the per-shard client count raises at
  trace time, and config validation rejects nonsensical knobs.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import (AsyncRoundsConfig, ModelConfig, TrainConfig,
                          WSSLConfig)
from repro.core import wssl
from repro.core.async_round import (async_params, init_async_state,
                                    make_async_round_fn)
from repro.core.round import (_client_grads_chunked, _client_grads_flat,
                              init_state, make_round_fn,
                              make_sharded_round_fn)
from repro.data.synthetic import lm_batch

TINY = ModelConfig(name="tiny-chunk", num_layers=2, d_model=32, num_heads=2,
                   num_kv_heads=2, d_ff=64, vocab_size=64,
                   dtype="float32", param_dtype="float32")
N = 8
W = WSSLConfig(num_clients=N, participation_fraction=0.5,
               importance_temp=0.1, importance_ema=0.8)

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 4,
    reason="sharded round needs >= 4 devices (CI: XLA_FLAGS="
           "--xla_force_host_platform_device_count=8)")


def _t(chunk=None):
    return TrainConfig(remat=False, learning_rate=1e-3, warmup_steps=0,
                       schedule="constant", client_chunk=chunk)


def _batches(rounds=2):
    vd = lm_batch(4, 16, TINY.vocab_size, seed=999)
    val = {"tokens": jnp.asarray(vd["tokens"]),
           "labels": jnp.asarray(vd["labels"])}
    out = []
    for r in range(rounds):
        d = lm_batch(N * 2, 16, TINY.vocab_size, seed=r)
        out.append({"tokens": jnp.asarray(d["tokens"]).reshape(N, 2, 16),
                    "labels": jnp.asarray(d["labels"]).reshape(N, 2, 16)})
    return val, out


def _run_sync(chunk, rounds=2, w=W):
    val, batches = _batches(rounds)
    t = _t(chunk)
    state, _ = init_state(jax.random.PRNGKey(0), TINY, w, t)
    rf = jax.jit(make_round_fn(TINY, w, t, impl="dense"))
    for b in batches:
        state, m = rf(state, b, val)
    return state, m


def _run_async(chunk):
    val, batches = _batches()
    t = _t(chunk)
    state, _ = init_state(jax.random.PRNGKey(0), TINY, W, t)
    astate = init_async_state(state)
    rf = jax.jit(make_async_round_fn(TINY, W, t, impl="dense"))
    ap = async_params(AsyncRoundsConfig(deadline=1.0), N)
    for b in batches:
        state, astate, m = rf(state, astate, b, val, None, ap)
    return state, m.base


@pytest.mark.parametrize("chunk", [1, 2, 4])
def test_chunked_matches_flat_sync(chunk):
    s_f, m_f = _run_sync(None)
    s_c, m_c = _run_sync(chunk)
    # decisions are chunk-independent: same selection, same faults
    np.testing.assert_array_equal(np.asarray(m_c.mask), np.asarray(m_f.mask))
    for a, b in zip(jax.tree.leaves(s_c), jax.tree.leaves(s_f)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_c.val_loss),
                               np.asarray(m_f.val_loss), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(m_c.bytes_up),
                                  np.asarray(m_f.bytes_up))


@pytest.mark.parametrize("chunk", [1, 2, 4])
def test_chunked_matches_flat_async(chunk):
    s_f, m_f = _run_async(None)
    s_c, m_c = _run_async(chunk)
    np.testing.assert_array_equal(np.asarray(m_c.mask), np.asarray(m_f.mask))
    for a, b in zip(jax.tree.leaves(s_c), jax.tree.leaves(s_f)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-4)
    np.testing.assert_allclose(np.asarray(m_c.val_loss),
                               np.asarray(m_f.val_loss), atol=1e-4)


def test_single_chunk_is_bit_for_bit():
    """chunk == n: one scan step over all clients — identical reduction
    order to the flat trace, so every leaf and metric is bit-equal.  With
    every client selected both rounds run the masked block over all N
    rows (a flat round with k < N runs the block on k gathered clients'
    rows at a time: test_single_chunk_block_is_bit_for_bit and
    tests/test_selected_rows.py cover that)."""
    w = dataclasses.replace(W, participation_fraction=1.0)
    s_f, m_f = _run_sync(None, w=w)
    s_c, m_c = _run_sync(N, w=w)
    for a, b in zip(jax.tree.leaves((s_c, m_c)), jax.tree.leaves((s_f, m_f))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_single_chunk_block_is_bit_for_bit():
    """chunk == n against the flat block over all clients: every
    gradient, loss and byte count is bit-equal, with every client
    weighted (round 0) and with k of them (the masked path of later
    rounds)."""
    s_f, _ = _run_sync(None, rounds=1)
    _, batches = _batches()
    kw = dict(model_cfg=TINY, train_cfg=_t(N), impl="dense", n=N,
              ctx=None, comp_cfg=W.compression, comp_p=None,
              compress_acts=False, rng_sel=jax.random.PRNGKey(1))
    flat = jax.jit(functools.partial(_client_grads_flat, **kw))
    one = jax.jit(functools.partial(_client_grads_chunked, chunk=N, n_loc=N,
                                    **kw))
    for mask in (jnp.ones((N,), jnp.float32),
                 wssl.selection_mask(jnp.asarray([1, 4, 5, 6]), N)):
        coef = wssl.aggregation_weights(s_f.importance, mask, W) * mask
        args = (s_f.client_stack, s_f.edge_stages, s_f.server_params,
                batches[1]["tokens"], batches[1]["labels"], None, coef)
        got, want = one(*args), flat(*args)
        assert got[5:] == want[5:]
        for a, b in zip(jax.tree.leaves(got[:5]),
                        jax.tree.leaves(want[:5])):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_chunk_must_divide_clients():
    val, batches = _batches(rounds=1)
    t = _t(3)   # 3 does not divide 8
    state, _ = init_state(jax.random.PRNGKey(0), TINY, W, t)
    rf = jax.jit(make_round_fn(TINY, W, t, impl="dense"))
    with pytest.raises(ValueError, match="divide"):
        rf(state, batches[0], val)


def test_config_validation():
    with pytest.raises(ValueError):
        TrainConfig(client_chunk=0)
    with pytest.raises(ValueError):
        TrainConfig(fused_adam=True, optimizer="sgd")
    # valid combinations construct fine
    TrainConfig(client_chunk=4, fused_adam=True)


@needs_mesh
def test_chunked_composes_with_shard_map():
    """client_chunk under the sharded round: each shard scans its local
    n/S clients in chunks.  The chunked scan reorders each shard's local
    accumulation before the psum, and Adam's rsqrt/eps nonlinearity
    amplifies that reassociation exactly as in the sharded-vs-flat
    equivalence (see test_sharded_round.py module docstring) — so the
    post-optimizer band here is the same documented 5e-3, not the 1e-4
    single-device band."""
    from repro.launch.mesh import make_client_mesh
    mesh = make_client_mesh(4)
    val, batches = _batches()

    def run(chunk):
        t = _t(chunk)
        state, _ = init_state(jax.random.PRNGKey(0), TINY, W, t)
        rf = make_sharded_round_fn(TINY, W, t, mesh, impl="dense")
        state = rf.place_state(state)
        for b in batches:
            state, m = rf(state, rf.place_batch(b), val)
        assert rf.cache_size() == 1
        return state, m

    s_f, m_f = run(None)
    s_c, m_c = run(2)   # n/S = 2 local clients -> chunk 2 divides
    np.testing.assert_array_equal(np.asarray(m_c.mask), np.asarray(m_f.mask))
    for a, b in zip(jax.tree.leaves(s_c), jax.tree.leaves(s_f)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=5e-3)
