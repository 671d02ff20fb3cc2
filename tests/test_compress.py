"""Update-path compression (repro.compress + kernels/compress.py): kernel
parity vs the ref oracles, the error-feedback accumulation invariant, the
scheme="none" no-op, one-executable checks across dynamic rate/bits, and
the traced-vs-concrete byte-accounting agreement."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compress as C
from repro.config import (CompressionConfig, ModelConfig, TrainConfig,
                          WSSLConfig)
from repro.core import protocol
from repro.kernels import ops, ref
from repro.kernels.compress import (dequantize_2d, quantize_stochastic_2d,
                                    topk_mask_2d)

RNG = np.random.default_rng(7)


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(scale * RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# kernel parity vs kernels/ref.py (exact, interpret mode)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,bm", [(4, 1000, 256), (2, 33, 16),
                                    (8, 2048, 2048), (3, 2 * 256 + 93, 256)])
@pytest.mark.parametrize("levels", [127.0, 7.0])
def test_quantize_dequantize_parity(n, m, bm, levels):
    x = _rand((n, m))
    u = jnp.asarray(RNG.random((n, m)), jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=1)
    lv = jnp.float32(levels)
    inv = lv / scale
    q = quantize_stochastic_2d(x, u, inv, lv, block_m=bm, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(q), np.asarray(ref.quantize_stochastic_2d(x, u, inv, lv)))
    assert q.dtype == jnp.int8
    assert int(np.abs(np.asarray(q)).max()) <= int(levels)
    d = dequantize_2d(q, scale / lv, block_m=bm, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(d), np.asarray(ref.dequantize_2d(q, scale / lv)))
    # reconstruction error bounded by one step per element
    step = np.asarray(scale / lv)[:, None]
    assert np.abs(np.asarray(d) - np.asarray(x)).max() <= step.max() + 1e-6


@pytest.mark.parametrize("n,m,bm", [(4, 1000, 256), (2, 33, 16),
                                    (3, 2 * 256 + 93, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_topk_mask_parity(n, m, bm, dtype):
    x = _rand((n, m), dtype)
    t = C.topk_threshold(x.astype(jnp.float32), 0.05)
    got = topk_mask_2d(x, t, block_m=bm, interpret=True)
    want = ref.topk_mask_2d(x, t)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


def test_topk_threshold_keeps_rate_fraction():
    x = _rand((4, 1000))
    t = C.topk_threshold(x, 0.05)
    kept = (np.abs(np.asarray(x)) >= np.asarray(t)[:, None]).sum(axis=1)
    np.testing.assert_array_equal(kept, 50)   # continuous data: no ties
    # rate high enough to keep everything
    t1 = C.topk_threshold(x, 1.0)
    assert (np.abs(np.asarray(x)) >= np.asarray(t1)[:, None]).all()


def test_quantization_zero_row_guard():
    """An all-zero client row (masked client, empty delta) must quantize to
    exactly zero, not NaN from a 0/0 scale."""
    x = jnp.zeros((2, 64), jnp.float32)
    u = jnp.asarray(RNG.random((2, 64)), jnp.float32)
    scale = jnp.max(jnp.abs(x), axis=1)
    lv = jnp.float32(127.0)
    inv = jnp.where(scale > 0, lv / scale, 0.0)
    q = quantize_stochastic_2d(x, u, inv, lv, interpret=True)
    d = dequantize_2d(q, jnp.where(scale > 0, scale / lv, 0.0),
                      interpret=True)
    np.testing.assert_array_equal(np.asarray(d), 0.0)


# ---------------------------------------------------------------------------
# degenerate m == 0 inputs (the zero-division satellite)
# ---------------------------------------------------------------------------

def test_empty_leaf_kernels():
    z = jnp.zeros((3, 0), jnp.float32)
    assert quantize_stochastic_2d(z, z, jnp.zeros((3,)), jnp.float32(127.0),
                                  interpret=True).shape == (3, 0)
    assert dequantize_2d(jnp.zeros((3, 0), jnp.int8), jnp.zeros((3,)),
                         interpret=True).shape == (3, 0)
    assert topk_mask_2d(z, jnp.zeros((3,)), interpret=True).shape == (3, 0)
    assert C.topk_threshold(z, 0.05).shape == (3,)


def test_empty_leaf_apply_compression():
    cfg = CompressionConfig(scheme="int8")
    delta = {"w": _rand((4, 8)), "empty": jnp.zeros((4, 0), jnp.float32)}
    res = C.init_ef_residual(delta)
    sent, new_res = C.apply_compression(delta, res, jnp.ones((4,)),
                                        jax.random.PRNGKey(0), cfg)
    assert sent["empty"].shape == (4, 0)
    assert new_res["empty"].shape == (4, 0)
    assert np.isfinite(np.asarray(sent["w"])).all()


# ---------------------------------------------------------------------------
# error-feedback accumulation invariant
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme,tol", [("topk", 1e-5), ("int8", 1e-5),
                                        ("int4", 1e-5)])
def test_error_feedback_accumulation(scheme, tol):
    """Σ_t sent_t + e_T == Σ_t Δ_t exactly (up to fp addition error): the
    wire plus the residual accumulator conserves the raw update mass —
    the invariant that makes biased compressors converge (EF-SGD)."""
    cfg = CompressionConfig(scheme=scheme, rate=0.05)
    key = jax.random.PRNGKey(3)
    delta = {"a": _rand((4, 8, 16)), "b": _rand((4, 33))}
    res = C.init_ef_residual(delta)
    mask = jnp.asarray([1.0, 1.0, 0.0, 1.0])
    total_sent = jax.tree.map(jnp.zeros_like, delta)
    rounds = 6
    for r in range(rounds):
        sent, res = C.apply_compression(delta, res, mask,
                                        jax.random.fold_in(key, r), cfg)
        total_sent = jax.tree.map(lambda a, b: a + b, total_sent, sent)
    m = np.asarray(mask) > 0
    for leaf, s, e in zip(jax.tree.leaves(delta), jax.tree.leaves(total_sent),
                          jax.tree.leaves(res)):
        want = rounds * np.asarray(leaf)
        got = np.asarray(s) + np.asarray(e).reshape(leaf.shape)
        scale = np.abs(want).max() + 1.0
        assert np.abs((got - want)[m]).max() <= tol * scale * rounds
        # masked client: sent exactly 0, residual exactly 0 (never engaged)
        np.testing.assert_array_equal(np.asarray(s)[~m], 0.0)


def test_masked_client_keeps_residual():
    """A client masked this round must carry its residual unchanged."""
    cfg = CompressionConfig(scheme="topk", rate=0.1)
    delta = {"a": _rand((3, 64))}
    res = {"a": _rand((3, 64))}
    sent, new_res = C.apply_compression(delta, res,
                                        jnp.asarray([1.0, 0.0, 1.0]),
                                        jax.random.PRNGKey(0), cfg)
    np.testing.assert_array_equal(np.asarray(new_res["a"][1]),
                                  np.asarray(res["a"][1]))
    np.testing.assert_array_equal(np.asarray(sent["a"][1]), 0.0)
    assert not np.array_equal(np.asarray(new_res["a"][0]),
                              np.asarray(res["a"][0]))


def test_stochastic_quantization_unbiased():
    """E[dequantize(quantize(x))] == x over the uniform noise draw."""
    cfg = CompressionConfig(scheme="int4", error_feedback=False)
    # local generator: the shared RNG's draw position depends on which
    # tests ran before, and this statistical bound needs a pinned input
    x = {"a": jnp.asarray(np.random.default_rng(0).normal(size=(2, 256)),
                          jnp.float32)}
    key = jax.random.PRNGKey(11)
    acc = np.zeros((2, 256), np.float64)
    trials = 200
    for i in range(trials):
        sent, _ = C.apply_compression(x, (), jnp.ones((2,)),
                                      jax.random.fold_in(key, i), cfg)
        acc += np.asarray(sent["a"], np.float64)
    step = np.abs(np.asarray(x["a"])).max() / 7.0
    bias = np.abs(acc / trials - np.asarray(x["a"]))
    # CLT: se of U[0,1) rounding at step q is q/sqrt(12·trials); mean |bias|
    # over 512 elements concentrates at ~0.8·se, max at ~3.5·se
    se = step / np.sqrt(12 * trials)
    assert bias.mean() < 1.5 * se
    assert bias.max() < 6.0 * se


# ---------------------------------------------------------------------------
# scheme="none" is a structural no-op
# ---------------------------------------------------------------------------

def test_scheme_none_identity():
    delta = {"a": _rand((4, 16))}
    sent, res = C.apply_compression(delta, (), jnp.ones((4,)),
                                    jax.random.PRNGKey(0),
                                    CompressionConfig())
    assert sent is delta and res == ()


def test_config_validation():
    with pytest.raises(ValueError):
        CompressionConfig(scheme="int2")
    with pytest.raises(ValueError):
        CompressionConfig(scheme="topk", rate=0.0)
    with pytest.raises(ValueError):
        CompressionConfig(rate=1.5)
    assert not CompressionConfig().enabled
    assert CompressionConfig(scheme="int8").kind == "quant"
    assert CompressionConfig(scheme="int4").kind == "quant"
    assert CompressionConfig(scheme="int4").bits == 4
    assert CompressionConfig(scheme="topk").bits == 32
    assert CompressionConfig(scheme="int8").replace(scheme="topk").kind \
        == "topk"


# ---------------------------------------------------------------------------
# one executable across dynamic rate / bit width
# ---------------------------------------------------------------------------

def test_one_executable_across_rates_and_bits():
    traces = {"topk": 0, "quant": 0}
    delta = {"a": _rand((4, 128))}
    res = C.init_ef_residual(delta)
    mask = jnp.ones((4,))
    key = jax.random.PRNGKey(0)

    def make(kind, scheme):
        cfg = CompressionConfig(scheme=scheme)
        def fn(d, r, m, k, p):
            traces[kind] += 1
            return C.apply_compression(d, r, m, k, cfg, p)
        return jax.jit(fn)

    f_topk = make("topk", "topk")
    for rate in (0.01, 0.05, 0.5):
        cfg = CompressionConfig(scheme="topk", rate=rate)
        f_topk(delta, res, mask, key, C.compression_params(cfg))
    assert traces["topk"] == 1

    f_quant = make("quant", "int8")
    outs = {}
    for scheme in ("int8", "int4"):
        cfg = CompressionConfig(scheme=scheme)
        outs[scheme], _ = f_quant(delta, res, mask, key,
                                  C.compression_params(cfg))
    assert traces["quant"] == 1
    # the dynamic level count really changed the computation
    assert not np.array_equal(np.asarray(outs["int8"]["a"]),
                              np.asarray(outs["int4"]["a"]))


# ---------------------------------------------------------------------------
# byte accounting: traced formula == concrete protocol formula
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["none", "topk", "int8", "int4"])
def test_traced_bytes_match_protocol(scheme):
    n = 4
    stack = {"a": jnp.zeros((n, 8, 16)), "b": jnp.zeros((n, 33)),
             "c": jnp.zeros((n, 0))}
    per_client = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype), stack)
    cfg = CompressionConfig(scheme=scheme) if scheme != "none" \
        else CompressionConfig()
    traced = float(C.compressed_stage_bytes(stack, cfg))
    concrete = protocol.compressed_update_bytes(per_client, scheme,
                                                cfg.rate)
    assert traced == concrete
    if scheme == "none":
        assert concrete == protocol.tree_bytes(per_client)


# ---------------------------------------------------------------------------
# compressed fused round end-to-end (tiny model)
# ---------------------------------------------------------------------------

TINY = ModelConfig(name="tiny-comp", num_layers=2, d_model=32, num_heads=2,
                   num_kv_heads=2, d_ff=64, vocab_size=64,
                   dtype="float32", param_dtype="float32")


@pytest.mark.parametrize("scheme,want_ratio", [("topk", 12.5), ("int8", 4.0),
                                               ("int4", 8.0)])
def test_compressed_round_end_to_end(scheme, want_ratio):
    from repro.core.round import init_state, make_round_fn
    from repro.data.synthetic import lm_batch
    w = WSSLConfig(num_clients=4, participation_fraction=0.5,
                   compression=CompressionConfig(scheme=scheme, rate=0.04))
    t = TrainConfig(remat=False, learning_rate=1e-3, warmup_steps=0,
                    schedule="constant")
    state, _ = init_state(jax.random.PRNGKey(0), TINY, w, t)
    assert len(jax.tree.leaves(state.ef_residual)) == \
        len(jax.tree.leaves(state.client_stack))
    rf = jax.jit(make_round_fn(TINY, w, t, impl="dense"))
    for r in range(2):
        d = lm_batch(8, 16, TINY.vocab_size, seed=r)
        batch = {"tokens": jnp.asarray(d["tokens"]).reshape(4, 2, 16),
                 "labels": jnp.asarray(d["labels"]).reshape(4, 2, 16)}
        state, m = rf(state, batch)
    assert np.isfinite(float(m.loss))
    ratio = float(m.bytes_update_raw) / float(m.bytes_update_comp)
    assert ratio == pytest.approx(want_ratio, rel=0.05)
    # residuals engaged: some participating client carries non-zero error
    assert max(float(jnp.abs(l).max())
               for l in jax.tree.leaves(state.ef_residual)) > 0
    # sync accounting: compressed upload + raw broadcast to all N
    n, sel = 4, float(np.asarray(m.mask).sum())
    stage = protocol.tree_bytes(jax.tree.map(
        lambda l: jax.ShapeDtypeStruct(l.shape[1:], l.dtype),
        state.client_stack))
    comp_stage = float(m.bytes_update_comp) / sel
    assert float(m.bytes_sync) == pytest.approx(
        sel * comp_stage + n * stage, rel=1e-6)


def test_compressed_async_round_end_to_end():
    """Compression composes with bounded-staleness delivery: the fused
    async round compresses at delivery (fractional contrib mask), carries
    EF residuals, and reports the same topk byte ratio."""
    from repro.config import AsyncRoundsConfig
    from repro.core.async_round import (async_params, init_async_state,
                                        make_async_round_fn)
    from repro.core.round import init_state
    from repro.data.synthetic import lm_batch
    a = AsyncRoundsConfig(deadline=2.0, max_staleness=4,
                          staleness_weighting="polynomial")
    w = WSSLConfig(num_clients=4, participation_fraction=0.5, async_rounds=a,
                   compression=CompressionConfig(scheme="topk", rate=0.04))
    t = TrainConfig(remat=False, learning_rate=1e-3, warmup_steps=0,
                    schedule="constant")
    state, _ = init_state(jax.random.PRNGKey(0), TINY, w, t)
    astate = init_async_state(state)
    rf = jax.jit(make_async_round_fn(TINY, w, t, impl="dense"))
    ap = async_params(a, 4)
    for r in range(3):
        d = lm_batch(8, 16, TINY.vocab_size, seed=r)
        batch = {"tokens": jnp.asarray(d["tokens"]).reshape(4, 2, 16),
                 "labels": jnp.asarray(d["labels"]).reshape(4, 2, 16)}
        state, astate, am = rf(state, astate, batch, None, None, ap)
    m = am.base
    assert np.isfinite(float(m.loss))
    assert float(m.bytes_update_comp) > 0
    ratio = float(m.bytes_update_raw) / float(m.bytes_update_comp)
    assert ratio == pytest.approx(12.5, rel=0.05)
    assert max(float(jnp.abs(l).max())
               for l in jax.tree.leaves(state.ef_residual)) > 0
