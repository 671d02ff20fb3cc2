"""Pallas kernel correctness: shape/dtype sweeps vs the pure-jnp oracles
(interpret mode on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rg_lru import rg_lru_scan
from repro.kernels.ssd_scan import ssd_scan
from repro.kernels.wavg import weighted_average_2d

RNG = np.random.default_rng(42)


def _rand(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(scale * RNG.normal(size=shape), dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,hq,hkv,s,hd", [
    (1, 2, 2, 128, 64),     # MHA
    (2, 4, 2, 256, 64),     # GQA
    (1, 8, 1, 128, 128),    # MQA
    (2, 4, 4, 384, 32),     # non-pow2 seq (3 blocks of 128)
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(b, hq, hkv, s, hd, dtype):
    q = _rand((b, hq, s, hd), dtype)
    k = _rand((b, hkv, s, hd), dtype)
    v = _rand((b, hkv, s, hd), dtype)
    out = flash_attention_bhsd(q, k, v, causal=True, interpret=True)
    want = ref.flash_attention(q, k, v, causal=True)
    tol = 2e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("window", [64, 128, 256])
def test_flash_attention_window(window):
    q = _rand((1, 2, 256, 64))
    k = _rand((1, 2, 256, 64))
    v = _rand((1, 2, 256, 64))
    out = flash_attention_bhsd(q, k, v, causal=True, window=window,
                               interpret=True)
    want = ref.flash_attention(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)


def test_flash_attention_softcap():
    q = _rand((1, 2, 128, 64))
    k = _rand((1, 2, 128, 64))
    v = _rand((1, 2, 128, 64))
    out = flash_attention_bhsd(q, k, v, logit_softcap=30.0, interpret=True)
    want = ref.flash_attention(q, k, v, logit_softcap=30.0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-6)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,h,p,n,chunk,bh", [
    (1, 128, 4, 32, 16, 64, 4),
    (2, 256, 8, 64, 32, 128, 4),
    (1, 64, 2, 16, 8, 32, 2),
])
def test_ssd_scan_sweep(b, s, h, p, n, chunk, bh):
    x = _rand((b, s, h, p))
    dt = jnp.asarray(RNG.uniform(0.01, 0.5, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 4.0, size=(h,)), jnp.float32)
    b_ = _rand((b, s, n))
    c_ = _rand((b, s, n))
    out = ssd_scan(x, dt, a, b_, c_, chunk=chunk, block_h=bh, interpret=True)
    want = ref.ssd_scan(x, dt, a, b_, c_)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=5e-4, rtol=1e-3)


def test_ssd_scan_bf16():
    b, s, h, p, n = 1, 128, 4, 32, 16
    x = _rand((b, s, h, p), jnp.bfloat16)
    dt = jnp.asarray(RNG.uniform(0.01, 0.5, size=(b, s, h)), jnp.float32)
    a = -jnp.asarray(RNG.uniform(0.5, 4.0, size=(h,)), jnp.float32)
    b_ = _rand((b, s, n), jnp.bfloat16)
    c_ = _rand((b, s, n), jnp.bfloat16)
    out = ssd_scan(x, dt, a, b_, c_, chunk=64, block_h=4, interpret=True)
    want = ref.ssd_scan(x, dt, a, b_, c_)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=0.15,
                               rtol=0.05)


# ---------------------------------------------------------------------------
# RG-LRU scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,s,w,chunk,bw", [
    (1, 128, 64, 64, 64),
    (2, 256, 256, 128, 128),
    (1, 64, 512, 32, 256),
])
def test_rg_lru_sweep(b, s, w, chunk, bw):
    log_a = -jnp.asarray(RNG.uniform(1e-3, 0.5, size=(b, s, w)), jnp.float32)
    bb = _rand((b, s, w))
    out = rg_lru_scan(log_a, bb, chunk=chunk, block_w=bw, interpret=True)
    want = ref.rg_lru_scan(log_a, bb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# weighted average (WSSL aggregation)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,m,bm", [(4, 1000, 256), (16, 4096, 2048),
                                    (2, 33, 16), (8, 2048, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_wavg_sweep(n, m, bm, dtype):
    st = _rand((n, m), dtype)
    w = jnp.asarray(RNG.dirichlet(np.ones(n)), jnp.float32)
    out = weighted_average_2d(st, w, block_m=bm, interpret=True)
    want = ref.weighted_average_2d(st, w)
    tol = 1e-6 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol)


@pytest.mark.parametrize("n,m,bm", [
    (4, 4096, 2048),        # exact multiple: no padding
    (4, 2 * 2048 + 931, 2048),   # M % block_m != 0 -> padding branch
    (3, 97, 64),            # single padded tile
])
def test_wavg_parity_vs_wssl_reference(n, m, bm):
    """kernels/wavg vs the reference path in wssl.weighted_average, incl.
    M not divisible by block_m (interpret mode on CPU)."""
    from repro.core import wssl
    st = _rand((n, m))
    w = jnp.asarray(RNG.dirichlet(np.ones(n)), jnp.float32)
    got = weighted_average_2d(st, w, block_m=bm, interpret=True)
    want = wssl.weighted_average({"x": st}, w)["x"]
    assert got.shape == (m,)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_wavg_empty_leaf():
    """m == 0 must not reach the kernel grid (division by zero): an empty
    leaf aggregates to an empty result, through both the 2-D entry point
    and the pytree wrapper in ops.weighted_average."""
    from repro.kernels import ops
    w = jnp.asarray([0.5, 0.5], jnp.float32)
    out = weighted_average_2d(jnp.zeros((2, 0), jnp.float32), w,
                              interpret=True)
    assert out.shape == (0,)
    got = ops.weighted_average(jnp.zeros((2, 0, 5), jnp.float32), w)
    assert got.shape == (0, 5)
    full = _rand((2, 3))
    np.testing.assert_allclose(np.asarray(ops.weighted_average(full, w)),
                               np.asarray(full).mean(0), atol=1e-6)


def test_wavg_matches_tree_aggregation():
    """ops.weighted_average == core.wssl.weighted_average on a pytree."""
    from repro.core import wssl
    from repro.kernels import ops
    tree = {"a": _rand((4, 8, 16)), "b": [_rand((4, 32)), _rand((4, 3, 5))]}
    w = jnp.asarray([0.1, 0.2, 0.3, 0.4], jnp.float32)
    got = wssl.weighted_average(tree, w, use_kernel=True)
    want = wssl.weighted_average(tree, w, use_kernel=False)
    for g, x in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(x), atol=1e-5)


# ---------------------------------------------------------------------------
# fused masked-AdamW (optimizer hot path)
# ---------------------------------------------------------------------------

def _adam_problem(n, m, dtype=jnp.float32, mask=None):
    p = _rand((n, m), dtype)
    g = _rand((n, m), dtype, scale=1e-2)
    mm = _rand((n, m), jnp.float32, scale=1e-2)
    v = jnp.abs(_rand((n, m), jnp.float32, scale=1e-4))
    if mask is None:
        mask = jnp.asarray(RNG.integers(0, 2, size=n), jnp.float32)
    # step=3 bias corrections, computed exactly as adamw_update does
    t = jnp.float32(3.0)
    b1, b2 = 0.9, 0.95
    s = jnp.stack([jnp.asarray(x, jnp.float32) for x in
                   (3e-3, b1, b2, 1 - b1, 1 - b2, 1e-8, 0.01,
                    1.0 - b1 ** t, 1.0 - b2 ** t)])
    return p, g, mm, v, mask, s


@pytest.mark.parametrize("n,m,bm", [
    (4, 4096, 2048),           # exact multiple: no padding
    (4, 2 * 2048 + 931, 2048),  # M % block_m != 0 -> padding branch
    (3, 97, 64),               # single padded tile
    (6, 1037, 2048),           # odd width, one block covers all
])
def test_fused_adamw_parity_fp32(n, m, bm):
    """Kernel == oracle bit-for-bit in fp32 — compared jit-to-jit, which
    is how the round runs both paths (eager-vs-jit differs in the last
    ulp because XLA contracts a*b+c into FMA; see kernels/fused_adam.py)."""
    from repro.kernels.fused_adam import fused_adamw_2d
    p, g, mm, v, mask, s = _adam_problem(n, m)
    ker = jax.jit(lambda *a: fused_adamw_2d(*a, block_m=bm, interpret=True))
    orc = jax.jit(ref.fused_adamw_2d)
    for got, want in zip(ker(p, g, mm, v, mask, s),
                         orc(p, g, mm, v, mask, s)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("bm", [64, 2048])
def test_fused_adamw_bf16(bm):
    """bf16 params: moments stay fp32 (bit-exact vs oracle); the p' cast
    through the kernel's fp32 compute lands within one bf16 ulp."""
    from repro.kernels.fused_adam import fused_adamw_2d
    p, g, mm, v, mask, s = _adam_problem(5, 731, jnp.bfloat16)
    ker = jax.jit(lambda *a: fused_adamw_2d(*a, block_m=bm, interpret=True))
    orc = jax.jit(ref.fused_adamw_2d)
    po, mo, vo = ker(p, g, mm, v, mask, s)
    pw, mw, vw = orc(p, g, mm, v, mask, s)
    assert po.dtype == jnp.bfloat16 and mo.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(po, np.float32),
                               np.asarray(pw, np.float32), atol=1e-2)
    np.testing.assert_array_equal(np.asarray(mo), np.asarray(mw))
    np.testing.assert_array_equal(np.asarray(vo), np.asarray(vw))


def test_fused_adamw_mask_freezes_rows():
    """mask=0 rows keep p AND both moments bit-identical (the paper's
    non-participation contract), straight from the kernel."""
    from repro.kernels.fused_adam import fused_adamw_2d
    mask = jnp.asarray([1.0, 0.0, 1.0, 0.0], jnp.float32)
    p, g, mm, v, _, s = _adam_problem(4, 257, mask=mask)
    po, mo, vo = jax.jit(
        lambda *a: fused_adamw_2d(*a, interpret=True))(p, g, mm, v, mask, s)
    for row in (1, 3):
        np.testing.assert_array_equal(np.asarray(po[row]), np.asarray(p[row]))
        np.testing.assert_array_equal(np.asarray(mo[row]), np.asarray(mm[row]))
        np.testing.assert_array_equal(np.asarray(vo[row]), np.asarray(v[row]))
    assert not np.array_equal(np.asarray(po[0]), np.asarray(p[0]))


def test_fused_adamw_empty_leaf_and_mask_none():
    """ops.fused_adamw: zero-size leaves short-circuit (grid math would
    divide by zero), and mask=None (shared stage) flattens any-rank
    leaves to one always-on row."""
    from repro.kernels import ops
    _, _, _, _, _, s = _adam_problem(1, 8)
    p0 = jnp.zeros((4, 0, 5), jnp.float32)
    po, mo, vo = ops.fused_adamw(p0, p0, p0, p0,
                                 jnp.ones((4,), jnp.float32), s)
    assert po.shape == (4, 0, 5) and mo.dtype == jnp.float32
    p3 = _rand((3, 4, 5))
    g3 = _rand((3, 4, 5), scale=1e-2)
    m3 = jnp.zeros((3, 4, 5), jnp.float32)
    v3 = jnp.zeros((3, 4, 5), jnp.float32)
    po, mo, vo = jax.jit(lambda *a: ops.fused_adamw(*a, None, s))(
        p3, g3, m3, v3)
    assert po.shape == p3.shape
    assert not np.array_equal(np.asarray(po), np.asarray(p3))


def test_fused_adamw_dispatch_matches_treemap():
    """adamw_update(use_kernel=True) == the unfused tree.map chain
    bit-for-bit in fp32 over a mixed-rank pytree (jit-to-jit), for both
    the masked stacked stage and the mask=None shared stage."""
    from repro.optim.optimizers import adamw_init, adamw_update
    params = {"w": _rand((4, 33, 7)), "b": _rand((4, 129)),
              "s": _rand((4,))}
    grads = jax.tree.map(lambda l: 1e-2 * l, params)
    st = adamw_init(params)
    for mask in (jnp.asarray([1.0, 0.0, 1.0, 1.0], jnp.float32), None):
        f0 = jax.jit(lambda p, g, o, mk=mask: adamw_update(
            p, g, o, lr=3e-3, mask=mk))
        f1 = jax.jit(lambda p, g, o, mk=mask: adamw_update(
            p, g, o, lr=3e-3, mask=mk, use_kernel=True))
        p0, o0 = f0(params, grads, st)
        p1, o1 = f1(params, grads, st)
        for a, b in zip(jax.tree.leaves((p0, o0)), jax.tree.leaves((p1, o1))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_adamw_one_executable_across_hypers():
    """lr / weight-decay / step reach the kernel as the (9,) scalar
    vector — dynamic lr across calls must not retrace."""
    from repro.optim.optimizers import adamw_init, adamw_update
    params = {"w": _rand((2, 65))}
    grads = {"w": _rand((2, 65), scale=1e-2)}
    st = adamw_init(params)
    f = jax.jit(lambda p, g, o, lr: adamw_update(
        p, g, o, lr=lr, mask=jnp.ones((2,), jnp.float32),
        use_kernel=True))
    for lr in (1e-3, 3e-3, 1e-4):
        _, st = f(params, grads, st, jnp.float32(lr))
    assert f._cache_size() == 1


# ---------------------------------------------------------------------------
# dispatch: interpret on the CPU backend only
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["gpu", "METAL"])
def test_ops_raise_on_backend_neither_cpu_nor_tpu(backend, monkeypatch):
    """A kernel never falls back to the interpreter on an accelerator it
    was not written for: only the CPU backend (the tests) interprets."""
    from repro.kernels import ops
    x, w = _rand((4, 256)), jnp.full((4,), 0.25, jnp.float32)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    with pytest.raises(RuntimeError, match=backend):
        ops.weighted_average(x, w)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    np.testing.assert_allclose(np.asarray(ops.weighted_average(x, w)),
                               np.asarray(ref.weighted_average_2d(x, w)),
                               atol=1e-6)
