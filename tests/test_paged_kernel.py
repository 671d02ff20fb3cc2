"""Paged-attention kernel (kernels/paged_attention.py): oracle parity
sweeps, physical-block permutation invariance, engine-level kernel ≡
gather ≡ contiguous token parity, KV-pool buffer donation, device-table
upload caching, and O(reserved-blocks) paged admission."""

import gc
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_fallback import given, settings, st
from repro.config import get_arch, reduced
from repro.kernels import ref
from repro.kernels.paged_attention import paged_decode_attention
from repro.models import transformer as tf
from repro.serve import BlockAllocator, DecodeEngine
from repro.serve.engine import _scatter_slot_paged_jit, _walk_cache

FAMILIES = {"dense": "gemma3-12b", "ssm": "mamba2-370m",
            "hybrid": "recurrentgemma-2b"}


def _setup(arch):
    cfg = reduced(get_arch(arch))
    params, _ = tf.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _make_case(b, hq, hkv, hd, bs, nb, pos, *, seed=0, extra_blocks=2):
    """An engine-reachable paged case: disjoint per-row physical blocks
    drawn from a pool with ``extra_blocks`` unowned garbage blocks, K/V
    random everywhere, ``ppos`` valid (= absolute position) on each row's
    live prefix and -1 elsewhere — the invariant admission/rollback
    maintain."""
    rng = np.random.default_rng(seed)
    num_blocks = b * nb + extra_blocks
    perm = rng.permutation(num_blocks)
    table = perm[:b * nb].reshape(b, nb).astype(np.int32)
    q = rng.standard_normal((b, hq, hd)).astype(np.float32)
    pk = rng.standard_normal((num_blocks, bs, hkv, hd)).astype(np.float32)
    pv = rng.standard_normal((num_blocks, bs, hkv, hd)).astype(np.float32)
    ppos = np.full((num_blocks, bs), -1, np.int32)
    pos = np.asarray(pos, np.int32)
    for row in range(b):
        for e in range(int(pos[row]) + 1):
            ppos[table[row, e // bs], e % bs] = e
    return (jnp.asarray(q), jnp.asarray(pk), jnp.asarray(pv),
            jnp.asarray(ppos), jnp.asarray(table), jnp.asarray(pos))


# ---------------------------------------------------------------------------
# kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("b,hq,hkv,hd,bs,nb,pos,softcap", [
    (2, 4, 2, 8, 5, 4, [7, 12], None),      # odd bs, GQA, partial block
    (1, 2, 2, 8, 8, 3, [15], 30.0),         # b=1, MHA, pos on boundary
    (3, 4, 4, 16, 8, 2, [0, 8, 13], None),  # pos=0, boundary, partial
    (2, 8, 2, 8, 4, 5, [3, 19], 20.0),      # full-table live prefix
])
def test_kernel_matches_oracle(b, hq, hkv, hd, bs, nb, pos, softcap):
    """The Pallas block-table kernel reproduces the gather oracle across
    odd block sizes, partial last blocks, GQA vs MHA, softcap on/off, and
    positions at block boundaries (fp32, interpret mode)."""
    case = _make_case(b, hq, hkv, hd, bs, nb, pos, seed=b * nb)
    got = paged_decode_attention(*case, logit_softcap=softcap,
                                 interpret=True)
    want = ref.paged_decode_attention(*case, logit_softcap=softcap)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)


def test_kernel_empty_and_single_live_rows():
    """An all-invalid row finalizes to exactly 0 (not a uniform average
    over garbage) in both kernel and oracle; a single-live-entry row
    returns that entry's V exactly (softmax over one logit)."""
    case = _make_case(2, 4, 2, 8, 4, 3, [0, 5], seed=3)
    q, pk, pv, ppos, table, pos = case
    ppos = ppos.at[table[0]].set(-1)              # row 0: nothing valid
    got = paged_decode_attention(q, pk, pv, ppos, table, pos,
                                 interpret=True)
    want = ref.paged_decode_attention(q, pk, pv, ppos, table, pos)
    assert np.array_equal(np.asarray(got[0]), np.zeros_like(got[0]))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)

    # row with exactly one live entry -> output is that entry's V
    case1 = _make_case(1, 2, 2, 8, 4, 2, [0], seed=4)
    q1, pk1, pv1, ppos1, table1, pos1 = case1
    got1 = paged_decode_attention(*case1, interpret=True)
    v0 = np.asarray(pv1)[int(table1[0, 0]), 0]    # (hkv, hd)
    np.testing.assert_allclose(np.asarray(got1[0]), v0, rtol=0, atol=1e-6)


def test_kernel_masks_stale_future_positions():
    """Entries with ``ppos > pos`` inside the live prefix (what a
    speculative rollback leaves behind) are masked identically by kernel
    and oracle."""
    q, pk, pv, ppos, table, pos = _make_case(2, 4, 2, 8, 4, 3, [6, 9],
                                             seed=5)
    ppos = ppos.at[table[0, 1], 3].set(7)          # stale pp = pos+1
    got = paged_decode_attention(q, pk, pv, ppos, table, pos,
                                 interpret=True)
    want = ref.paged_decode_attention(q, pk, pv, ppos, table, pos)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=2e-6)
    # and the stale entry really is invisible: zeroing its K/V changes
    # nothing
    pk2 = pk.at[table[0, 1], 3].set(0.0)
    pv2 = pv.at[table[0, 1], 3].set(0.0)
    got2 = paged_decode_attention(q, pk2, pv2, ppos, table, pos,
                                  interpret=True)
    assert np.array_equal(np.asarray(got), np.asarray(got2))


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(min_value=0, max_value=10**6))
def test_kernel_block_permutation_invariance(seed):
    """Property: the kernel's output is a function of the *logical* view
    only — relabeling physical block ids (permuting the pool and
    remapping the table) leaves the output bit-identical."""
    rng = np.random.default_rng(seed)
    bs = int(rng.integers(2, 9))
    nb = int(rng.integers(1, 5))
    pos = [int(rng.integers(0, nb * bs)) for _ in range(2)]
    q, pk, pv, ppos, table, posa = _make_case(2, 4, 2, 8, bs, nb, pos,
                                              seed=seed)
    base = np.asarray(paged_decode_attention(q, pk, pv, ppos, table, posa,
                                             interpret=True))
    sigma = rng.permutation(pk.shape[0])           # old id -> new id
    inv = np.argsort(sigma)
    got = np.asarray(paged_decode_attention(
        q, pk[inv], pv[inv], ppos[inv], jnp.asarray(sigma)[table], posa,
        interpret=True))
    assert np.array_equal(base, got)


# ---------------------------------------------------------------------------
# engine-level: kernel == gather == contiguous, one executable each
# ---------------------------------------------------------------------------


def _paged_engine(cfg, params, prompts, max_len, bs, **kw):
    eng = DecodeEngine(cfg, impl="dense", **kw)
    slots = len(prompts)
    nb = max_len // bs
    st_ = eng.new_batch_state(slots, max_len, block_size=bs)
    alloc = BlockAllocator(slots * (nb + 1), bs, reserved=slots)
    for slot, pr in enumerate(prompts):
        eng.admit(st_, params, pr, slot, blocks=alloc.allocate(max_len))
    return eng, st_


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_engine_kernel_matches_gather_and_contiguous(family):
    """Tokens from the paged-kernel engine are identical to the paged
    gather path and the contiguous layout — chunked AND speculative — on
    all three cache families, each through ONE decode / draft / verify
    executable."""
    cfg, params = _setup(FAMILIES[family])
    prompts = [np.arange(1, 6) % cfg.vocab_size,
               np.arange(3, 10) % cfg.vocab_size]
    slots, max_len, bs, chunk = 2, 32, 8, 4
    forced = np.zeros((slots, chunk), np.int32)
    flen = np.zeros((slots,), np.int32)
    rng = jax.random.PRNGKey(1)

    ceng = DecodeEngine(cfg, impl="dense")
    cst = ceng.new_batch_state(slots, max_len)
    for slot, pr in enumerate(prompts):
        ceng.admit(cst, params, pr, slot)
    ref_toks = ceng.decode_chunk(cst, params, forced, flen, rng)

    out = {}
    for name, kw in (("gather", {}), ("kernel", {"paged_kernel": True})):
        eng, st_ = _paged_engine(cfg, params, prompts, max_len, bs, **kw)
        toks = [eng.decode_chunk(st_, params, forced, flen, rng)]
        toks.append(eng.decode_chunk(st_, params, forced, flen, rng))
        g, _, n = eng.spec_chunk(st_, params, 2)
        out[name] = (np.concatenate(toks, 1),
                     np.where(np.arange(2)[None] < n[:, None], g, -1))
        assert eng.decode_compiles == 1
        assert eng.draft_compiles == 1 and eng.verify_compiles == 1
    assert np.array_equal(out["gather"][0][:, :chunk], ref_toks)
    for a, b in zip(out["gather"], out["kernel"]):
        assert np.array_equal(a, b)


def _pool_leaves(cache):
    pools = []

    def grab(d, stacked):
        if isinstance(d, dict) and "pk" in d:
            pools.extend([d["pk"], d["pv"], d["ppos"]])

    _walk_cache(grab, cache)
    return pools


def test_chunk_exec_donates_kv_pool():
    """The chunk executable donates the cache operand: after a decode
    chunk the previous pool buffers are deleted (donated into the new
    cache) and exactly one pool-shaped copy is live — peak memory holds
    ONE pool, not input + output."""
    cfg, params = _setup(FAMILIES["dense"])
    prompts = [np.arange(1, 6) % cfg.vocab_size]
    eng, st_ = _paged_engine(cfg, params, prompts, 32, 8,
                             paged_kernel=True)
    old = _pool_leaves(st_.cache)
    assert old and not any(a.is_deleted() for a in old)
    eng.decode_chunk(st_, params, np.zeros((1, 4), np.int32),
                     np.zeros((1,), np.int32), jax.random.PRNGKey(0))
    assert all(a.is_deleted() for a in old)
    new = _pool_leaves(st_.cache)
    gc.collect()
    shapes = {a.shape for a in new}
    live = Counter(a.shape for a in jax.live_arrays()
                   if a.shape in shapes and not a.is_deleted())
    assert live == Counter(a.shape for a in new)


def test_device_table_cached_across_chunks():
    """The block table uploads host→device once and is reused across
    chunks; admission (and any ``mark_table_dirty``) invalidates it so
    the next chunk re-uploads."""
    cfg, params = _setup(FAMILIES["dense"])
    slots, max_len, bs = 2, 32, 8
    nb = max_len // bs
    eng = DecodeEngine(cfg, impl="dense", paged_kernel=True)
    st_ = eng.new_batch_state(slots, max_len, block_size=bs)
    alloc = BlockAllocator(slots * (nb + 1), bs, reserved=slots)
    eng.admit(st_, params, np.arange(1, 6), 0, blocks=alloc.allocate(16))
    args = (params, np.zeros((slots, 4), np.int32),
            np.zeros((slots,), np.int32), jax.random.PRNGKey(0))
    eng.decode_chunk(st_, *args)
    assert st_.table_uploads == 1
    dev = st_.device_table()
    eng.decode_chunk(st_, *args)
    eng.spec_chunk(st_, params, 2)
    assert st_.table_uploads == 1             # cached copy reused
    assert st_.device_table() is dev
    eng.admit(st_, params, np.arange(2, 9), 1, blocks=alloc.allocate(16))
    eng.decode_chunk(st_, *args)
    assert st_.table_uploads == 2             # admission invalidated it


# ---------------------------------------------------------------------------
# O(reserved-blocks) paged admission
# ---------------------------------------------------------------------------


def test_paged_admission_cost_is_o_reserved():
    """The admission scatter's compiled cost is O(touched blocks), not
    O(pool): with the pool far larger than the reservation, bytes
    accessed stay far below the pool size (the donated dst updates in
    place)."""
    L, NB, bs, H, D, nr, nb = 2, 128, 8, 2, 4, 2, 2
    dst = {"stack": [{
        "pk": jnp.zeros((L, NB, bs, H, D)),
        "pv": jnp.zeros((L, NB, bs, H, D)),
        "ppos": jnp.full((L, NB, bs), -1, jnp.int32)}]}
    src = {"stack": [{
        "k": jnp.ones((L, 1, nb * bs, H, D)),
        "v": jnp.ones((L, 1, nb * bs, H, D)),
        "pos": jnp.zeros((L, 1, nb * bs), jnp.int32)}]}
    compiled = _scatter_slot_paged_jit.lower(
        dst, src, jnp.asarray(0, jnp.int32),
        jnp.arange(nr, dtype=jnp.int32), bs).compile()
    ca = compiled.cost_analysis()
    pool_bytes = sum(int(a.nbytes) for a in jax.tree.leaves(dst))
    assert float(ca["bytes accessed"]) < pool_bytes / 8


def _pool_rows(cache, block_ids):
    """Per pool leaf, the rows for ``block_ids`` (axis 1 when the leaf
    carries the stacked scan axis, axis 0 otherwise)."""
    rows = []

    def grab(d, stacked):
        if isinstance(d, dict) and "pk" in d:
            ax = 1 if stacked else 0
            for leaf in (d["pk"], d["pv"], d["ppos"]):
                rows.append(np.take(np.asarray(leaf), block_ids, axis=ax))

    _walk_cache(grab, cache)
    return rows


def test_paged_admission_touches_only_reserved_blocks():
    """Admitting into one slot leaves every other slot's pool blocks
    bit-identical, and wipes the new slot's scratch-block positions
    (poisoned by the empty slot's lockstep garbage decode) to -1."""
    cfg, params = _setup(FAMILIES["dense"])
    slots, max_len, bs = 2, 32, 8
    nb = max_len // bs
    eng = DecodeEngine(cfg, impl="dense", paged_kernel=True)
    st_ = eng.new_batch_state(slots, max_len, block_size=bs)
    alloc = BlockAllocator(slots * (nb + 1), bs, reserved=slots)
    b0 = np.asarray(alloc.allocate(max_len))
    eng.admit(st_, params, np.arange(1, 6), 0, blocks=b0)
    # slot 1 is empty: the lockstep garbage decode writes real positions
    # into its scratch block (pool row 1)
    eng.decode_chunk(st_, params, np.zeros((slots, 4), np.int32),
                     np.zeros((slots,), np.int32), jax.random.PRNGKey(0))
    scratch = _pool_rows(st_.cache, [1])
    assert any((p >= 0).any() for p in scratch[2::3])     # poisoned
    before = _pool_rows(st_.cache, b0)

    eng.admit(st_, params, np.arange(2, 9), 1, blocks=alloc.allocate(16))
    for old, new in zip(before, _pool_rows(st_.cache, b0)):
        assert np.array_equal(old, new)
    for p in _pool_rows(st_.cache, [1])[2::3]:
        assert (p == -1).all()                            # scratch wiped
