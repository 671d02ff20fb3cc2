"""Serving subsystem (repro.serve): golden decode parity, compile
discipline, continuous-batching mechanics, and fault-routed replicas.

``tests/golden/serve_greedy.npz`` holds the greedy tokens of the
pre-refactor host-side decode loop (``_host_loop_generate``: prefill, then
one jitted ``decode_step`` dispatch per token) for the three architecture
families — the scan-fused engine must reproduce them bit-for-bit, and so
must that loop, run alongside.  The params come from ``jax.random``, so
the golden pins the PRNG implementation too: regenerate it after a JAX
upgrade that changes the draws with ``PYTHONPATH=src python
tests/test_serve.py``."""

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import Scenario, get_arch, reduced
from repro.models import transformer as tf
from repro.serve import (BlockAllocator, DecodeEngine, FaultRoutedServer,
                         Request, ServeParams, SimEngine, SlotScheduler,
                         PendingWork, acceptance_rate, bursty_trace,
                         get_engine, latency_percentiles, output_agreement,
                         slo_attainment, synthetic_requests)
from repro.sim import get_scenario

GOLDEN = os.path.join(os.path.dirname(__file__), "golden",
                      "serve_greedy.npz")
FAMILIES = {"dense": "gemma3-12b", "ssm": "mamba2-370m",
            "hybrid": "recurrentgemma-2b"}


def _setup(arch):
    cfg = reduced(get_arch(arch))
    params, _ = tf.init_params(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _host_loop_generate(params, cfg, prompts, gen):
    """The pre-refactor greedy decode: one dispatch per token."""
    s0 = prompts.shape[1]
    logits, cache = tf.prefill(params, cfg, prompts, max_len=s0 + gen,
                               impl="dense")
    decode = jax.jit(lambda p, t, c, pos: tf.decode_step(p, cfg, t, c, pos))
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    out = [tok]
    for t in range(gen - 1):
        logits, cache = decode(params, tok, cache, jnp.asarray(s0 + t))
        tok = jnp.argmax(logits[:, 0], axis=-1)[:, None].astype(jnp.int32)
        out.append(tok)
    return jnp.concatenate(out, axis=1)


# ---------------------------------------------------------------------------
# golden parity + compile discipline
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_scan_engine_matches_legacy_loop_golden(family):
    """Bit-for-bit greedy parity with the pre-refactor Python decode loop,
    across the dense / SSM / hybrid cache families."""
    g = np.load(GOLDEN)
    cfg, params = _setup(FAMILIES[family])
    eng = DecodeEngine(cfg, impl="dense")
    prompts = jnp.asarray(g[f"{family}_prompts"])
    toks = eng.generate(params, prompts, int(g["gen"]))
    loop = _host_loop_generate(params, cfg, prompts, int(g["gen"]))
    np.testing.assert_array_equal(np.asarray(toks), np.asarray(loop))
    np.testing.assert_array_equal(np.asarray(toks), g[f"{family}_tokens"])


def test_generate_compiles_once_across_calls():
    """Regression for the legacy per-call recompilation bug: a fresh
    ``jax.jit(lambda ...)`` was created inside every ``generate()`` call.
    Two same-shape calls must share ONE AOT-compiled prefill and ONE
    scan-fused decode executable (AOT executables cannot retrace)."""
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense")
    prompts = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0,
                                 cfg.vocab_size)
    a = eng.generate(params, prompts, 6)
    b = eng.generate(params, prompts, 6)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert eng.decode_compiles == 1
    assert eng.prefill_compiles == 1


def test_get_engine_is_process_cached():
    cfg, _ = _setup("mamba2-370m")
    assert get_engine(cfg, impl="dense") is get_engine(cfg, impl="dense")
    assert get_engine(cfg, impl="dense") is not get_engine(
        cfg, impl="dense", cuts=(cfg.period,))


def test_launch_serve_generate_delegates_to_engine():
    """The public ``launch/serve.py::generate`` shim reuses the cached
    engine — repeated calls add no compiles."""
    from repro.launch.serve import generate
    cfg, params = _setup("mamba2-370m")
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 10), 0,
                                 cfg.vocab_size)
    a = generate(params, cfg, prompts, 5, impl="dense")
    eng = get_engine(cfg, impl="dense")
    d0, p0 = eng.decode_compiles, eng.prefill_compiles
    b = generate(params, cfg, prompts, 5, impl="dense")
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert (eng.decode_compiles, eng.prefill_compiles) == (d0, p0)


# ---------------------------------------------------------------------------
# split-mode serving
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_split_mode_generate_matches_merged(family):
    """Serving through the client→edge→server pipeline stages produces the
    merged model's tokens exactly (stage boundaries only move activations
    across hops)."""
    cfg, params = _setup(FAMILIES[family])
    prompts = jax.random.randint(jax.random.PRNGKey(3), (2, 12), 0,
                                 cfg.vocab_size)
    merged = DecodeEngine(cfg, impl="dense")
    split = DecodeEngine(cfg, impl="dense", cuts=(cfg.period,))
    np.testing.assert_array_equal(
        np.asarray(merged.generate(params, prompts, 6)),
        np.asarray(split.generate(params, prompts, 6)))
    assert split.num_hops == 1 and split.num_stages == 2


def test_split_decode_step_equals_merged_decode_step():
    """Model-layer invariant: partitioned decode (params + cache cut at
    the WSSL layers) is the merged ``decode_step``, bitwise."""
    cfg, params = _setup("recurrentgemma-2b")
    b, s0 = 2, 10
    tokens = jax.random.randint(jax.random.PRNGKey(4), (b, s0), 0,
                                cfg.vocab_size)
    logits, cache = tf.prefill(params, cfg, tokens, max_len=s0 + 2,
                               impl="dense")
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    want, merged_cache = tf.decode_step(params, cfg, tok, cache,
                                        jnp.asarray(s0))
    cuts = (cfg.period,)
    got, stage_caches = tf.split_decode_step(
        tf.partition_params(params, cfg, cuts), cfg, tok,
        tf.partition_cache(cache, cfg, cuts), jnp.asarray(s0))
    np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
    for a, b_ in zip(jax.tree.leaves(merged_cache),
                     jax.tree.leaves(tf.join_cache_stages(stage_caches))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))


def test_per_row_positions_match_scalar_positions():
    """A (B,) position vector with equal entries is the scalar decode,
    bitwise — the mixed-length serving path rests on this."""
    cfg, params = _setup("gemma3-12b")
    tokens = jax.random.randint(jax.random.PRNGKey(5), (3, 8), 0,
                                cfg.vocab_size)
    _, cache = tf.prefill(params, cfg, tokens, max_len=10, impl="dense")
    tok = tokens[:, -1:]
    a, _ = tf.decode_step(params, cfg, tok, cache, jnp.asarray(8))
    b, _ = tf.decode_step(params, cfg, tok, cache,
                          jnp.full((3,), 8, jnp.int32))
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# scheduler mechanics
# ---------------------------------------------------------------------------


def _mk_requests(cfg, n, lens, gens):
    reqs = []
    for rid in range(n):
        prompt = np.asarray(jax.random.randint(
            jax.random.PRNGKey(100 + rid), (lens[rid % len(lens)],), 0,
            cfg.vocab_size))
        reqs.append(Request(rid=rid, prompt=prompt,
                            max_new=gens[rid % len(gens)]))
    return reqs


def test_slot_scheduler_admission_eviction_mechanics():
    sched = SlotScheduler(2)
    reqs = [Request(rid=i, prompt=np.arange(4), max_new=3)
            for i in range(4)]
    for r in reqs:
        sched.submit(PendingWork(r))
    # FIFO fill of the two slots
    admitted = list(sched.admissions())
    assert [(s, w.req.rid) for s, w in admitted] == [(0, 0), (1, 1)]
    for slot, work in admitted:
        sched.activate(slot, work, first_token=7)
    assert sched.num_active == 2 and len(sched.queue) == 2
    # no free slot -> no admissions
    assert list(sched.admissions()) == []
    # a chunk that completes slot 0 (max_new=3: prefill token + 2 more)
    toks = np.array([[1, 2, 3], [4, 5, 6]], np.int32)
    finished, credited = sched.credit_chunk(toks)
    assert [s for s, _ in finished] == [0, 1]
    assert credited == 4                 # 2 new tokens credited per slot
    for slot, active in finished:
        assert active.done == [7, toks[slot, 0], toks[slot, 1]]
        sched.release(slot)
    # freed slots admit the remaining queue
    assert [(s, w.req.rid) for s, w in sched.admissions()] == [(0, 2),
                                                               (1, 3)]


def test_scheduler_replay_buffers():
    """Re-routed work replays its credited tokens through the forced lane
    without re-crediting them."""
    sched = SlotScheduler(1)
    req = Request(rid=0, prompt=np.arange(4), max_new=5)
    work = PendingWork(req, done=[9, 8, 7])        # re-routed mid-flight
    sched.submit(work)
    ((slot, w),) = list(sched.admissions())
    active = sched.activate(slot, w, first_token=9)   # re-derived token
    assert active.replay == [8, 7] and active.done == [9, 8, 7]
    forced, force_len = sched.force_buffers(chunk=4)
    assert forced[0, :2].tolist() == [8, 7] and force_len[0] == 2
    # chunk emits the replayed pair then two fresh tokens; only the fresh
    # pair is credited
    finished, credited = sched.credit_chunk(np.array([[8, 7, 5, 4]],
                                                     np.int32))
    assert finished and finished[0][1].done == [9, 8, 7, 5, 4]
    assert credited == 2


def test_continuous_batching_matches_solo_generation():
    """Mixed prompt/gen lengths through the slot scheduler produce exactly
    the tokens each request gets when served alone."""
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense")
    reqs = _mk_requests(cfg, 5, lens=[8, 11, 14], gens=[4, 6, 7])
    sp = ServeParams(replicas=1, slots=2, chunk=4, max_len=32, seed=0)
    report = FaultRoutedServer(eng, params, sp).run(reqs)
    assert sorted(report.outputs) == [r.rid for r in reqs]
    for r in reqs:
        solo = np.asarray(eng.generate(
            params, jnp.asarray(r.prompt)[None], r.max_new))[0]
        np.testing.assert_array_equal(solo,
                                      np.asarray(report.outputs[r.rid]),
                                      err_msg=f"rid={r.rid}")


# ---------------------------------------------------------------------------
# fault-routed serving
# ---------------------------------------------------------------------------


def _run_scenarios(eng, cfg, params, names, sp=None):
    reqs = _mk_requests(cfg, 6, lens=[8, 12, 10], gens=[4, 6, 5])
    sp = sp or ServeParams(replicas=2, slots=2, chunk=4, max_len=32, seed=3)
    return {name: FaultRoutedServer(eng, params, sp,
                                    scenario=get_scenario(name)).run(reqs)
            for name in names}


def test_one_decode_executable_across_fault_scenarios():
    """Scenarios steer host-side routing and the simulated clock only —
    clean, dropped-replica, and slow-host serving all share ONE scan-fused
    decode executable (the serving analog of the one-executable rounds)."""
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense")
    reports = _run_scenarios(eng, cfg, params,
                             ["clean", "replica-drop", "slow-host"])
    assert eng.decode_compiles == 1
    for name, rep in reports.items():
        assert sorted(rep.outputs) == list(range(6)), name


def test_degraded_mode_agreement_under_dropped_replica():
    """Dropped replicas re-route + re-prefill + replay: every request must
    still complete with exactly the clean run's tokens, and the recovery
    traffic must land in the sync column."""
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense")
    reports = _run_scenarios(eng, cfg, params, ["clean", "replica-drop"])
    clean, dropped = reports["clean"], reports["replica-drop"]
    ag = output_agreement(clean.outputs, dropped.outputs)
    assert ag == {"exact": 1.0, "token": 1.0, "answered": 1.0}
    assert dropped.reroutes > 0, \
        "replica-drop at p=0.25 over >=4 ticks must re-route something"
    assert dropped.log.summary()["sync_MB"] > 0.0
    assert clean.reroutes == 0
    assert clean.log.summary()["sync_MB"] == 0.0


def test_slow_host_inflates_tail_latency():
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense")
    reports = _run_scenarios(eng, cfg, params, ["clean", "slow-host"])
    assert (reports["slow-host"].percentiles["p99"]
            > reports["clean"].percentiles["p99"])
    # slow hosts delay but never corrupt
    ag = output_agreement(reports["clean"].outputs,
                          reports["slow-host"].outputs)
    assert ag["exact"] == 1.0


def test_split_mode_serving_accounts_hop_bytes():
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense", cuts=(cfg.period,))
    reports = _run_scenarios(eng, cfg, params, ["clean"])
    summary = reports["clean"].log.summary()
    assert summary["hop0_MB"] > 0.0
    assert reports["clean"].log.num_hops == 1


def test_engine_rejects_oversized_prompt_and_missing_rng():
    """Guards: a prompt that cannot fit the cache must raise (a silent KV
    ring wrap would overwrite the prompt and corrupt outputs), and
    sampling without an rng key must raise instead of silently reusing a
    fixed key."""
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense")
    state = eng.new_batch_state(slots=2, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.admit(state, params, np.arange(8), slot=0)
    with pytest.raises(ValueError, match="rng"):
        eng.generate(params, np.zeros((1, 4), np.int32), 4,
                     temperature=1.0)
    sp = ServeParams(replicas=1, slots=1, chunk=4, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        FaultRoutedServer(eng, params, sp).run(
            [Request(rid=0, prompt=np.arange(6), max_new=4)])


def test_executable_cache_keys_on_all_cache_leaves():
    """Two cache trees whose FIRST leaf shape coincides (a saturated local
    ring) but whose global-KV size differs must not collide in the
    executable cache."""
    cfg, params = _setup("gemma3-12b")        # local(window=64) + global
    eng = DecodeEngine(cfg, impl="dense")
    prompts = jax.random.randint(jax.random.PRNGKey(7), (2, 48), 0,
                                 cfg.vocab_size)
    a = eng.generate(params, prompts, 32)     # max_len 80: ring 64, kv 80
    b = eng.generate(params, prompts, 64)     # max_len 112: ring 64, kv 112
    assert eng.decode_compiles == 2
    np.testing.assert_array_equal(np.asarray(a[:, :1]),
                                  np.asarray(b[:, :1]))


def test_staggered_arrivals_gate_admission():
    """A request must not be served before its arrival time, and latencies
    are measured from arrival (never negative)."""
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense")
    reqs = synthetic_requests(cfg, 4, prompt_len=10, gen=4, seed=2,
                              arrival_spacing=12.0)
    sp = ServeParams(replicas=1, slots=2, chunk=4, max_len=32)
    report = FaultRoutedServer(eng, params, sp).run(reqs)
    assert sorted(report.outputs) == [0, 1, 2, 3]
    for r in reqs:
        assert report.latencies[r.rid] > 0.0
    # the last request (arrival 36) cannot finish before its arrival
    assert report.sim_time >= 36.0


def test_reroute_sync_bytes_charged_once_per_reprefill():
    """A re-routed request is charged sync bytes when it is actually
    re-prefilled (at re-admission), and the clean run charges nothing."""
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, impl="dense")
    reports = _run_scenarios(eng, cfg, params, ["clean", "replica-drop"])
    dropped = reports["replica-drop"]
    # every charged byte corresponds to a re-admission of re-routed work:
    # sync totals must be bounded by reroutes × the largest possible
    # (prompt + credited) re-ship
    max_ship = max(r.prompt_len + r.max_new
                   for r in _mk_requests(cfg, 6, lens=[8, 12, 10],
                                         gens=[4, 6, 5])) * 4
    total_sync = sum(t.bytes_sync for t in dropped.log.ticks)
    assert 0 < total_sync <= dropped.reroutes * max_ship
    assert sum(t.bytes_sync for t in reports["clean"].log.ticks) == 0


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_latency_percentiles():
    pct = latency_percentiles(list(range(1, 101)))
    assert pct["p50"] == pytest.approx(50.5)
    assert pct["p99"] == pytest.approx(99.01)
    assert latency_percentiles([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                                       "mean": 0.0, "max": 0.0}


def test_output_agreement_metric():
    ref = {0: [1, 2, 3], 1: [4, 5]}
    assert output_agreement(ref, ref)["exact"] == 1.0
    got = {0: [1, 2, 9], 1: [4, 5]}
    ag = output_agreement(ref, got)
    assert ag["exact"] == 0.5 and ag["answered"] == 1.0
    assert ag["token"] == pytest.approx((2 / 3 + 1.0) / 2)
    missing = output_agreement(ref, {0: [1, 2, 3]})
    assert missing["answered"] == 0.5 and missing["exact"] == 0.5
    # truncated answers are penalized by the longer stream's length
    trunc = output_agreement(ref, {0: [1, 2], 1: [4, 5]})
    assert trunc["token"] == pytest.approx((2 / 3 + 1.0) / 2)


def test_slo_attainment_metric():
    deadlines = {0: 10.0, 1: 20.0, 2: 5.0, 3: math.inf}
    completions = {0: 8.0, 1: 25.0, 3: 99.0}   # 2 never completed
    slo = slo_attainment(deadlines, completions)
    assert slo == {"attainment": pytest.approx(1 / 3), "on_time": 1.0,
                   "late": 1.0, "missed": 1.0}
    # no finite deadlines -> vacuous attainment
    assert slo_attainment({0: math.inf}, {})["attainment"] == 1.0
    assert acceptance_rate(3, 4) == 0.75 and acceptance_rate(0, 0) == 0.0


# ---------------------------------------------------------------------------
# paged KV slots
# ---------------------------------------------------------------------------


def _serve_tokens(eng, params, prompts, gens, max_len, *, block_size=0,
                  spec=0):
    """Minimal slot loop: admit every prompt, decode until each request's
    budget drains — the per-request token streams, engine-level."""
    slots = len(prompts)
    alloc = None
    if block_size:
        nb = max_len // block_size
        alloc = BlockAllocator(slots * (nb + 1), block_size, reserved=slots)
        st = eng.new_batch_state(slots, max_len, block_size=block_size)
    else:
        st = eng.new_batch_state(slots, max_len)
    outs = []
    for i, p in enumerate(prompts):
        blocks = None
        if alloc is not None:
            blocks = alloc.allocate(
                min(len(p) + gens[i] + max(8, spec), max_len))
        outs.append([eng.admit(st, params, p, i, blocks=blocks)])
    need = [g - 1 for g in gens]
    rng = jax.random.PRNGKey(0)
    while any(n > 0 for n in need):
        if spec:
            toks, _, cnt = eng.spec_chunk(st, params, spec)
            steps = [min(int(cnt[i]), need[i]) for i in range(slots)]
        else:
            toks = eng.decode_chunk(st, params,
                                    np.zeros((slots, 4), np.int32),
                                    np.zeros((slots,), np.int32), rng)
            steps = [min(4, need[i]) for i in range(slots)]
        for i in range(slots):
            outs[i].extend(int(t) for t in toks[i, :steps[i]])
            need[i] -= steps[i]
    return outs


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_paged_kv_matches_contiguous(family):
    """Block-pooled global-attention KV reproduces the contiguous layout
    bit-for-bit across the dense / SSM / hybrid cache families (table rows
    in logical order make the gathered view layout-identical)."""
    cfg, params = _setup(FAMILIES[family])
    prompts = [np.arange(1, 7) % cfg.vocab_size,
               (np.arange(3, 15) * 5) % cfg.vocab_size]
    gens = [10, 7]
    ref = _serve_tokens(DecodeEngine(cfg), params, prompts, gens, 32)
    pag = _serve_tokens(DecodeEngine(cfg), params, prompts, gens, 32,
                        block_size=8)
    assert ref == pag


def test_paged_engine_reproduces_golden():
    """The paged decode path reproduces the pre-refactor golden greedy
    tokens — paging changes storage, never values."""
    g = np.load(GOLDEN)
    cfg, params = _setup(FAMILIES["dense"])
    prompts = [np.asarray(p) for p in g["dense_prompts"]]
    gens = [int(g["gen"])] * len(prompts)
    outs = _serve_tokens(DecodeEngine(cfg), params, prompts, gens, 56,
                         block_size=8)
    np.testing.assert_array_equal(np.asarray(outs), g["dense_tokens"])


def test_block_allocator_reclaim_and_reuse():
    a = BlockAllocator(10, 4, reserved=2)
    assert a.capacity == 8 and a.free_blocks == 8
    assert a.blocks_for(9) == 3 and a.blocks_for(8) == 2
    first = a.allocate(9)          # 3 blocks, ids in logical order
    assert len(first) == 3 and a.blocks_in_use == 3
    assert all(b >= 2 for b in first)     # never hands out scratch ids
    rest = a.allocate(20)          # the remaining 5
    assert a.free_blocks == 0 and not a.can_fit(1)
    with pytest.raises(RuntimeError, match="exhausted"):
        a.allocate(1)
    a.free(first)
    assert a.free_blocks == 3 and a.can_fit(12) and not a.can_fit(13)
    with pytest.raises(RuntimeError, match="double"):
        a.free([first[0]])
    reused = a.allocate(12)        # reclaimed blocks circulate
    assert sorted(reused) == sorted(first)
    assert a.peak_in_use == 8
    a.reset()
    assert a.free_blocks == 8 and a.blocks_in_use == 0
    del rest


def test_scheduler_reserves_and_frees_blocks():
    """Admission reserves worst-case blocks, release returns them, drain
    resets the pool; the pool (not slots) is the binding capacity axis."""
    alloc = BlockAllocator(6, 8, reserved=1)   # 5 allocatable
    sched = SlotScheduler(2, allocator=alloc, reserve_margin=4,
                          max_reserve=40)
    for rid in range(3):
        sched.submit(PendingWork(Request(rid=rid, prompt=np.arange(10),
                                         max_new=10)))
    admitted = list(sched.admissions())
    # each request needs ceil((10+10+4)/8) = 3 blocks: only ONE fits the
    # 5-block pool — head-of-line blocks the second despite a free slot
    assert len(admitted) == 1
    (slot, work) = admitted[0]
    assert len(work.blocks) == 3 and alloc.free_blocks == 2
    sched.activate(slot, work, first_token=1)
    sched.release(slot)
    assert work.blocks is None and alloc.free_blocks == 5
    # freed blocks admit the next request
    admitted = list(sched.admissions())
    assert len(admitted) == 1
    sched.activate(admitted[0][0], admitted[0][1], first_token=1)
    assert alloc.free_blocks == 2
    moved = sched.drain()
    assert alloc.free_blocks == 5
    assert all(w.blocks is None for w in moved)


def test_edf_admission_order():
    """Earliest deadline first, FIFO among equal (and infinite) deadlines."""
    sched = SlotScheduler(1)
    rids_deadlines = [(0, math.inf), (1, 50.0), (2, 10.0), (3, 50.0)]
    for rid, dl in rids_deadlines:
        sched.submit(PendingWork(Request(rid=rid, prompt=np.arange(4),
                                         max_new=2, deadline=dl)))
    order = []
    while sched.queue:
        ((slot, work),) = list(sched.admissions())
        order.append(work.req.rid)
        sched.activate(slot, work, first_token=0)
        sched.release(slot)
    assert order == [2, 1, 3, 0]


# ---------------------------------------------------------------------------
# self-drafting speculative decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_speculative_decode_matches_greedy(family):
    """Draft with the client stage, verify with one fused chunk, accept by
    longest prefix: the emitted stream is greedy decoding, bit-for-bit,
    across all three cache families (exact rollback of rejected drafts)."""
    cfg, params = _setup(FAMILIES[family])
    prompts = [np.arange(1, 7) % cfg.vocab_size,
               (np.arange(3, 15) * 5) % cfg.vocab_size]
    gens = [10, 7]
    ref = _serve_tokens(DecodeEngine(cfg), params, prompts, gens, 32)
    eng = DecodeEngine(cfg)
    spc = _serve_tokens(eng, params, prompts, gens, 32, spec=4)
    assert ref == spc
    assert eng.draft_compiles == 1 and eng.verify_compiles == 1
    assert 0.0 < eng.draft_fraction < 1.0


def test_speculative_ring_wrap_rollback():
    """The hard rollback case: a windowed (ring) KV smaller than max_len,
    where a rejected draft's write can WRAP and evict a still-visible valid
    entry — per-step overwritten-line snapshots must restore it exactly."""
    cfg, params = _setup("gemma3-12b")         # local window 64 < max_len 80
    prompts = [(np.arange(2, 60) * 3) % cfg.vocab_size,
               (np.arange(1, 50) * 7) % cfg.vocab_size]
    gens = [20, 18]
    ref = _serve_tokens(DecodeEngine(cfg), params, prompts, gens, 80)
    spc = _serve_tokens(DecodeEngine(cfg), params, prompts, gens, 80,
                        spec=4)
    assert ref == spc
    # paged + speculative combined on the same wrap-prone shapes
    both = _serve_tokens(DecodeEngine(cfg), params, prompts, gens, 80,
                         block_size=16, spec=4)
    assert ref == both


def test_speculative_serving_under_replica_drop_matches_clean():
    """Router-level: speculative serving under replica drops reproduces
    the plain clean run's outputs exactly (spec rounds never overlap
    replay; the replay lane rides normal chunks)."""
    cfg, params = _setup("mamba2-370m")
    reqs = _mk_requests(cfg, 6, lens=[8, 12, 10], gens=[4, 6, 5])
    sp = ServeParams(replicas=2, slots=2, chunk=4, max_len=32, seed=3)
    clean = FaultRoutedServer(DecodeEngine(cfg), params, sp).run(reqs)
    spec_sp = ServeParams(replicas=2, slots=2, chunk=4, max_len=32, seed=3,
                          speculate=True, draft_k=4)
    rep = FaultRoutedServer(DecodeEngine(cfg), params, spec_sp,
                            scenario=get_scenario("replica-drop")).run(reqs)
    assert rep.outputs == clean.outputs
    assert rep.drafted >= rep.accepted >= 0


def test_speculative_acceptance_accounting():
    """Acceptance is partial and consistently accounted (SimEngine drafts
    diverge at every position divisible by draft_divergence)."""
    reqs = bursty_trace(200, seed=5, deadline_frac=0.0)
    sp = ServeParams(replicas=2, slots=4, chunk=8, max_len=64,
                     speculate=True, draft_k=4)
    rep = FaultRoutedServer(SimEngine(), None, sp).run(reqs)
    assert rep.spec_rounds > 0
    assert 0 < rep.accepted < rep.drafted     # partial, not degenerate
    assert rep.acceptance == acceptance_rate(rep.accepted, rep.drafted)
    assert sum(t.drafted for t in rep.log.ticks) == rep.drafted
    assert sum(t.accepted for t in rep.log.ticks) == rep.accepted
    # identity with plain greedy, same trace
    plain = FaultRoutedServer(
        SimEngine(), None,
        ServeParams(replicas=2, slots=4, chunk=8, max_len=64)).run(reqs)
    assert rep.outputs == plain.outputs


# ---------------------------------------------------------------------------
# SLO-aware routing
# ---------------------------------------------------------------------------


def test_impossible_deadline_is_shed_with_explicit_outcome():
    """Provably-late work is rejected at admission — an explicit outcome,
    never a silently-late answer — and every request ends in exactly one
    of outputs / rejected."""
    reqs = bursty_trace(40, seed=7, deadline_frac=0.0)
    reqs[3] = Request(rid=3, prompt=reqs[3].prompt, max_new=reqs[3].max_new,
                      arrival=reqs[3].arrival, deadline=reqs[3].arrival + .1)
    sp = ServeParams(replicas=2, slots=2, chunk=8, max_len=64)
    rep = FaultRoutedServer(SimEngine(), None, sp).run(reqs)
    assert 3 in rep.rejected and 3 not in rep.outputs
    assert set(rep.outputs) | set(rep.rejected) == {r.rid for r in reqs}
    assert set(rep.outputs) & set(rep.rejected) == set()
    assert rep.slo["missed"] == 1.0
    assert rep.log.summary()["rejected"] == 1.0


@pytest.mark.parametrize("preset", ["slow-host", "replica-drop"])
def test_slo_shed_vs_serve_under_fault_presets(preset):
    """Under fault presets every deadline-carrying request still resolves
    to served-or-shed, attainment is well-formed, and only finite-deadline
    work is ever shed."""
    reqs = bursty_trace(400, seed=9, slack=(1.2, 8.0))
    sp = ServeParams(replicas=2, slots=4, chunk=8, max_len=64)
    rep = FaultRoutedServer(SimEngine(), None, sp,
                            scenario=get_scenario(preset)).run(reqs)
    assert rep.unfinished == 0
    assert set(rep.outputs) | set(rep.rejected) == {r.rid for r in reqs}
    assert 0.0 <= rep.slo["attainment"] <= 1.0
    by_rid = {r.rid: r for r in reqs}
    assert all(math.isfinite(by_rid[rid].deadline) for rid in rep.rejected)


def test_autoscale_absorbs_burst():
    """Queue pressure grows the fleet toward autoscale_max (and outputs
    stay identical to the fixed fleet — scaling moves capacity, not
    values); idle replicas park back down."""
    reqs = bursty_trace(600, seed=11, deadline_frac=0.0, burst_every=100,
                        burst_size=80)
    fixed = ServeParams(replicas=2, slots=4, chunk=8, max_len=64)
    auto = ServeParams(replicas=2, slots=4, chunk=8, max_len=64,
                       autoscale_max=6, scale_up_queue=4)
    rf = FaultRoutedServer(SimEngine(), None, fixed).run(reqs)
    ra = FaultRoutedServer(SimEngine(), None, auto).run(reqs)
    assert rf.peak_replicas == 2
    assert ra.peak_replicas > 2
    assert ra.outputs == rf.outputs
    assert ra.sim_time <= rf.sim_time    # extra capacity never hurts


# ---------------------------------------------------------------------------
# serving-plane bugfix regressions
# ---------------------------------------------------------------------------


def test_replayed_final_chunk_logs_hop_bytes():
    """Regression (hop-byte undercount): a final chunk whose slots ALL
    finish via replay credits zero tokens and empties every slot — but the
    chunk still crossed the wire.  The old gate (post-release occupancy OR
    credited tokens) logged zero hop bytes for it."""
    cfg, params = _setup("mamba2-370m")
    eng = DecodeEngine(cfg, cuts=(cfg.period,))     # 1 hop
    prompt = np.arange(1, 7) % cfg.vocab_size
    sp = ServeParams(replicas=1, slots=1, chunk=4, max_len=32)
    pre = FaultRoutedServer(eng, params, sp).run(
        [Request(rid=0, prompt=prompt, max_new=5)])
    done = list(pre.outputs[0])
    assert len(done) == 5
    # re-admission with 4 credited tokens to replay == exactly one chunk:
    # the slot finishes inside it, nothing new is credited
    work = PendingWork(Request(rid=0, prompt=prompt, max_new=5), done=done)
    rep = FaultRoutedServer(eng, params, sp).run([], preloaded=[(0, work)])
    t0 = rep.log.ticks[0]
    assert t0.tokens == 0                     # the old gate's blind spot
    itemsize = jnp.dtype(cfg.dtype).itemsize
    want = (1 * 4 + len(prompt)) * cfg.d_model * itemsize
    assert t0.bytes_per_hop == (want,)
    assert rep.outputs[0] == done


def test_arrival_admission_is_linear():
    """Regression (O(n²) arrival queue): admitting n requests costs O(n)
    scans of the arrival list, not O(n) pops of a python list head."""
    n = 3000
    reqs = bursty_trace(n, seed=13, deadline_frac=0.0)
    sp = ServeParams(replicas=2, slots=8, chunk=8, max_len=64,
                     max_ticks=10 * n)
    rep = FaultRoutedServer(SimEngine(), None, sp).run(reqs)
    assert rep.unfinished == 0
    assert rep.arrival_scans <= n + rep.ticks + 1


def test_max_ticks_truncation_is_reported():
    """Regression (silent truncation): hitting max_ticks used to look
    identical to a clean drain — the report must carry the unfinished
    count."""
    reqs = bursty_trace(200, seed=15, deadline_frac=0.0)
    truncated = FaultRoutedServer(
        SimEngine(), None,
        ServeParams(replicas=1, slots=2, chunk=8, max_len=64,
                    max_ticks=3)).run(reqs)
    assert truncated.unfinished > 0
    assert truncated.ticks == 3
    assert len(truncated.outputs) + truncated.unfinished >= 200
    drained = FaultRoutedServer(
        SimEngine(), None,
        ServeParams(replicas=2, slots=8, chunk=8, max_len=64)).run(reqs)
    assert drained.unfinished == 0
    assert sorted(drained.outputs) == [r.rid for r in reqs]


def _regenerate_golden():
    """Rewrite the golden tokens from the host loop (same prompts)."""
    g = dict(np.load(GOLDEN))
    for family, arch in FAMILIES.items():
        cfg, params = _setup(arch)
        g[f"{family}_tokens"] = np.asarray(_host_loop_generate(
            params, cfg, jnp.asarray(g[f"{family}_prompts"]), int(g["gen"])))
    np.savez(GOLDEN, **g)


if __name__ == "__main__":
    jax.config.update("jax_platforms", "cpu")
    _regenerate_golden()
