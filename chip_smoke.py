#!/usr/bin/env python3
"""Smoke run of the WSSL system on a TPU, through the entry points a user calls.

    python chip_smoke.py                # one chip: train, kernels, serve
    python chip_smoke.py --four-chips   # four chips: sharded vs flat round

One chip, three phases, all at mamba2-370m's published widths and depth
(48 SSD layers, d_model 1024, 32 heads x 64, state 128, vocab 50,280), with
random weights and synthetic tokens made from ``--seed``:

* train: ``init_state`` + ``make_round_fn(donate=True)`` for N=4 clients,
  k=2 selected, one warm-up round and three timed rounds at seq 1024;
* kernels: every Pallas kernel once through ``kernels/ops.py`` against its
  ``kernels/ref.py`` oracle;
* serve: ``DecodeEngine.generate`` (batch 4, prompt 256, 32 new tokens),
  and the logits decoded through the cache against the full forward.

``--four-chips`` runs only the client-axis scale-out: the round sharded
over a 4-device client mesh (``make_sharded_round_fn``) against the flat
round on one chip, same state, batch and seed, two rounds, under the bands
of docs/scaling.md.  Those bands were measured at fp32 compute, so both
rounds run at fp32 compute with fp32 matmuls, at seq 512 (the flat round's
fp32 activations at seq 1024 would nearly fill its one chip).

Each phase prints its measurements and every check with its limit.  The
last line is ``{"ok": true, "device": {...}}`` and is printed only when
every check passed; any failure exits non-zero.  Without a TPU, or outside
a checkout, the script exits non-zero before doing anything.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

ARCH = "mamba2-370m"
CLIENTS, PARTICIPATION = 4, 0.5
SEQ, ROWS, VAL_ROWS, TIMED_ROUNDS = 1024, 2, 2, 3
BATCH, PROMPT, NEW_TOKENS = 4, 256, 32
# the sharded-vs-flat bands of docs/scaling.md were measured at fp32
# compute, so the four-chip comparison runs there; fp32 activations at seq
# 1024 would leave the flat round about 1 GB of its one chip (compiled for
# a described v5e: 15.8 GB), so the sequence is cut, never a width
FOUR_CHIP_SEQ = 512
# decode-vs-forward at fp32 compute with fp32 matmuls: the tolerance of
# tests/test_archs_smoke.py::test_reduced_decode_matches_forward (logits of
# unit scale); the two paths differ only in summation order (chunked scan
# vs one-step recurrence), 2.2e-5 at 8 layers of this model on the CPU
DECODE_LOGIT_ATOL = 2e-3


def fail(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr)
    sys.exit(2)


class Checks:
    """Every check prints one line with its value and limit."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, value, ok: bool, limit: str) -> None:
        print(f"check {name}: {value} (limit: {limit}) "
              f"{'PASS' if ok else 'FAIL'}", flush=True)
        if not ok:
            self.failed.append(name)


def report(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileCounter:
    """Backend compilations seen by this process (JAX's monitoring event)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, jax):
        self.n = 0

        def on(name, secs, **kw):
            if name == self.EVENT:
                self.n += 1
        jax.monitoring.register_event_duration_secs_listener(on)


def peak_bytes(device) -> int:
    stats = device.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


def rel_err(jnp, got, want) -> float:
    """max |got - want| over max |want|: the error on the output's scale
    (the largest over the parts, where a kernel returns several)."""
    if isinstance(got, tuple):
        return max(rel_err(jnp, g, w) for g, w in zip(got, want))
    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def lm_batches(jnp, cfg, seed, rounds, n, b, s, val_rows):
    from repro.data.synthetic import lm_batch
    vd = lm_batch(val_rows, s, cfg.vocab_size, seed=seed + 10_000)
    val = {"tokens": jnp.asarray(vd["tokens"]),
           "labels": jnp.asarray(vd["labels"])}
    batches = []
    for r in range(rounds):
        d = lm_batch(n * b, s, cfg.vocab_size, seed=seed * 1000 + r)
        batches.append({"tokens": jnp.asarray(d["tokens"]).reshape(n, b, s),
                        "labels": jnp.asarray(d["labels"]).reshape(n, b, s)})
    return batches, val


def train_phase(jax, cfg, seed, check, compiles, *, seq=SEQ, rows=ROWS,
                val_rows=VAL_ROWS, timed=TIMED_ROUNDS):
    import jax.numpy as jnp
    from repro.config import TrainConfig, WSSLConfig
    from repro.core.round import init_state, make_round_fn

    w = WSSLConfig(num_clients=CLIENTS, participation_fraction=PARTICIPATION)
    t = TrainConfig(remat=True)
    k = w.num_selected()
    state, _ = init_state(jax.random.PRNGKey(seed), cfg, w, t)
    rf = make_round_fn(cfg, w, t, impl="dense", donate=True)
    batches, val = lm_batches(jnp, cfg, seed, timed + 1, CLIENTS, rows, seq,
                              val_rows)

    t0 = time.perf_counter()
    state, m = rf(state, batches[0], val)
    jax.block_until_ready((state, m))
    warm_s = time.perf_counter() - t0
    metrics = [m]
    before = compiles.n
    round_s = []
    for batch in batches[1:]:
        t0 = time.perf_counter()
        state, m = rf(state, batch, val)
        jax.block_until_ready((state, m))
        round_s.append(time.perf_counter() - t0)
        metrics.append(m)
    in_window = compiles.n - before

    losses = [float(x.loss) for x in metrics]
    selected = [float(x.mask.sum()) for x in metrics]
    finite_leaves = all(bool(jnp.isfinite(l).all())
                        for l in jax.tree.leaves(state)
                        if jnp.issubdtype(l.dtype, jnp.floating))
    tokens = CLIENTS * rows * seq
    report("train", arch=cfg.name, layers=cfg.num_layers,
           d_model=cfg.d_model, clients=CLIENTS, selected=k, seq=seq,
           rows_per_client=rows, val_rows=val_rows,
           warmup_round_s_incl_compile=warm_s, round_s=round_s,
           tokens_per_round=tokens, losses=losses,
           mean_val_loss=float(metrics[-1].val_loss.mean()),
           peak_bytes_in_use=peak_bytes(jax.devices()[0]), compiles_in_window=in_window)
    check("train.losses_finite", losses, all(map(_finite, losses)),
          "every round loss finite")
    check("train.selected", selected,
          selected[0] == CLIENTS and all(s == k for s in selected[1:]),
          f"mask.sum() == k == {k} in every timed round; the warm-up is "
          f"round 0, which selects all {CLIENTS} (Algorithm 1, line 4)")
    check("train.executables", rf.cache_size(), rf.cache_size() == 1,
          "one executable serves all rounds")
    check("train.compiles_in_window", in_window, in_window == 0,
          "no compile during the timed rounds")
    check("train.state_finite", finite_leaves, finite_leaves,
          "every state leaf finite after the last round")


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def kernel_cases(jax, cfg, timer, *, seq=1024, flash_seq=2048, leaf=None):
    """(name, limit, why, fn) per kernel: fn() -> (kernel out, oracle out).
    The default shapes are those of tests/test_tpu_compile.py.  Kernel and
    oracle are each jitted, as inside a round, so no eager temporary of a
    leaf-sized operand stays live; ``timer["kernel_s"]`` gets the seconds
    of the kernel's first call (compile included), apart from making the
    inputs and running the oracle."""
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import ops, ref

    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(keys), shape, jnp.float32).astype(dtype)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    def pair(kernel, oracle, *args):
        jax.block_until_ready(args)
        t0 = time.perf_counter()
        got = jax.block_until_ready(jax.jit(kernel)(*args))
        timer["kernel_s"] = time.perf_counter() - t0
        return got, jax.jit(oracle)(*args)

    leaf = leaf or cfg.vocab_size * cfg.d_model
    bf16 = ("bf16 output: kernel and oracle may round one bf16 ulp apart "
            "(2^-7 of the value), on top of fp32 reassociation")

    def flash(hq, hkv, hd):
        def run():
            q = normal((1, flash_seq, hq, hd), jnp.bfloat16)
            k = normal((1, flash_seq, hkv, hd), jnp.bfloat16)
            v = normal((1, flash_seq, hkv, hd), jnp.bfloat16)

            def oracle(q, k, v):
                return ref.flash_attention(*(a.transpose(0, 2, 1, 3)
                                             for a in (q, k, v))
                                           ).transpose(0, 2, 1, 3)
            return pair(ops.flash_attention, oracle, q, k, v)
        return run

    def paged(hq, hkv, hd, bs=16, nb=32, b=8):
        def run():
            rng = np.random.default_rng(0)
            pool = b * nb
            table = rng.permutation(pool).reshape(b, nb).astype(np.int32)
            pos = rng.integers(0, nb * bs, size=b).astype(np.int32)
            ppos = np.full((pool, bs), -1, np.int32)
            for row in range(b):
                for e in range(int(pos[row]) + 1):
                    ppos[table[row, e // bs], e % bs] = e
            q = normal((b, hq, hd), jnp.bfloat16)
            pk = normal((pool, bs, hkv, hd), jnp.bfloat16)
            pv = normal((pool, bs, hkv, hd), jnp.bfloat16)
            return pair(ops.paged_decode_attention,
                        ref.paged_decode_attention, q, pk, pv,
                        jnp.asarray(ppos), jnp.asarray(table),
                        jnp.asarray(pos))
        return run

    def ssd():
        h, p, n = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
        x = normal((2, seq, h, p), jnp.bfloat16)
        dt = uniform((2, seq, h), 0.01, 0.5)
        a = -uniform((h,), 0.5, 4.0)
        b_ = normal((2, seq, n), jnp.bfloat16)
        c_ = normal((2, seq, n), jnp.bfloat16)
        return pair(lambda *a_: ops.ssd_scan(*a_, chunk=cfg.ssm_chunk,
                                             block_h=8),
                    ref.ssd_scan, x, dt, a, b_, c_)

    def rg_lru():
        log_a = -uniform((2, seq, 2560), 1e-3, 0.5)
        b = normal((2, seq, 2560))
        return pair(ops.rg_lru_scan, ref.rg_lru_scan, log_a, b)

    def wavg():
        x, w = normal((CLIENTS, leaf)), uniform((CLIENTS,), 0.0, 1.0)
        return pair(ops.weighted_average, ref.weighted_average_2d, x,
                    w / w.sum())

    def fused_adam():
        p, g = normal((CLIENTS, leaf)), normal((CLIENTS, leaf))
        m, v = 0.1 * normal((CLIENTS, leaf)), uniform((CLIENTS, leaf), 0, 1)
        mask = jnp.asarray([1.0, 0.0, 1.0, 0.0], jnp.float32)
        b1, b2 = jnp.float32(0.9), jnp.float32(0.999)
        s = jnp.stack([jnp.float32(3e-4), b1, b2, 1 - b1, 1 - b2,
                       jnp.float32(1e-8), jnp.float32(0.01), 1 - b1,
                       1 - b2])
        return pair(ops.fused_adamw, ref.fused_adamw_2d, p, g, m, v, mask, s)

    def quantize():
        x, u = normal((CLIENTS, leaf)), uniform((CLIENTS, leaf), 0, 1)
        inv = 127.0 / jnp.abs(x).max(axis=1)
        got, want = pair(lambda *a: ops.quantize_stochastic(*a, 127.0),
                         lambda *a: ref.quantize_stochastic_2d(*a, 127.0),
                         x, u, inv)
        diff = jnp.abs(got.astype(jnp.int32) - want.astype(jnp.int32))
        return diff.max(), (diff > 0).mean()

    def dequantize():
        q = jax.random.randint(next(keys), (CLIENTS, leaf), -127, 128,
                               jnp.int32).astype(jnp.int8)
        step = uniform((CLIENTS,), 1e-3, 1e-2)
        return pair(ops.dequantize, ref.dequantize_2d, q, step)

    def topk():
        x, t = normal((CLIENTS, leaf)), uniform((CLIENTS,), 1.0, 2.0)
        return pair(ops.topk_mask, ref.topk_mask_2d, x, t)

    return [
        ("flash_24x64", 1e-2, bf16, flash(24, 24, 64)),
        ("flash_gqa_32/8x128", 1e-2, bf16, flash(32, 8, 128)),
        ("paged_8x128_bs16", 1e-2, bf16, paged(32, 8, 128)),
        ("paged_24x64_bs16", 1e-2, bf16, paged(24, 24, 64)),
        ("ssd_mamba2", 1e-2, bf16 + "; fp32 chunked scan vs the sequential "
         "recurrence", ssd),
        ("rg_lru_2560", 1e-5, "fp32 recurrence; exp may differ by an ulp "
         "between Mosaic and XLA, and the decay (a < 1) bounds how far that "
         "carries", rg_lru),
        ("wavg_mamba2_leaf", 1e-5, "fp32 mean of 4 rows: reassociation "
         "bound 4 x 2^-24 of the scale, with margin", wavg),
        ("fused_adam_mamba2_leaf", 1e-6, "fp32 elementwise update in the "
         "oracle's op order; division and sqrt may differ by an ulp",
         fused_adam),
        ("quantize_mamba2_leaf", None, "codes equal except where "
         "x*inv+u lands on an integer and one rounding flips the floor: "
         "|code diff| <= 1 on at most 1e-6 of the codes", quantize),
        ("dequantize_mamba2_leaf", 0.0, "one correctly rounded fp32 "
         "multiply per element: exact", dequantize),
        ("topk_mask_mamba2_leaf", 0.0, "compare and select: exact", topk),
    ]


def kernels_phase(jax, cfg, check, **shapes):
    import jax.numpy as jnp
    results, timer = {}, {}
    # oracles run with fp32 matmuls: XLA's default TPU precision would round
    # their fp32 operands to bf16 and make the oracle the worse of the two
    with jax.default_matmul_precision("highest"):
        for name, limit, why, run in kernel_cases(jax, cfg, timer,
                                                  **shapes):
            t0 = time.perf_counter()
            got, want = run()
            jax.block_until_ready((got, want))
            secs = time.perf_counter() - t0
            if limit is None:              # quantize: (max diff, share)
                worst, share = int(got), float(want)
                results[name] = {"max_code_diff": worst,
                                 "share_differing": share}
                check(f"kernels.{name}", f"max|dcode|={worst} "
                      f"share={share:.3g}", worst <= 1 and share <= 1e-6,
                      "|dcode| <= 1 on <= 1e-6 of codes: " + why)
            else:
                err = rel_err(jnp, got, want)
                results[name] = {"rel_err": err}
                check(f"kernels.{name}", f"max|err|/max|ref|={err:.3g}",
                      err <= limit, f"{limit:g}: {why}")
            results[name].update(case_s=secs,
                                 kernel_first_call_s=timer["kernel_s"])
            del got, want
            gc.collect()
    report("kernels", results=results, peak_bytes_in_use=peak_bytes(jax.devices()[0]))


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def serve_phase(jax, cfg, seed, check, *, batch=BATCH, prompt=PROMPT,
                new=NEW_TOKENS):
    import jax.numpy as jnp
    import numpy as np
    from repro.data.synthetic import make_token_stream
    from repro.models import transformer as tf
    from repro.serve import DecodeEngine

    params, _ = tf.init_params(jax.random.PRNGKey(seed), cfg)
    prompts = np.asarray(make_token_stream(batch, prompt, cfg.vocab_size,
                                           seed=seed))

    # the path launch/serve.py builds, at the registered (bf16) compute
    engine = DecodeEngine(cfg, impl="dense")
    t0 = time.perf_counter()
    toks = jax.block_until_ready(engine.generate(params, prompts, new))
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    toks = jax.block_until_ready(engine.generate(params, prompts, new))
    steady_s = time.perf_counter() - t0
    toks = np.asarray(toks)

    # decode through the cache vs the full forward, at fp32 compute with
    # fp32 matmuls (the setting of test_reduced_decode_matches_forward)
    c32 = cfg.replace(dtype="float32")
    with jax.default_matmul_precision("highest"):
        eng32 = DecodeEngine(c32, impl="dense")
        toks32 = np.asarray(eng32.generate(params, prompts, new))
        # the forward's scan needs whole chunks; causality leaves the
        # compared positions untouched by the filler behind them
        total = prompt + new
        total += (-total) % c32.ssm_chunk
        filler = make_token_stream(batch, total - prompt - new,
                                   cfg.vocab_size, seed=seed + 1)
        seq = jnp.asarray(np.concatenate([prompts, toks32, filler], 1),
                          jnp.int32)
        full = jax.jit(lambda p, t: tf.forward(p, c32, t, impl="dense",
                                               remat=False)[0])(params, seq)
        lp, cache = jax.jit(lambda p, t: tf.prefill(
            p, c32, t, max_len=prompt + new, impl="dense"))(
                params, seq[:, :prompt])
        step = jax.jit(lambda p, t, c, pos: tf.decode_step(p, c32, t, c, pos))
        errs = [float(jnp.abs(lp[:, -1] - full[:, prompt - 1]).max())]
        for pos in range(prompt, prompt + new - 1):
            lg, cache = step(params, seq[:, pos:pos + 1], cache,
                             jnp.asarray(pos))
            errs.append(float(jnp.abs(lg[:, 0] - full[:, pos]).max()))
    # greedy tokens of the fp32 engine against the forward's argmax, where
    # the top two logits are further apart than the decode band
    ref_logits = np.asarray(full[:, prompt - 1:prompt + new - 1])
    top2 = np.sort(ref_logits, axis=-1)[..., -2:]
    clear = (top2[..., 1] - top2[..., 0]) > 2 * DECODE_LOGIT_ATOL
    agree = toks32 == ref_logits.argmax(-1)

    report("serve", arch=cfg.name, batch=batch, prompt=prompt, new=new,
           generate_s_incl_compile=first_s, generate_s=steady_s,
           new_tokens_per_s=batch * new / steady_s,
           decode_compiles=engine.decode_compiles,
           prefill_compiles=engine.prefill_compiles,
           decode_logit_max_err=max(errs),
           clear_positions=int(clear.sum()),
           peak_bytes_in_use=peak_bytes(jax.devices()[0]))
    check("serve.tokens", toks.shape, toks.shape == (batch, new)
          and bool(((toks >= 0) & (toks < cfg.vocab_size)).all()),
          f"({batch}, {new}) ids in [0, vocab)")
    check("serve.decode_compiles", engine.decode_compiles,
          engine.decode_compiles == 1 and engine.prefill_compiles == 1,
          "decode and prefill compile once over two generate calls")
    check("serve.decode_vs_forward", f"max|dlogit|={max(errs):.3g}",
          max(errs) <= DECODE_LOGIT_ATOL,
          f"{DECODE_LOGIT_ATOL:g} at fp32 compute: summation order only")
    check("serve.greedy_vs_forward", f"{int(agree[clear].sum())}/"
          f"{int(clear.sum())}", bool(agree[clear].all()),
          "every greedy token equals the forward's argmax where the top-2 "
          "gap exceeds twice the decode band")


# ---------------------------------------------------------------------------
# four chips: client-axis scale-out vs the flat round
# ---------------------------------------------------------------------------


def four_chip_phase(jax, cfg, seed, check, *, seq=FOUR_CHIP_SEQ, rows=ROWS,
                    val_rows=VAL_ROWS, rounds=2, shards=4):
    import jax.numpy as jnp
    import numpy as np
    from repro.config import TrainConfig, WSSLConfig
    from repro.core.round import (init_state, make_round_fn,
                                  make_sharded_round_fn)
    from repro.launch.mesh import make_client_mesh

    # fp32 compute and matmuls (this mode runs nothing else): at bf16 the
    # two programs' different tilings round differently, and the forward
    # alone moves round 0's loss by 5e-3
    cfg = cfg.replace(dtype="float32")
    jax.config.update("jax_default_matmul_precision", "highest")
    w = WSSLConfig(num_clients=CLIENTS, participation_fraction=PARTICIPATION)
    t = TrainConfig(remat=True)
    batches, val = lm_batches(jnp, cfg, seed, rounds, CLIENTS, rows, seq,
                              val_rows)

    def run(step, state, place_batch):
        """Rounds -> (seconds, per-round metrics, per-round host copies
        of the client stack and the server params, final state)."""
        t0 = time.perf_counter()
        ms, snaps = [], []
        for batch in batches:
            state, m = step(state, place_batch(batch), val)
            # copies (np.array): the next round donates these buffers
            ms.append(jax.tree.map(np.array, m))
            snaps.append([[np.array(l) for l in jax.tree.leaves(tree)]
                          for tree in (state.client_stack,
                                       state.server_params)])
        return time.perf_counter() - t0, ms, snaps, state

    # flat round on one chip
    state, _ = init_state(jax.random.PRNGKey(seed), cfg, w, t)
    rf = make_round_fn(cfg, w, t, impl="dense", donate=True)
    flat_s, flat_m, flat_snaps, state = run(rf, state, lambda b: b)
    del state
    gc.collect()

    # the same state, batch and seed, sharded over the client mesh
    mesh = make_client_mesh(shards)
    srf = make_sharded_round_fn(cfg, w, t, mesh, impl="dense", donate=True)
    state, _ = init_state(jax.random.PRNGKey(seed), cfg, w, t)
    placed = srf.place_state(state)
    del state
    gc.collect()
    shard_s, shard_m, shard_snaps, state = run(srf, placed, srf.place_batch)

    # the client stacks really live on four devices, one client each
    layout = []
    for leaf in jax.tree.leaves(state.client_stack):
        devs = {s.device for s in leaf.addressable_shards}
        rows_each = {s.data.shape[0] for s in leaf.addressable_shards}
        layout.append((len(devs), rows_each))
    spread = all(n == shards and r == {CLIENTS // shards}
                 for n, r in layout)

    def worst(a, b):
        return max(float(np.abs(x - y).max()) for x, y in zip(a, b))

    # per round: (client stack, server params) max |flat - sharded|
    d_rounds = [[worst(f, s) for f, s in zip(fs, ss)]
                for fs, ss in zip(flat_snaps, shard_snaps)]
    d_client, d_server = d_rounds[-1]
    d_val = max(float(np.abs(f.val_loss - s.val_loss).max())
                for f, s in zip(flat_m, shard_m))
    d_loss = max(abs(float(f.loss) - float(s.loss))
                 for f, s in zip(flat_m, shard_m))
    masks_equal = all(np.array_equal(f.mask, s.mask)
                      for f, s in zip(flat_m, shard_m))
    beyond = sum(int((np.abs(x - y) > 1e-5).sum()) for x, y in
                 zip(flat_snaps[-1][0], shard_snaps[-1][0]))
    report("four_chips", arch=cfg.name, shards=shards, rounds=rounds,
           seq=seq, compute_dtype=cfg.dtype, flat_s_incl_compile=flat_s,
           sharded_s_incl_compile=shard_s,
           client_server_max_abs_diff_per_round=d_rounds,
           client_stack_coords_beyond_1e5=beyond,
           val_loss_max_abs_diff=d_val, loss_max_abs_diff=d_loss,
           flat_losses=[float(m.loss) for m in flat_m],
           sharded_losses=[float(m.loss) for m in shard_m],
           peak_bytes_in_use=[peak_bytes(d) for d in jax.devices()[:shards]])
    check("four_chips.client_stack_on_4_devices", layout[0], spread,
          f"every client-stack leaf on {shards} devices, "
          f"{CLIENTS // shards} client each")
    check("four_chips.mask", masks_equal, masks_equal,
          "exact: replicated decisions (docs/scaling.md)")
    check("four_chips.client_stack", f"max|d|={d_client:.3g}",
          d_client <= 1e-5, "1e-5: psum reassociation (docs/scaling.md)")
    check("four_chips.server_params", f"max|d|={d_server:.3g}",
          d_server <= 5e-3, "5e-3: Adam's sqrt/eps amplifies the band "
          "(docs/scaling.md)")
    check("four_chips.val_loss", f"max|d|={d_val:.3g}", d_val <= 5e-3,
          "5e-3 (docs/scaling.md)")


# ---------------------------------------------------------------------------


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded-vs-flat round on 4 chips")
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro beside {Path(__file__).name}: run it from a "
             f"checkout of the repository")
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        fail(f"no TPU found: JAX's first device is {platform!r}")
    if args.four_chips and len(devices) < 4:
        fail(f"--four-chips needs 4 TPU devices, found {len(devices)}")
    sys.path.insert(0, str(ROOT / "src"))

    from repro.config import get_arch
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    report("device", **device, jax=jax.__version__, compile_cache=cache_dir)
    cfg = get_arch(ARCH)
    check = Checks()
    compiles = CompileCounter(jax)
    t0 = time.perf_counter()
    if args.four_chips:
        four_chip_phase(jax, cfg, args.seed, check)
    else:
        train_phase(jax, cfg, args.seed, check, compiles)
        gc.collect()
        kernels_phase(jax, cfg, check)
        gc.collect()
        serve_phase(jax, cfg, args.seed, check)
    report("total", seconds=time.perf_counter() - t0,
           backend_compiles=compiles.n, failed=check.failed)
    if check.failed:
        print(f"chip_smoke: {len(check.failed)} check(s) failed: "
              f"{', '.join(check.failed)}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
