"""Serving CLI — a thin shell over the ``repro.serve`` subsystem.

Merged-model batched generation (the classic path, now scan-fused and
compiled once):

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m --reduced \
      --batch 4 --prompt-len 32 --gen 16

Fault-routed continuous batching across replicas, driven by a
``repro.sim`` scenario (see docs/serving.md):

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-370m --reduced \
      --requests 8 --replicas 2 --scenario replica-drop

``--mode split`` serves through the client→edge→server pipeline stages at
the WSSL cuts instead of the merged model (identical tokens, per-hop
activation bytes accounted).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import numpy as np

from repro.config import WSSLConfig, get_arch, reduced
from repro.data.synthetic import make_token_stream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.serve import (DecodeEngine, FaultRoutedServer, ServeParams,
                         get_engine, synthetic_requests)
from repro.sim import get_scenario


def generate(params, cfg, prompts: jax.Array, gen: int, *,
             impl: str = "dense", temperature: float = 0.0,
             rng=None):
    """Greedy / temperature batched generation.

    Backward-compatible entry point; delegates to the process-wide
    :class:`~repro.serve.DecodeEngine` so repeated calls with the same
    shapes reuse ONE compiled prefill + one scan-fused decode executable
    (the legacy version re-jitted a fresh ``decode_step`` per call)."""
    engine = get_engine(cfg, impl=impl)
    return engine.generate(params, prompts, gen, temperature=temperature,
                           rng=rng)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--impl", default="dense")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", choices=["merged", "split"], default="merged")
    ap.add_argument("--cuts", default=None,
                    help="comma-separated cut layers for --mode split "
                         "(default: the WSSL config's resolved cut)")
    # fault-routed serving (engaged by --requests > 0)
    ap.add_argument("--requests", type=int, default=0,
                    help="serve N queued requests through the fault-routed "
                         "replica router instead of one batched generate")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--scenario", default="clean")
    ap.add_argument("--block-size", type=int, default=0,
                    help="paged KV block size in tokens (0 = contiguous)")
    ap.add_argument("--pool-blocks", type=int, default=0,
                    help="paged KV pool size (0 = full residency)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="paged decode via the Pallas block-table "
                         "attention kernel instead of the gather "
                         "(needs --block-size; interpret mode on CPU)")
    ap.add_argument("--speculate", action="store_true",
                    help="self-drafting speculative decode (greedy only)")
    ap.add_argument("--draft-k", type=int, default=4)
    ap.add_argument("--deadline-slack", type=float, default=0.0,
                    help="attach deadline = arrival + ideal_latency x slack "
                         "to every request (0 = no SLOs)")
    ap.add_argument("--autoscale-max", type=int, default=0,
                    help="replica ceiling for queue-driven autoscaling "
                         "(0 = fixed fleet)")
    args = ap.parse_args()
    if args.cuts and args.mode != "split":
        ap.error("--cuts only takes effect with --mode split")
    if args.paged_kernel and not args.block_size:
        ap.error("--paged-kernel needs a paged cache (--block-size)")
    enable_compile_cache()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    params, _ = tf.init_params(jax.random.PRNGKey(args.seed), cfg)

    cuts = None
    if args.mode == "split":
        cuts = (tuple(int(c) for c in args.cuts.split(","))
                if args.cuts else WSSLConfig().resolve_cuts(cfg))
    engine = DecodeEngine(cfg, impl=args.impl, cuts=cuts,
                          paged_kernel=args.paged_kernel)

    if args.requests > 0:
        sc = get_scenario(args.scenario)
        margin = max(args.chunk, args.draft_k if args.speculate else 0)
        max_len = args.prompt_len + args.gen + margin
        if args.block_size:
            max_len += (-max_len) % args.block_size   # round to a block
        sp = ServeParams(replicas=args.replicas, slots=args.slots,
                         chunk=args.chunk, max_len=max_len,
                         seed=args.seed, block_size=args.block_size,
                         pool_blocks=args.pool_blocks,
                         speculate=args.speculate, draft_k=args.draft_k,
                         autoscale_max=args.autoscale_max)
        server = FaultRoutedServer(engine, params, sp, scenario=sc)
        reqs = synthetic_requests(cfg, args.requests,
                                  prompt_len=args.prompt_len, gen=args.gen,
                                  seed=args.seed)
        if args.deadline_slack > 0:
            reqs = [dataclasses.replace(
                r, deadline=r.arrival + (r.prompt_len * sp.prefill_unit
                                         + r.max_new) * args.deadline_slack)
                    for r in reqs]
        t0 = time.time()
        report = server.run(reqs)
        dt = time.time() - t0
        pct = report.percentiles
        print(f"arch={cfg.name} mode={args.mode} scenario={sc.name} "
              f"replicas={args.replicas} slots={args.slots}: "
              f"{report.tokens_out} tokens in {dt:.2f}s wall "
              f"({report.tokens_out / max(dt, 1e-9):.1f} tok/s), "
              f"sim_time={report.sim_time:.0f} ticks={report.ticks} "
              f"reroutes={report.reroutes} rejected={len(report.rejected)} "
              f"peak_replicas={report.peak_replicas}")
        print(f"latency p50={pct['p50']:.1f} p95={pct['p95']:.1f} "
              f"p99={pct['p99']:.1f} (decode-step units)  "
              f"compiles: decode={report.decode_compiles} "
              f"prefill={report.prefill_compiles} "
              f"draft={report.draft_compiles} "
              f"verify={report.verify_compiles}")
        if report.drafted:
            print(f"speculative: {report.spec_rounds} rounds, "
                  f"acceptance {report.acceptance:.2f} "
                  f"({report.accepted}/{report.drafted} drafts)")
        if report.slo and args.deadline_slack > 0:
            print("slo:", report.slo)
        if report.unfinished:
            print(f"WARNING: max_ticks={sp.max_ticks} hit with "
                  f"{report.unfinished} requests unfinished — the trace "
                  f"was truncated, not drained")
        print("log:", report.log.summary())
        return

    prompts = np.asarray(make_token_stream(args.batch, args.prompt_len,
                                           cfg.vocab_size, seed=args.seed))
    t0 = time.time()
    toks = engine.generate(params, prompts, args.gen)
    dt = time.time() - t0
    print(f"arch={cfg.name} mode={args.mode} batch={args.batch} "
          f"prompt={args.prompt_len} gen={args.gen}: {dt:.2f}s "
          f"({args.batch * args.gen / dt:.1f} tok/s, "
          f"compiles: decode={engine.decode_compiles} "
          f"prefill={engine.prefill_compiles})")
    print("sample continuation:", np.asarray(toks[0])[:16].tolist())


if __name__ == "__main__":
    main()
