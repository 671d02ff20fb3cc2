#!/usr/bin/env python3
"""Device time per stage of the WSSL round in a training cell.

    python3 bench/stage_times.py --workload <name> --seed <n> --seconds <s>

One run of ``bench/run.py --trace 1`` (the same set-up, window, trace
reduction and result line, printed as run.py prints it), and besides:

* after the first checked round, the round is lowered and compiled again
  for its HLO text; JAX hands back the executable the round runs, and
  the line counts the compiles this took (none on the CPU and the v5e);
* the window's trace is kept until ``scope_reduce`` has read from it each
  stage's device self time (``wssl.*``, ``other``, ``unmapped`` and the
  ``attn_core`` cross-cut) and the offset of each round's first device op
  from its ``bench.dispatch`` span.

The last line on stdout is JSON: ``round_s``, busy and each stage in ms
per round with its share of busy, the ten ops with most self time with
their stages, the ten of ``other`` in ms per round, the dispatch offsets
and the compile counts.
"""

from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
from contextlib import contextmanager
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

import run  # noqa: E402
from common import ROOT, TRACE_DIR, BenchError, emit, load_json  # noqa: E402
from drivers import train_rounds  # noqa: E402
from scope_reduce import (OTHER, by_scope, dispatch_offsets,  # noqa: E402
                          hlo_op_paths, offset_summary, stage_of, top_ops)
from trace_reduce import read_events, reduce_events  # noqa: E402


class StageContext(run.Context):
    """``run.Context`` whose traced window also reads the stages."""

    op_paths = None
    hlo_compiles = None
    stages = None

    @contextmanager
    def window(self):
        out = TRACE_DIR / f"{self.workload['name']}.{self.seed}.stages"
        shutil.rmtree(out, ignore_errors=True)
        self.jax.profiler.start_trace(str(out))
        self.spans.tracing = True
        try:
            with self.jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self.spans.tracing = False
            self.jax.profiler.stop_trace()
        files = sorted(out.rglob("*.xplane.pb"),
                       key=lambda p: p.stat().st_mtime)
        if files:
            devices, spans = read_events(files[-1])
            self.trace_summary = reduce_events(devices, spans)
            self.stages = {
                "by_scope": by_scope(devices, spans, self.op_paths or {}),
                "other_ops": top_ops(devices, spans, self.op_paths or {},
                                     OTHER),
                "offsets": offset_summary(dispatch_offsets(devices, spans))}
        shutil.rmtree(out, ignore_errors=True)


class StageCell(train_rounds.TrainCell):
    """The training cell, taking the round's HLO text after its first
    round and adding the stage line to its output."""

    def step(self, state, batch, val):
        state, m = super().step(state, batch, val)
        ctx = self.ctx
        if ctx.op_paths is None:
            before = ctx.compiles.snapshot()
            # JAX hands back the executable the round runs: no compile
            compiled = self.round_fn._jitted.lower(
                state, batch, val, None, None, None).compile()
            ctx.op_paths = hlo_op_paths(compiled.as_text())
            after = ctx.compiles.snapshot()
            ctx.hlo_compiles = {k: after[k] - before[k] for k in after}
            ctx.note(f"round HLO: {len(ctx.op_paths)} instructions")
        return state, m

    def run(self):
        out = super().run()
        out["stages"] = stage_line(self.ctx, out)
        return out


def stage_line(ctx, out) -> dict:
    """The stages of the traced window, per round, beside busy."""
    rounds = out["readings"]["rounds"]
    summary = ctx.trace_summary or {}
    busy = summary.get("busy_s") or 0.0
    scopes = (ctx.stages or {}).get("by_scope", {})
    staged = sum(v for k, v in scopes.items() if k != "attn_core")
    paths = ctx.op_paths or {}
    return {
        "round_s": out["e2e"]["round_s"], "rounds": rounds,
        "busy_ms": 1e3 * busy / rounds,
        "stage_ms": {k: 1e3 * v / rounds for k, v in sorted(scopes.items())},
        "stage_share": {k: v / busy for k, v in sorted(scopes.items())
                        if busy},
        "stages_over_busy": staged / busy if busy else None,
        "top_ops": [[n, s, stage_of(n, paths)]
                    for n, s in summary.get("device_ops", [])],
        "other_ops": [[n, 1e3 * s / rounds] for n, s in
                      (ctx.stages or {}).get("other_ops", [])],
        "dispatch_offsets": (ctx.stages or {}).get("offsets", {}),
        "hlo_ops": len(paths), "hlo_compiles": ctx.hlo_compiles,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    try:
        bench = load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise BenchError(f"no workload {args.workload!r} in "
                             f"BENCHMARK.json")
        traffic = load_json(BENCH / "traffic"
                            / f"{cells[args.workload]['traffic']}.json")
        if traffic["driver"] != "train_rounds":
            raise BenchError(f"{args.workload} is not a training cell")
        # run_cell builds its context and cell from these two names
        run.Context = StageContext
        train_rounds.CELL = StageCell
        result, checks, out = run.run_cell(bench, cells[args.workload],
                                           args.seed, args.seconds, 1)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    gc.collect()
    emit(result, checks)
    print(json.dumps(out["stages"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
