#!/usr/bin/env python3
"""The cell benchmark.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data found by name: the workload in
``BENCHMARK.json`` names a configuration (``bench/configs/<config>.json``)
and a traffic mix (``bench/traffic/<traffic>.json``); the mix names the
driver that runs it (``bench/drivers/<driver>.py``); the limits of the
comparison that decides ``correct`` are ``bench/limits/<workload>.json``;
each per-layer metric is read by ``bench/metrics/<metric>.py``.

With ``--trace 0`` the result line carries the cell's end-to-end metrics;
with ``--trace 1`` the window is traced and it carries the per-layer
metrics, the device's busy time and the breakdown.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from contextlib import contextmanager  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from common import (ROOT, TRACE_DIR, BenchError, CompileCounter,  # noqa: E402
                    Spans, check_devices, device_record, emit, load_json,
                    load_module, place_compile_cache)


class Context:
    """What a driver gets: the cell's files, the seed and window, the
    spans and counters, and the hooks that mark set-up and the window."""

    def __init__(self, jax, devices, workload, config, traffic, limits,
                 seed, seconds, trace):
        self.jax, self.devices = jax, devices
        self.workload, self.config, self.traffic = workload, config, traffic
        self.limits, self.seed, self.seconds = limits, seed, seconds
        self.trace = trace
        self.spans = Spans(jax)
        self.compiles = CompileCounter(jax)
        self.setup_s = None
        self.setup_compiles = None
        self.trace_summary = None

    def note(self, text: str):
        print(f"{text} (at {time.perf_counter() - T_START:.3f} s)",
              file=sys.stderr, flush=True)

    def setup_done(self):
        self.setup_s = time.perf_counter() - T_START
        self.setup_compiles = self.compiles.snapshot()
        print(f"setup: {self.setup_s:.3f} s; "
              f"{self.setup_compiles['backend_compiles']} backend compiles, "
              f"{self.setup_compiles['cache_hits']} persistent-cache hits, "
              f"{self.setup_compiles['compiled']} compiled here "
              f"({self.setup_compiles['compile_s']:.3f} s in compiles)",
              file=sys.stderr, flush=True)

    def peak_bytes(self) -> int:
        return device_record(self.devices)["memory_peak_bytes"]

    @contextmanager
    def window(self):
        """The measured loop; traced (and then reduced) with --trace 1."""
        if not self.trace:
            yield
            return
        from trace_reduce import reduce_trace

        out = TRACE_DIR / f"{self.workload['name']}.{self.seed}"
        shutil.rmtree(out, ignore_errors=True)
        self.jax.profiler.start_trace(str(out))
        self.spans.tracing = True
        try:
            with self.jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            self.spans.tracing = False
            self.jax.profiler.stop_trace()
        self.trace_summary = reduce_trace(out)
        shutil.rmtree(out, ignore_errors=True)


def cell_metrics(bench: dict, name: str, key: str):
    """The metrics of ``bench[key]`` that the cell reports."""
    out = []
    e2e_names = {m["name"] for m in bench["end_to_end"]
                 if name in m.get("workloads", [name])}
    for m in bench[key]:
        if "workloads" in m:
            if name in m["workloads"]:
                out.append(m)
        elif key == "end_to_end" or m["moves"] in e2e_names:
            out.append(m)
    return out


def peak_table(kind: str) -> dict:
    peaks = load_json(BENCH / "peaks.json")["devices"]
    if kind not in peaks:
        raise BenchError(f"device kind {kind!r} is not in bench/peaks.json")
    return peaks[kind]


def run_cell(bench, workload, seed, seconds, trace, devices_fn=None):
    """One run of one cell -> (result line dict, checks, driver output)."""
    config = load_json(BENCH / "configs" / f"{workload['config']}.json")
    traffic = load_json(BENCH / "traffic" / f"{workload['traffic']}.json")
    limits = load_json(BENCH / "limits" / f"{workload['name']}.json")

    import jax
    place_compile_cache(jax)
    devices = (devices_fn or check_devices)(jax, workload["chips"])
    driver = importlib.import_module(f"drivers.{traffic['driver']}")
    ctx = Context(jax, devices, workload, config, traffic, limits, seed,
                  seconds, trace)
    ctx.note("jax and the cell's files loaded")
    cell = driver.CELL(ctx)
    ctx.note("cell built")
    out = cell.run()
    # the peak as the driver read it after the window, before the
    # reference ran
    device = dict(device_record(devices), memory_peak_bytes=out["peak_bytes"])

    metrics = {}
    if not trace:
        values = dict(out["e2e"], setup_s=ctx.setup_s)
        for m in cell_metrics(bench, workload["name"], "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        summary = ctx.trace_summary or {}
        readings = dict(out["readings"], trace=summary,
                        peak=peak_table(device["kind"]),
                        chips=len(devices))
        for m in cell_metrics(bench, workload["name"], "per_layer"):
            value = load_module("metrics", m["name"]).read(readings)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = summary.get("busy_s")
        device["window_s"] = summary.get("window_s")

    checks = out["checks"]
    result = {"correct": all(c["ok"] for c in checks),
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if trace and ctx.trace_summary:
        result["breakdown"] = {
            "device_ops": ctx.trace_summary["device_ops"],
            "idle_gaps": ctx.trace_summary["idle_gaps"]}
    return result, checks, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_json(ROOT / "BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if args.workload not in cells:
            raise BenchError(f"no workload {args.workload!r} in "
                             f"BENCHMARK.json")
        result, checks, _ = run_cell(bench, cells[args.workload], args.seed,
                                     args.seconds, args.trace)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    gc.collect()
    emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
