#!/usr/bin/env python3
"""The readings that a training cell's limits are set from, on the chip,
at the cell's own size.

    python bench/calibrate.py --workload <name> --seeds <n> [<n> ...] \
        [--dump <file.jsonl>]

For each seed in one process: the program's first rounds as a run drives
them (no window), then the plain float32 reference, the control (the
reference in the program's place with every product's operands rounded
to float8 e4m3, the step below the configuration's bfloat16), and the
planted faults of a training round (``FAULTS``: each client's loss over
half of its rows with every row forwarded; the first k clients trained in
place of those selected).  Each seed prints its numbers as a run compares
them, as one JSON line; the last line gives, per number, the largest
sound reading and the smallest control and fault readings.  ``--dump``
writes each seed's raw readings (per leaf and per client) as a JSON line.
The benchmark's runs never run the control or the faults.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from common import ROOT, check_devices, load_json, place_compile_cache  # noqa: E402

CONTROL = "float8_e4m3fn"
FAULTS = ("half_batch", "first_k")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--dump", type=Path,
                    help="write each seed's raw readings here")
    args = ap.parse_args()

    import jax
    from run import Context

    place_compile_cache(jax)
    bench = load_json(ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}[args.workload]
    cfg = load_json(BENCH / "configs" / f"{wl['config']}.json")
    tr = load_json(BENCH / "traffic" / f"{wl['traffic']}.json")
    # a cell being calibrated may have no limits yet
    path = BENCH / "limits" / f"{wl['name']}.json"
    limits = load_json(path) if path.is_file() else {}
    devices = check_devices(jax, wl["chips"])
    ctx = Context(jax, devices, wl, cfg, tr, limits, args.seeds[0], 0, 0)
    return train(ctx, args)


def summarise(rows):
    summary = {"max_program": {k: max(r["program"][k] for r in rows)
                               for k in rows[0]["program"]}}
    for name in ("control",) + FAULTS:
        summary[f"min_{name}"] = {k: min(r[name][k] for r in rows)
                                  for k in rows[0][name]}
    print(json.dumps(summary), flush=True)


def train(ctx, args) -> int:
    from drivers.train_rounds import TrainCell, compare

    tr, limits = ctx.traffic, ctx.limits
    floor = tr["moved_floor"]
    cell = TrainCell(ctx)
    rows = []
    dump = args.dump.open("w") if args.dump else None
    for seed in args.seeds:
        t0 = time.perf_counter()
        state, prog, key, _ = cell.checked_rounds(seed)
        del state
        gc.collect()
        t1 = time.perf_counter()
        ref = cell.reference(key, prog["masks"])
        t2 = time.perf_counter()
        row = {"seed": seed, "program_s": t1 - t0, "reference_s": t2 - t1,
               "program": {c["name"]: c["value"] for c in
                           compare(prog, ref, limits, floor)},
               "worst_leaf": {c["name"]: c["leaf"] for c in
                              compare(prog, ref, limits, floor)
                              if c["leaf"]}}
        raw = {"seed": seed, "program": prog, "reference": ref}
        others = [("control", {"round_to": CONTROL})] + [
            (f, {"fault": f}) for f in FAULTS]
        for name, kw in others:
            other = cell.reference(key, prog["masks"], **kw)
            row[name] = {c["name"]: c["value"] for c in
                         compare(other, ref, limits, floor)}
            raw[name] = other
        print(json.dumps(row), flush=True)
        if dump:
            dump.write(json.dumps(raw) + "\n")
            dump.flush()
        rows.append(row)
    if dump:
        dump.close()
    summarise(rows)
    return 0


if __name__ == "__main__":
    sys.exit(main())
