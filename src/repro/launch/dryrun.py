import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run: lower + compile every (architecture × input shape)
on the production meshes, print memory/cost analysis, and emit the roofline
terms (EXPERIMENTS.md §Dry-run / §Roofline).

The two XLA_FLAGS lines above MUST stay the first statements in this module:
jax locks the device count at first initialization, and only the dry-run
wants 512 placeholder host devices.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2.5-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""

import argparse
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.config import (INPUT_SHAPES, ModelConfig, TrainConfig, WSSLConfig,
                          get_arch, list_archs)
from repro.core.round import abstract_state
from repro.models import transformer as tf
from repro.launch import specs as sp
from repro.launch import steps as st
from repro.launch.mesh import data_axis_size, make_production_mesh
from repro.roofline import analysis as ra
from repro.roofline import hlo_cost as hc
from repro.sharding import use_sharding_rules


def _wssl_for_mesh(mesh) -> WSSLConfig:
    return WSSLConfig(num_clients=data_axis_size(mesh))


def lower_one(arch: str, shape_name: str, *, multi_pod: bool = False,
              impl: str = "chunked", rule_overrides: Optional[Dict] = None,
              verbose: bool = True) -> Dict[str, Any]:
    """Lower + compile one (arch, shape, mesh); return the §Roofline record."""
    model_cfg = get_arch(arch)
    shape = INPUT_SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    mesh_name = "x".join(str(s) for s in mesh.devices.shape)

    rules = sp.build_rules(mesh, model_cfg, shape.kind, shape.global_batch,
                           rule_overrides)
    wssl_cfg = _wssl_for_mesh(mesh)
    train_cfg = TrainConfig()

    t0 = time.time()
    with mesh, use_sharding_rules(mesh, rules):
        if shape.kind == "train":
            state_shapes, state_axes = abstract_state(model_cfg, wssl_cfg,
                                                      train_cfg)
            batch_shapes, batch_axes = sp.batch_specs(model_cfg, shape,
                                                      wssl_cfg)
            state_sh = sp.shardings_from_axes(mesh, rules, state_axes,
                                              state_shapes)
            batch_sh = sp.shardings_from_axes(mesh, rules, batch_axes,
                                              batch_shapes)
            step = st.make_train_step(model_cfg, wssl_cfg, train_cfg, impl)
            lowered = jax.jit(step, in_shardings=(state_sh, batch_sh),
                              out_shardings=(state_sh, None),
                              donate_argnums=(0,)   # state is consumed
                              ).lower(state_shapes, batch_shapes)
        elif shape.kind == "prefill":
            param_shapes, param_axes = sp.serve_param_specs(model_cfg)
            batch_shapes, batch_axes = sp.batch_specs(model_cfg, shape)
            param_sh = sp.shardings_from_axes(mesh, rules, param_axes,
                                              param_shapes)
            batch_sh = sp.shardings_from_axes(mesh, rules, batch_axes,
                                              batch_shapes)
            step = st.make_prefill_step(model_cfg, impl)
            lowered = jax.jit(step, in_shardings=(param_sh, batch_sh)
                              ).lower(param_shapes, batch_shapes)
        else:  # decode
            param_shapes, param_axes = sp.serve_param_specs(model_cfg)
            batch_shapes, batch_axes = sp.batch_specs(model_cfg, shape)
            cache_shapes, cache_axes = sp.cache_specs(model_cfg, shape)
            param_sh = sp.shardings_from_axes(mesh, rules, param_axes,
                                              param_shapes)
            batch_sh = sp.shardings_from_axes(mesh, rules, batch_axes,
                                              batch_shapes)
            cache_sh = sp.shardings_from_axes(mesh, rules, cache_axes,
                                              cache_shapes)
            step = st.make_serve_step(model_cfg, shape)
            lowered = jax.jit(step, in_shardings=(param_sh, cache_sh,
                                                  batch_sh),
                              out_shardings=(None, cache_sh),
                              donate_argnums=(1,)   # cache updated in place
                              ).lower(param_shapes, cache_shapes,
                                      batch_shapes)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    cost = compiled.cost_analysis()
    mem = ra.summarize_memory(compiled.memory_analysis())
    hlo = compiled.as_text()
    # XLA's HloCostAnalysis counts while (scan) bodies once; the structural
    # parser applies known_trip_count multiplicities (roofline/hlo_cost.py).
    struct = hc.analyze_text(hlo)
    flops = float(struct["flops"])
    bytes_accessed = float(struct["bytes"])
    coll = {k.removeprefix("coll_"): v for k, v in struct.items()
            if k.startswith("coll_")}
    coll["weighted_total"] = struct["coll_weighted"]
    coll["count"] = hlo.count("all-reduce(") + hlo.count("all-gather(") + \
        hlo.count("reduce-scatter(") + hlo.count("all-to-all(") + \
        hlo.count("collective-permute(")

    report = ra.RooflineReport(
        arch=arch, shape=shape_name, mesh=mesh_name,
        flops_per_device=flops, bytes_per_device=bytes_accessed,
        coll_bytes_per_device=float(coll["weighted_total"]),
        model_flops_global=ra.model_flops(model_cfg, shape, wssl_cfg),
        chips=chips, coll_detail=coll, memory_per_device=mem)
    rec = report.to_dict()
    rec["xla_cost_analysis"] = {"flops_body_once": float(cost.get("flops", 0.0)),
                                "bytes_body_once": float(cost.get("bytes accessed", 0.0))}
    rec["t_lower_s"] = t_lower
    rec["t_compile_s"] = t_compile
    rec["impl"] = impl
    rec["rules"] = {k: str(v) for k, v in rules.items()}

    if verbose:
        print(f"== {arch} × {shape_name} × {mesh_name} "
              f"(lower {t_lower:.0f}s, compile {t_compile:.0f}s)")
        if mem:
            print(f"   memory/device: args={mem.get('argument_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"temp={mem.get('temp_size_in_bytes', 0)/2**30:.2f}GiB "
                  f"peak≈{mem.get('peak_estimate_bytes', 0)/2**30:.2f}GiB "
                  f"fits16GiB={mem.get('fits_16GiB')}")
        print(f"   flops/dev={flops:.3e} bytes/dev={bytes_accessed:.3e} "
              f"coll/dev={coll['weighted_total']:.3e} ({coll['count']} colls)")
        print(f"   t_comp={report.t_compute*1e3:.2f}ms t_mem={report.t_memory*1e3:.2f}ms "
              f"t_coll={report.t_collective*1e3:.2f}ms -> {report.bottleneck}-bound, "
              f"MODEL/HLO={report.model_flops_ratio:.2f} mfu_bound={report.mfu_bound:.2f}")
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(INPUT_SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--impl", default="chunked")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(INPUT_SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    os.makedirs(args.out, exist_ok=True)
    failures = []
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                tag = f"{arch}_{shape}_{'2x16x16' if mp else '16x16'}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path):
                    print(f"-- skip {tag} (exists)")
                    continue
                try:
                    rec = lower_one(arch, shape, multi_pod=mp, impl=args.impl)
                    with open(path, "w") as f:
                        json.dump(rec, f, indent=2)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    failures.append((tag, str(e)))
    if failures:
        print("FAILURES:")
        for tag, err in failures:
            print(" ", tag, err.splitlines()[0] if err else "")
        raise SystemExit(1)
    print("dry-run complete")


if __name__ == "__main__":
    main()
