"""Pieces every cell driver shares: files by name, the device check, the
compile counter, host spans, seeds and the result line.

Only ``program_model`` touches the program under test (its registered
model configs).  ``jax`` is imported by the caller, which places the
persistent compilation cache first.
"""

from __future__ import annotations

import importlib.util
import json
import sys
import time
from contextlib import contextmanager
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# fixed, inside the checkout: the cache directory is part of the cache key
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
GIB = 1024 ** 3


class BenchError(RuntimeError):
    """A run that cannot produce a result (no chip, unknown name, ...)."""


def load_json(path: Path) -> dict:
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = BENCH / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"missing file {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def program_model(cfg: dict):
    """The program's registered model config with the file's model keys,
    each checked: the program runs as the configuration file states."""
    from repro.config import get_arch

    base = get_arch(cfg["arch"])
    model = base.replace(**{k: v for k, v in cfg["model"].items()
                            if getattr(base, k) != v})
    for k, v in cfg["model"].items():
        if getattr(model, k) != v:
            raise BenchError(f"{cfg['arch']}: {k}={getattr(model, k)!r}, "
                             f"the configuration file states {v!r}")
    return model


def place_compile_cache(jax) -> str:
    """JAX's persistent cache at the fixed ``.jax_cache/`` in the checkout,
    for executables of every size and compile time."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def check_devices(jax, chips: int) -> list:
    """The chips the cell asks for, or BenchError: never a CPU fallback."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX's first device is "
                         f"{devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devices)}")
    return devices[:chips]


def device_record(devices) -> dict:
    peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


class CompileCounter:
    """Backend compiles (persistent-cache hits included: JAX times both
    under one event) and persistent-cache hits, from JAX's monitoring
    events.  ``backend - hits`` compiled from scratch."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self, jax):
        self.backend = 0
        self.hits = 0
        self.compile_s = 0.0

        def on_duration(name, secs, **kw):
            if name == self.COMPILE:
                self.backend += 1
                self.compile_s += secs

        def on_event(name, **kw):
            if name == self.HIT:
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> dict:
        return {"backend_compiles": self.backend, "cache_hits": self.hits,
                "compiled": self.backend - self.hits,
                "compile_s": self.compile_s}


class Spans:
    """Host spans from the benchmark's own files: durations in memory, and
    the same names in the profiler's trace while one is recorded."""

    def __init__(self, jax):
        self._annotate = jax.profiler.TraceAnnotation
        self.tracing = False
        self.times: dict = {}

    @contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.tracing:
            with self._annotate(f"bench.{name}"):
                yield
        else:
            yield
        self.times.setdefault(name, []).append(time.perf_counter() - t0)


def prng_key(jax, seed: int, *path: int):
    """A PRNG key from a seed of any size (64 bits and more) and a path."""
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    key = jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)
    for p in path:
        key = jax.random.fold_in(key, p)
    return key


def np_rng(seed: int, *path: int):
    import numpy as np
    return np.random.default_rng([seed % (1 << 63), *path])


def emit(result: dict, checks: list) -> None:
    """Each compared number beside its limit as the last lines on stderr,
    then the result as the last line on stdout, ``checks`` its last key."""
    for c in checks:
        print(f"check {c['name']}: {c['value']!r} (limit {c['limit']!r}) "
              f"{'ok' if c['ok'] else 'FAIL'}", file=sys.stderr, flush=True)
    result = dict(result)
    result["checks"] = {c["name"]: {"value": c["value"], "limit": c["limit"]}
                        for c in checks}
    print(json.dumps(result), flush=True)
