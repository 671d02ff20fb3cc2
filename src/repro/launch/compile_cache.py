"""Where JAX keeps its persistent compilation cache.

The entry points (``chip_smoke.py``, ``launch/train.py``,
``launch/serve.py``) call :func:`enable_compile_cache` before their first
compile.  A ``JAX_COMPILATION_CACHE_DIR`` set from outside wins, and JAX
reads it itself, so nothing is set.  Otherwise the cache goes to one fixed
directory inside the checkout (``.jax_cache/``, ignored by git): the
directory is part of what lets a later process find an entry again, so it
never comes from a temporary name, a process id or the time.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
