"""Placement of JAX's persistent compilation cache by the entry points."""

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import CACHE_DIR, enable_compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture()
def cache_config():
    """Restore the process-wide cache directory after the test."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_dir_is_left_to_jax(cache_config, monkeypatch, tmp_path):
    """A JAX_COMPILATION_CACHE_DIR set from outside wins, and the helper
    sets nothing: JAX reads the variable itself."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_env_gives_one_fixed_dir_in_checkout(cache_config,
                                                    monkeypatch):
    """Unset, the cache goes to the same directory inside the checkout on
    every call, so a later process finds what an earlier one wrote."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert enable_compile_cache() == first == str(CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert Path(first).parent == REPO
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{Path(first).name}/" in ignored
