"""The persistent-compilation-cache helper every entry point calls."""
from __future__ import annotations

import pathlib

import jax
import pytest

from repro.launch import compile_cache

CHECKOUT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_dir_config():
    """Restore jax_compilation_cache_dir after the test (no compile runs
    in between, so the cache itself is never initialised here)."""
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_var_set_leaves_config_untouched(monkeypatch, tmp_path,
                                             cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_without_env_var_uses_fixed_checkout_dir(monkeypatch,
                                                 cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = str(CHECKOUT / ".jax_cache")
    assert compile_cache.enable() == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same path on every call: nothing temporary, per-process or timed
    assert compile_cache.enable() == want
