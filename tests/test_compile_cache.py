"""The one persistent compilation cache the entry points share: placed
from outside through JAX_COMPILATION_CACHE_DIR, else a fixed directory
at the root of the checkout."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture()
def updates(monkeypatch):
    """Record config updates instead of turning the cache on here."""
    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.append((name, value)))
    return seen


def test_env_dir_is_used_and_nothing_else_set(monkeypatch, updates, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in dict(updates)


def test_default_is_fixed_dir_at_checkout_root(monkeypatch, updates):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    root = Path(__file__).resolve().parents[1]
    want = str(root / ".jax_cache")
    assert compile_cache.use_compile_cache() == want
    assert dict(updates)["jax_compilation_cache_dir"] == want
    # the same path on every call: a moving directory never hits
    assert compile_cache.use_compile_cache() == want


def test_every_compile_is_cached(monkeypatch, updates):
    """Solve buckets compile in well under a second each; JAX's default
    1 s floor would leave the cache empty."""
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    compile_cache.use_compile_cache()
    assert dict(updates)["jax_persistent_cache_min_compile_time_secs"] == 0
