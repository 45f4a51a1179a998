"""`launch.compile_cache.use_compile_cache`: the environment variable wins,
the fallback is a fixed directory inside the checkout, and a second call
sets nothing."""
import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def updates(monkeypatch):
    """Record `jax.config.update` calls made by the helper; restore the
    cache directory afterwards so no later test writes a cache."""
    calls = []
    real = jax.config.update
    before = jax.config.jax_compilation_cache_dir

    def record(name, value):
        calls.append((name, value))
        real(name, value)

    monkeypatch.setattr(jax.config, "update", record)
    yield calls
    real("jax_compilation_cache_dir", before)


def test_env_var_wins_and_nothing_is_set(monkeypatch, updates, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert updates == []


def test_fallback_is_fixed_checkout_path(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    expected = os.path.join(repo, ".jax_cache")
    assert compile_cache.CACHE_DIR == expected
    assert compile_cache.use_compile_cache() == expected
    assert jax.config.jax_compilation_cache_dir == expected
    assert updates == [("jax_compilation_cache_dir", expected)]


def test_second_call_sets_nothing(monkeypatch, updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.use_compile_cache()
    second = compile_cache.use_compile_cache()
    assert first == second
    assert len(updates) == 1
