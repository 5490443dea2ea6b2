"""The persistent compilation cache helper used by every entry point."""
import os

import jax
import pytest

from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config():
    prev = (jax.config.jax_compilation_cache_dir,
            jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", prev[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", prev[1])


def test_env_var_wins_and_nothing_is_overridden(monkeypatch,
                                                restore_cache_config):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
    assert compile_cache.enable_compile_cache() == "/some/where"
    assert jax.config.jax_compilation_cache_dir is None
    # every program is written, however quickly it compiled
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_default_is_a_fixed_path_in_the_checkout(monkeypatch,
                                                 restore_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    # the same path every time: it is part of each entry's key
    assert compile_cache.enable_compile_cache() == path
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
