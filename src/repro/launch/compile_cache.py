"""JAX's persistent compilation cache, kept at one fixed place.

A cold run of a full-width model spends minutes compiling; the cache lets
the next process on the same chip load those programs instead.  The
directory is part of each entry's key, so it never moves: where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads that directory itself
and nothing here overrides it; otherwise the cache lives at
``<checkout>/.jax_cache`` (listed in ``.gitignore``).
"""
from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    # Write every program, not only those over JAX's default 1 s: a
    # server's decode and per-bucket prefill programs each compile in
    # about a second, and together they are its cold start.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
