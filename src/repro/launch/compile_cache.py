"""JAX's persistent compilation cache for the launchers.

Each launcher calls :func:`enable_compile_cache` before its first compile,
so a second run in the same checkout reads compiled programs back instead
of compiling every segment and bucket shape again. Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is set
here; otherwise the cache lives at one fixed path inside the checkout
(``.jax_cache``, git-ignored). The path is part of what makes an entry hit,
so it never depends on a temp name, the pid or the time.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def cache_dir() -> str:
    """The directory the cache uses: the env var where set, else the fixed
    in-checkout path."""
    return os.environ.get(ENV_VAR) or str(CHECKOUT_CACHE)


def enable_compile_cache() -> str:
    """Point JAX's persistent cache at :func:`cache_dir` and return it.
    Leaves JAX's own reading of ``JAX_COMPILATION_CACHE_DIR`` alone."""
    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
