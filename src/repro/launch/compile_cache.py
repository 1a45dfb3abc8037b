"""One persistent compilation cache per checkout.

Every entry point calls :func:`enable_compile_cache` before its first
compile. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself
and nothing is set here. Otherwise the cache lives at ``<checkout>/.jax_cache``:
a fixed path, because a directory named after a temp dir, a pid or the
time would never be found again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = str(Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    return CACHE_DIR
