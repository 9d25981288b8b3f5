"""JAX's persistent compilation cache, placed where the next run finds it.

Entry points (``launch/train.py``, ``launch/serve.py``, ``chip_smoke.py``)
call :func:`enable_compile_cache` once at start-up; library code and tests
never do. The cache directory is part of what makes an entry hit, so it is
a fixed path: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it
(JAX reads that variable itself), otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os

import jax

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
