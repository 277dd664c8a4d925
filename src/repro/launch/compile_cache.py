"""JAX's persistent compilation cache for the drivers that run on the chip.

Called from ``main`` of ``launch/serve.py`` and from ``chip_smoke.py``,
never at import.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads
it itself and nothing is set here.  Otherwise the cache goes to the
fixed ``.jax_cache/`` at the checkout root: the directory is part of the
cache's key, so it is never built from a temporary name, a process id or
the time.
"""
from __future__ import annotations

import os

import jax

CHECKOUT_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(CHECKOUT_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
