"""Where JAX keeps its persistent compilation cache for this checkout.

A cache directory is part of the cache key, so it must not move between
runs: a temporary, per-process or time-stamped path never hits.  Entry
points (``chip_smoke.py``, ``benchmarks/*``) call :func:`configure` once,
before their first compile.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Return the persistent compile cache directory, enabling the default.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing else is set here.  Otherwise the cache goes to the fixed,
    git-ignored ``<checkout>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
