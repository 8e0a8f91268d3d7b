"""Persistent XLA compilation cache for the repo's entry points.

A cold process compiles every kernel and jitted step again; with the cache
on, a second process with the same programs reads them back from disk.
Entry points (``chip_smoke.py``, ``benchmarks/run.py``, ``examples/``)
call :func:`enable_compile_cache` once, before their first compile.  The
library never calls it at import, and tests never call it.
"""
from __future__ import annotations

import os
from pathlib import Path

# fixed, so that every process of this checkout finds the same entries
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here; otherwise the cache lives in
    ``<repo>/.jax_cache``.  Every compile is cached, however short: the
    Pallas kernels compile in about a second, below JAX's default floor.
    """
    import jax
    path = os.environ.get(ENV_VAR)
    if not path:
        path = str(DEFAULT_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
