"""Persistent XLA compilation cache for the repo's entry points.

Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the benchmark
modules' ``__main__``) call :func:`enable_compile_cache` before their
first compile; importing :mod:`repro` never touches the cache setting.
Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
nothing is changed here; otherwise the cache lives at a fixed
``<repo>/.jax_cache`` (the path is part of the cache key, so it must not
move between runs).
"""
from __future__ import annotations

import os
from pathlib import Path

REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
