"""Persistent XLA compile cache for the repository's entry points.

A cold start compiles every program (the FL cohort trainer, the codec
kernels, the serving chain); the persistent cache lets the next process
on the same machine load them instead. Entry points (``chip_smoke.py``,
``examples/``, ``benchmarks/``) call :func:`enable_compile_cache` from
``main``; library code never does, and nothing happens at import.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# fixed, so a later process finds what an earlier one wrote (the path is
# part of what the cache is looked up by); git-ignored
REPO_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    no other directory is set here. Otherwise the cache lives at the
    fixed ``<repo>/.jax_cache``."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
