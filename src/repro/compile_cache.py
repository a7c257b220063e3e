"""JAX's persistent compilation cache for the repo's entry points.

Entry points (`chip_smoke.py`, `benchmarks.run`, the advisor examples)
call `enable_compile_cache` before their first compile, so repeat runs
load executables instead of compiling them. No library module turns the
cache on when it is imported.

The directory is part of the cache's key, so it must not move between
runs: ``JAX_COMPILATION_CACHE_DIR`` when it is set (JAX reads it itself,
and this module sets no other), otherwise ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# src/repro/compile_cache.py -> the checkout root
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> Path:
    """Where the persistent cache lives: the environment's directory if
    ``JAX_COMPILATION_CACHE_DIR`` is set, else the fixed checkout path."""
    env = os.environ.get(ENV_VAR)
    return Path(env) if env else DEFAULT_DIR


def enable_compile_cache() -> Path:
    """Turn the persistent cache on at `compile_cache_dir` and return it."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", str(path))
    return path
