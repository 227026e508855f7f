"""JAX's persistent compilation cache for the repo's entry points.

Host-mode rounds compile one executable per power-of-two shape bucket,
so a cold traversal of a large graph spends much of its time compiling.
Entry points (``chip_smoke.py``, ``benchmarks/run.py``, the examples)
call :func:`enable_compile_cache` once at start-up; library modules
never do, so importing ``repro`` changes no JAX setting.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# one fixed directory inside the checkout (git-ignored), so every run
# from this checkout finds what earlier runs wrote
DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def compile_cache_dir() -> str:
    """Where the cache lives: ``$JAX_COMPILATION_CACHE_DIR`` when set
    (JAX reads the variable itself), else :data:`DEFAULT_DIR`."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process, caching every
    compiled program whatever its compile time; returns the directory.
    Sets no path when the environment variable names one."""
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
