"""Process-level JAX setup shared by the command-line entry points.

``enable_compile_cache`` turns on JAX's persistent compilation cache, so
a second process (or a second phase of one run) that compiles the same
program at the same shapes loads it instead of compiling again. Where
the ``JAX_COMPILATION_CACHE_DIR`` environment variable is set, JAX reads
it itself and nothing is set here. Otherwise the cache lives in
:data:`DEFAULT_CACHE_DIR`, one fixed directory inside the checkout: the
directory is part of what a cached entry is found by, so a temporary or
per-process path would never be hit again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    placed = os.environ.get(CACHE_ENV)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return str(DEFAULT_CACHE_DIR)


def jax_platform() -> str:
    """Platform of this process's default JAX device (``tpu``, ``cpu``...)."""
    return jax.devices()[0].platform
