"""JAX persistent compilation cache for the entry points.

Every launcher (and ``chip_smoke.py``) calls :func:`enable_compile_cache`
once at start-up, before anything compiles, so a second run of the same
shapes loads its executables instead of compiling them again.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  here, so the environment's cache is the only one.
* Otherwise the cache lives at ``.jax_cache/`` in the checkout. The path is
  fixed (never a temporary name, process id or time), because the
  directory is part of what a later run must find again. It is listed in
  ``.gitignore``.

Tests never call this: a test process must not write into the checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

__all__ = ["enable_compile_cache", "CHECKOUT_CACHE_DIR"]

# src/repro/launch/compile_cache.py -> the checkout root is three levels up.
CHECKOUT_CACHE_DIR = str(pathlib.Path(__file__).resolve().parents[3] / ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR
