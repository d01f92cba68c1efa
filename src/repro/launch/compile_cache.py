"""Persistent JAX compilation cache for the entry points.

Entry points (``chip_smoke.py``, ``examples/``) call
:func:`enable_compile_cache` once before their first compile; library
imports and tests never do.  Where ``JAX_COMPILATION_CACHE_DIR`` is set,
JAX already reads it and nothing is changed.  Otherwise the cache lives
at a fixed path inside the checkout: the path is part of the cache key,
so a directory that moves between runs (a temp name, a pid, the time)
would never hit.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on; returns the directory in use."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
