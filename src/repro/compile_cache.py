"""Persistent XLA compilation cache for the repository's entry points.

``chip_smoke.py``, the benchmarks and the examples call
:func:`enable_compile_cache` once at start-up; importing :mod:`repro`
never does.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it
itself and this sets no other directory.  Otherwise the cache lives at
the fixed ``<checkout>/.jax_cache`` (git-ignored): the directory is
part of what a later run has to find, so it is never derived from a
temp name, a PID or the clock.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the cache directory used when ``JAX_COMPILATION_CACHE_DIR`` is unset
CHECKOUT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
