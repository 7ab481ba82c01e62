"""Persistent XLA compile cache for the command-line entry points.

Entry points (``chip_smoke.py``, ``examples/*``, ``benchmarks/*``) call
:func:`enable_compile_cache` once at start-up, before their first
compile; tests never do, so a test run leaves no cache behind.
"""
from __future__ import annotations

import os
import pathlib

import jax

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed path inside the checkout (``artifacts/`` is git-ignored), so a
#: later run of the same checkout finds what an earlier one compiled
DEFAULT_DIR = (pathlib.Path(__file__).resolve().parents[3]
               / "artifacts" / "jax-cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, names the directory: JAX
    reads that variable itself and nothing here overrides it.  Otherwise
    the cache goes to :data:`DEFAULT_DIR`.  A Pallas kernel compiles in
    about a second, under JAX's default one-second floor for caching an
    entry, so the floor is dropped to keep every compile.
    """
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir
