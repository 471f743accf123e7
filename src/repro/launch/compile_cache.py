"""Where JAX's persistent compilation cache lives — one rule for every
entry point (``launch.serve_graph``, ``benchmarks/run.py``,
``chip_smoke.py``).

If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing is
set here. Otherwise the cache goes to ``<checkout>/.jax_cache``: a fixed
path, because a cache directory whose name changes from run to run (a
temporary name, a PID, a time) never hits. Call it from ``main``, never at
import time.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    configured = os.environ.get(ENV_VAR)
    if configured:
        return configured
    path = str(CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
