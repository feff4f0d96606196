"""Where the persistent XLA compilation cache lives: one rule for every
entry point (``chip_smoke.py``, ``benchmarks/run.py``, ``tune/autotune.py``).

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads the variable itself, so the
  cache lives there and this module names no other path.
* Not set: the cache goes to ``.jax_cache/`` at the root of the checkout
  (gitignored). The path is fixed: never a temp name, a pid or a time, since
  a cache directory that moves between runs never hits.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
# src/repro/launch/compile_cache.py -> the checkout root
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its one directory and
    return that directory. Call before the first compile."""
    path = os.environ.get(ENV_VAR)
    if path:
        return path
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
