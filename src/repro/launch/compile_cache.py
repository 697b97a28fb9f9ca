"""Where the persistent JAX compilation cache lives.

Entry points (``chip_smoke.py``, ``python -m repro.launch.serve``,
``python -m benchmarks.run``) call :func:`enable_compile_cache` once at
start-up; importing the library never touches the cache.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets no other directory.
* Otherwise the cache goes to ``.jax_cache/`` at the root of the checkout.
  The path is fixed (never made from a temporary name, a pid or the time),
  so a second run of the same program finds what the first one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory."""
    import jax

    env = os.environ.get(CACHE_ENV)
    if env:
        return env
    # src/repro/launch/ is three levels below the checkout root
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
