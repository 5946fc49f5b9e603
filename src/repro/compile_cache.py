"""Where JAX keeps its persistent compilation cache.

Entry points (``examples/*.py``, ``benchmarks/*_bench.py``,
``benchmarks/run.py``, ``chip_smoke.py``) call :func:`use_compile_cache`
once, before their first compile; tests do not.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and nothing here
  names another directory.
* Otherwise the cache goes to ``<checkout>/.jax_cache`` (git-ignored).
  The path is fixed on purpose: it is part of what a later run has to
  find again, so a temp, pid- or time-based directory would never hit.

Updating the config touches no backend, so calling this before a
subprocess that needs the accelerator keeps the parent off the device.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

DEFAULT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory and
    return that directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
