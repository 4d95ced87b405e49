"""JAX's persistent compilation cache for the entry points.

The launchers (``launch/serve.py``, ``launch/train.py``) and
``chip_smoke.py`` call :func:`enable_compile_cache` before their first
compile; importing the library never does.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, and nothing
  else is set.
* otherwise: ``.jax_cache/`` at the checkout root, listed in
  ``.gitignore``.  The path is fixed (never temporary, per-process or
  time-stamped), so a later process finds what an earlier one compiled.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["CHECKOUT_CACHE_DIR", "enable_compile_cache"]

CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Point the persistent compilation cache at its directory; returns
    the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)
