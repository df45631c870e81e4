"""Where JAX keeps its persistent compilation cache.

A cold compile of the update step at real widths takes minutes, so every
process of this repository (tests, the benchmark runner, fleet workers,
``chip_smoke.py``) shares one on-disk cache, and only a fixed path can hit:
the directory is part of each entry's key.

* When ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
  module sets no other directory.
* Otherwise the cache is ``.jax_cache/`` at the root of the checkout that
  holds this source tree (listed in ``.gitignore``).
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def cache_dir(environ=None) -> str:
    """The directory the cache lives in under ``environ`` (default: ours)."""
    environ = os.environ if environ is None else environ
    return environ.get(ENV_VAR) or str(CHECKOUT_DIR)


def enable() -> str:
    """Turn the persistent cache on; returns its directory."""
    import jax

    path = cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    return path
