"""Shared test fixtures.  NOTE: no XLA_FLAGS device-count forcing here —
smoke tests and benches must see the real single CPU device; only
``launch/dryrun.py`` (run as a script) forces 512 placeholder devices."""
import os
import sys

# make `_hypothesis_fallback` importable from test modules regardless of how
# pytest inserted their own directories into sys.path, and the repo root so
# `benchmarks.*` (regression gate, reporting) is testable
sys.path.insert(0, os.path.dirname(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(__file__)))

import jax
import numpy as np
import pytest

from repro import compile_cache

# persistent compilation cache (JAX_COMPILATION_CACHE_DIR, else the
# checkout's .jax_cache/): repeated pytest runs and the fleet workers the
# tests spawn skip recompiles
compile_cache.enable()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


@pytest.fixture
def rng():
    return np.random.default_rng(0)
