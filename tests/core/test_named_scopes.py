"""The ``d4m.`` named scopes on both engine steps: they reach the lowered
program's op metadata, and change nothing else in it."""
import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.core import hierarchical
from repro.kernels.hier_cascade import ops

CUTS = (8, 32)
TOP = 256
B = 16
K = 2
SCOPES = {
    "single": {"d4m.batch", "d4m.insert", "d4m.merge.l1", "d4m.merge.l2"},
    "pallas": {"d4m.batch", "d4m.cascade"},
}


def _lower(engine):
    """The engine's update step lowered at a tiny size, traced afresh."""
    if engine == "single":
        h = hierarchical.init(CUTS, TOP, B)
        keys = jnp.zeros((B,), jnp.int32)

        def step(h, rows, cols, vals):
            return hierarchical.update_triples(h, rows, cols, vals, CUTS)
    else:
        h, caps = ops.init_state(K, CUTS, TOP, B)
        h = ops.tile_state(h)
        keys = jnp.zeros((K, B), jnp.int32)

        def step(h, rows, cols, vals):
            return ops.cascade_update_tiled(h, rows, cols, vals, CUTS, caps, interpret=True)

    return jax.jit(step).lower(h, keys, keys, jnp.ones(keys.shape, jnp.float32))


@pytest.mark.parametrize("engine", ["single", "pallas"])
def test_step_scopes_reach_op_metadata_and_nothing_else(engine, monkeypatch):
    lowered = _lower(engine)
    named = lowered.as_text(debug_info=True)
    assert set(re.findall(r"d4m\.[a-z0-9.]+", named)) == SCOPES[engine]

    # the same step traced with every named scope a no-op: the program,
    # without its debug locations, is the same op for op
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    plain = _lower(engine)
    assert "d4m." not in plain.as_text(debug_info=True)
    assert lowered.as_text() == plain.as_text()
