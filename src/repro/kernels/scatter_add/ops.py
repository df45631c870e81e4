"""Jit'd wrapper for the scatter_add flush kernel."""
from __future__ import annotations

import functools

import jax

from .kernel import scatter_add_pallas
from .ref import scatter_add_ref


@functools.partial(jax.jit, static_argnames=("interpret", "use_kernel"), donate_argnums=(2,))
def scatter_add(ids, rows, table, interpret: bool | None = None, use_kernel: bool = True):
    """``table[ids] += rows`` (PAD ids skipped), donating the table buffer."""
    if use_kernel:
        return scatter_add_pallas(ids, rows, table, interpret=interpret)
    return scatter_add_ref(ids, rows, table)
