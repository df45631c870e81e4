"""Jit'd wrapper around the merge_add Pallas kernel.

``merge_add(a, b, cap, sr)`` is a drop-in replacement for
``repro.core.assoc.add`` that routes the merge through the bitonic kernel.
The wrapper pads both inputs so the combined length is a power of two
(PAD keys sort to the end and are masked), invokes the kernel, then performs
the single O(n) compaction scatter in XLA.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.core import assoc as assoc_mod
from repro.core.assoc import Assoc, PAD
from repro.core.semiring import PLUS_TIMES, Semiring

from .. import common
from .kernel import merge_add_pallas


def _pad_to(x, n, fill):
    m = x.shape[0]
    if m == n:
        return x
    return jnp.concatenate([x, jnp.full((n - m,), fill, x.dtype)])


@functools.partial(jax.jit, static_argnames=("cap", "sr", "interpret"))
def merge_add(
    a: Assoc,
    b: Assoc,
    cap: int | None = None,
    sr: Semiring = PLUS_TIMES,
    interpret: bool | None = None,
) -> Assoc:
    """``C = A (+) B`` via the Pallas bitonic-merge kernel."""
    if cap is None:
        cap = a.capacity + b.capacity
    m, n = a.capacity, b.capacity
    total = common.next_pow2(m + n)
    # grow B's padding so m + n_padded is a power of two
    npad = total - m
    br = _pad_to(b.rows, npad, PAD)
    bc = _pad_to(b.cols, npad, PAD)
    bv = _pad_to(b.vals, npad, jnp.asarray(sr.zero, b.vals.dtype))
    rows, cols, vals, keep = merge_add_pallas(
        a.rows, a.cols, a.vals, br, bc, bv, sr=sr, interpret=interpret
    )
    out = assoc_mod._compact(rows, cols, vals, keep, cap, sr)
    return dataclasses.replace(out, overflow=out.overflow | a.overflow | b.overflow)
