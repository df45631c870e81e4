"""Jit'd wrapper: ``from_triples`` through the Pallas sort kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import assoc as assoc_mod
from repro.core.assoc import Assoc, PAD
from repro.core.semiring import PLUS_TIMES, Semiring

from .. import common
from .kernel import sort_dedup_pallas


@functools.partial(jax.jit, static_argnames=("cap", "sr", "interpret"))
def from_triples(
    rows,
    cols,
    vals,
    cap: int,
    sr: Semiring = PLUS_TIMES,
    valid=None,
    interpret: bool | None = None,
) -> Assoc:
    rows = rows.astype(jnp.int32)
    cols = cols.astype(jnp.int32)
    if valid is not None:
        rows = jnp.where(valid, rows, PAD)
        cols = jnp.where(valid, cols, PAD)
        vals = jnp.where(valid, vals, jnp.asarray(sr.zero, vals.dtype))
    n = rows.shape[0]
    total = common.next_pow2(n)
    if total != n:
        pad = total - n
        rows = jnp.concatenate([rows, jnp.full((pad,), PAD, jnp.int32)])
        cols = jnp.concatenate([cols, jnp.full((pad,), PAD, jnp.int32)])
        vals = jnp.concatenate([vals, jnp.full((pad,), sr.zero, vals.dtype)])
    r, c, v, keep = sort_dedup_pallas(rows, cols, vals, sr=sr, interpret=interpret)
    return assoc_mod._compact(r, c, v, keep, cap, sr)
