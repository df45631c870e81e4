"""Pallas TPU kernel: triple ingestion — bitonic sort + duplicate combine.

This is the front half of ``Assoc(k1, k2, v)`` (paper Section II): an
unsorted batch of streaming triples becomes a sorted, duplicate-combined
run with a survivor mask.  It feeds ``from_triples`` and the layer-1 ingest
of the hierarchical array.

TPU adaptation: a full bitonic **sort** network — ``log2(n) * (log2(n)+1)/2``
strided compare-exchange passes, all ``reshape + select`` on VMEM lanes.
XLA's generic ``sort`` on CPU/GPU uses data-dependent algorithms; on the TPU
vector unit the oblivious network is the native formulation.  Working set:
4 lanes x n x 4 B; the default block (2**16) uses 1 MiB of VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.assoc import PAD
from repro.core.semiring import PLUS_TIMES, Semiring

from .. import common


def _sort_dedup_kernel(
    rows_ref,
    cols_ref,
    vals_ref,
    out_rows_ref,
    out_cols_ref,
    out_vals_ref,
    keep_ref,
    *,
    sr: Semiring,
):
    rows, cols, vals = rows_ref[...], cols_ref[...], vals_ref[...]
    src = jnp.zeros(rows.shape, jnp.int32)  # single-source: src lane unused
    rows, cols, src, vals = common.bitonic_sort((rows, cols, src, vals))
    vals, is_end = common.run_combine(rows, cols, vals, sr.add)
    keep = is_end & (rows != PAD)
    out_rows_ref[...] = rows
    out_cols_ref[...] = cols
    out_vals_ref[...] = vals
    keep_ref[...] = keep


def sort_dedup_pallas(rows, cols, vals, sr: Semiring = PLUS_TIMES, interpret: bool | None = None):
    """Sort + combine a power-of-two triple batch.  Returns
    ``(rows, cols, vals, keep)`` sorted with run-combined values."""
    n = rows.shape[0]
    assert n & (n - 1) == 0, f"length must be a power of two, got {n}"
    out_shape = [
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), jnp.int32),
        jax.ShapeDtypeStruct((n,), vals.dtype),
        jax.ShapeDtypeStruct((n,), jnp.bool_),
    ]
    kernel = functools.partial(_sort_dedup_kernel, sr=sr)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[pl.BlockSpec((n,), lambda: (0,))] * 3,
        out_specs=[pl.BlockSpec((n,), lambda: (0,))] * 4,
        interpret=common.resolve_interpret(interpret),
    )(rows, cols, vals)
