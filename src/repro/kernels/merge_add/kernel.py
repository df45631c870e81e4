"""Pallas TPU kernel: sorted-COO merge + semiring combine (``A (+) B``).

This is the cascade hot-spot of the hierarchical associative array: every
streaming update merges a batch into layer 1, and every cut overflow merges
layer i into layer i+1.

TPU adaptation (vs. the paper's CPU pointer-walk merge):

* Both inputs live in VMEM as flat lanes ``(rows, cols, src, vals)``.
* ``concat(A, reverse(B))`` is a *bitonic* sequence, so a bitonic **merge**
  network — ``log2(m+n)`` strided compare-exchange passes — sorts it with
  zero gathers/scatters and no data-dependent control flow.  Each pass is a
  ``reshape(n/(2d), 2, d)`` + vectorized select: pure VPU work on 32-bit
  lanes, the layout the TPU vector unit is built for.
* Duplicate keys (present in both inputs) are then folded with a
  Hillis-Steele segmented combine (``log2 n`` shift passes), and the run-end
  mask + scan ranks are emitted so the (cheap, O(n)) compaction scatter runs
  once in XLA — scatters never enter the kernel.

Grid/Blocking: a single program instance owns the whole (power-of-two padded)
problem in VMEM.  With 4-byte lanes and the default ``block_cap = 2**17`` the
working set is 4 lanes x 512 KiB = 2 MiB < 16 MiB VMEM (v5e); callers split
larger merges hierarchically — which is exactly what the hierarchical array
already does by construction (layer capacities are the BlockSpec).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.assoc import PAD
from repro.core.semiring import PLUS_TIMES, Semiring

from .. import common


def _merge_add_kernel(
    a_rows_ref,
    a_cols_ref,
    a_vals_ref,
    b_rows_ref,
    b_cols_ref,
    b_vals_ref,
    out_rows_ref,
    out_cols_ref,
    out_vals_ref,
    keep_ref,
    *,
    sr: Semiring,
):
    m = a_rows_ref.shape[0]
    n = b_rows_ref.shape[0]
    ar, ac, av = a_rows_ref[...], a_cols_ref[...], a_vals_ref[...]
    br, bc, bv = b_rows_ref[...], b_cols_ref[...], b_vals_ref[...]
    # build the bitonic sequence: A ascending ++ B descending
    rows = jnp.concatenate([ar, br[::-1]])
    cols = jnp.concatenate([ac, bc[::-1]])
    vals = jnp.concatenate([av, bv[::-1]])
    src = jnp.concatenate(
        [jnp.zeros((m,), jnp.int32), jnp.ones((n,), jnp.int32)[::-1]]
    )
    rows, cols, src, vals = common.bitonic_merge((rows, cols, src, vals))
    # fold duplicate keys (at most 2 per key: one from A, one from B)
    vals, is_end = common.run_combine(rows, cols, vals, sr.add)
    keep = is_end & (rows != PAD)
    out_rows_ref[...] = rows
    out_cols_ref[...] = cols
    out_vals_ref[...] = vals
    keep_ref[...] = keep


def merge_add_pallas(
    a_rows,
    a_cols,
    a_vals,
    b_rows,
    b_cols,
    b_vals,
    sr: Semiring = PLUS_TIMES,
    interpret: bool | None = None,
):
    """Run the merge kernel; returns (rows, cols, vals, keep) of length
    ``next_pow2(m + n)`` — sorted, run-combined, with the survivor mask.

    Inputs must each be power-of-two length (callers pad with PAD keys /
    semiring-zero values; see ops.py).
    """
    m, n = a_rows.shape[0], b_rows.shape[0]
    total = m + n
    assert total & (total - 1) == 0, f"m + n must be a power of two, got {total}"
    out_shape = [
        jax.ShapeDtypeStruct((total,), jnp.int32),
        jax.ShapeDtypeStruct((total,), jnp.int32),
        jax.ShapeDtypeStruct((total,), a_vals.dtype),
        jax.ShapeDtypeStruct((total,), jnp.bool_),
    ]
    kernel = functools.partial(_merge_add_kernel, sr=sr)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[
            pl.BlockSpec((m,), lambda: (0,)),
            pl.BlockSpec((m,), lambda: (0,)),
            pl.BlockSpec((m,), lambda: (0,)),
            pl.BlockSpec((n,), lambda: (0,)),
            pl.BlockSpec((n,), lambda: (0,)),
            pl.BlockSpec((n,), lambda: (0,)),
        ],
        out_specs=[
            pl.BlockSpec((total,), lambda: (0,)),
            pl.BlockSpec((total,), lambda: (0,)),
            pl.BlockSpec((total,), lambda: (0,)),
            pl.BlockSpec((total,), lambda: (0,)),
        ],
        interpret=common.resolve_interpret(interpret),
    )(a_rows, a_cols, a_vals, b_rows, b_cols, b_vals)
