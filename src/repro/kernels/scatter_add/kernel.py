"""Pallas TPU kernel: flush dedup'd sparse rows into a dense table.

The hierarchical sparse embedding-gradient accumulator (DESIGN.md section 3.4)
ends each optimizer step by applying ``k`` unique ``(token_id, grad_row)``
pairs to the dense ``[V, d]`` parameter/accumulator table.  ``k << V``
(hypersparse), so a dense ``V x d`` add would waste ``(V-k)/V`` of HBM
bandwidth — this kernel touches exactly the ``k`` live rows.

TPU adaptation: the table stays in HBM/ANY and is aliased in-place
(``input_output_aliasing``); the row block and id block are VMEM-resident.
The grid walks id blocks; within a block a ``fori_loop`` issues one
dynamic-slice row read-modify-write per live id.  TPU grids execute
sequentially, and ids are sorted-unique by construction (they come out of the
hierarchy's top layer), so there are no write conflicts.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from repro.core.assoc import PAD

from .. import common


def _scatter_add_kernel(ids_ref, rows_ref, table_ref, out_ref, *, block: int):
    # out_ref is aliased to table_ref's buffer; nothing to initialize.
    def body(i, _):
        tid = ids_ref[i]

        def apply(_):
            row = out_ref[pl.ds(tid, 1), :]
            add = rows_ref[i, :][None, :].astype(row.dtype)
            out_ref[pl.ds(tid, 1), :] = row + add
            return 0

        lax.cond(tid != PAD, apply, lambda _: 0, 0)
        return 0

    lax.fori_loop(0, block, body, 0)


def scatter_add_pallas(ids, rows, table, interpret: bool | None = None):
    """``table[ids] += rows`` for live (non-PAD) ids; returns the new table.

    ids: int32[k] sorted-unique (PAD = dead slot); rows: [k, d]; table: [V, d].
    """
    k = ids.shape[0]
    v, d = table.shape
    kernel = functools.partial(_scatter_add_kernel, block=k)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((v, d), table.dtype),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        input_output_aliases={2: 0},
        interpret=common.resolve_interpret(interpret),
    )(ids, rows, table)
