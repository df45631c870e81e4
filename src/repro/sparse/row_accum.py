"""Sorted sparse *row* accumulators: associative arrays whose values are
f32 rows instead of scalars.

This is the bridge between the paper's hierarchical associative arrays and
LM training: an embedding-gradient microbatch is a hypersparse update stream
``token_id -> grad_row`` (a few thousand distinct ids out of a 32 K-262 K
vocab).  The structure below is exactly ``repro.core.assoc`` with
``(row=token_id, col=0)`` keys and vector payloads — same sorted-key layout,
same segmented combine, same capacity discipline.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.assoc import PAD

INT_MAX = PAD


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RowAccum:
    """Sorted unique int32 ids with f32[d] payload rows; pad id = PAD."""

    ids: jax.Array  # int32[cap]
    rows: jax.Array  # f32[cap, d]
    nnz: jax.Array  # int32[]
    overflow: jax.Array  # bool[]

    @property
    def capacity(self) -> int:
        return self.ids.shape[0]

    @property
    def dim(self) -> int:
        return self.rows.shape[1]


def empty(cap: int, d: int, dtype=jnp.float32) -> RowAccum:
    return RowAccum(
        ids=jnp.full((cap,), PAD, jnp.int32),
        rows=jnp.zeros((cap, d), dtype),
        nnz=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), jnp.bool_),
    )


def _combine_sorted(ids, rows, cap: int) -> RowAccum:
    """Fold duplicate ids (sorted input) and compact."""

    def comb(left, right):
        li, lr = left
        ri, rr = right
        same = (li == ri)[..., None]
        return ri, jnp.where(same, lr + rr, rr)

    _, acc = lax.associative_scan(comb, (ids, rows))
    nxt = jnp.concatenate([ids[1:], jnp.full((1,), -1, jnp.int32)])
    keep = (ids != nxt) & (ids != PAD)
    n_keep = keep.sum(dtype=jnp.int32)
    pos = jnp.cumsum(keep, dtype=jnp.int32) - 1
    pos = jnp.where(keep, pos, cap)
    out = empty(cap, rows.shape[1], rows.dtype)
    return RowAccum(
        ids=out.ids.at[pos].set(ids, mode="drop"),
        rows=out.rows.at[pos].set(acc, mode="drop"),
        nnz=jnp.minimum(n_keep, cap),
        overflow=n_keep > cap,
    )


def from_pairs(ids: jax.Array, rows: jax.Array, cap: int) -> RowAccum:
    """Build from (possibly duplicated, unsorted) id/row pairs."""
    order = jnp.argsort(ids.astype(jnp.int32))
    return _combine_sorted(ids.astype(jnp.int32)[order], rows[order], cap)


def merge(a: RowAccum, b: RowAccum, cap: int | None = None) -> RowAccum:
    """``A (+) B`` by rank-merge (both inputs sorted), with a row payload."""
    if cap is None:
        cap = a.capacity + b.capacity
    m, n = a.capacity, b.capacity
    pos_a = jnp.arange(m, dtype=jnp.int32) + jnp.searchsorted(
        b.ids, a.ids, side="left"
    ).astype(jnp.int32)
    pos_b = jnp.arange(n, dtype=jnp.int32) + jnp.searchsorted(
        a.ids, b.ids, side="right"
    ).astype(jnp.int32)
    ids = jnp.full((m + n,), PAD, jnp.int32)
    rows = jnp.zeros((m + n, a.dim), a.rows.dtype)
    ids = ids.at[pos_a].set(a.ids).at[pos_b].set(b.ids)
    rows = rows.at[pos_a].set(a.rows).at[pos_b].set(b.rows)
    out = _combine_sorted(ids, rows, cap)
    return dataclasses.replace(out, overflow=out.overflow | a.overflow | b.overflow)


def to_dense(a: RowAccum, v: int) -> jax.Array:
    """[v, d] dense materialization (tests)."""
    dense = jnp.zeros((v, a.dim), a.rows.dtype)
    return dense.at[a.ids].add(a.rows, mode="drop")


# ---------------------------------------------------------------------------
# hierarchical cascade (paper Section III, row-valued)
# ---------------------------------------------------------------------------

@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HierRowAccum:
    layers: Tuple[RowAccum, ...]
    cascades: jax.Array  # int32[N]


def hier_init(cuts: Sequence[int], top_capacity: int, batch: int, d: int) -> HierRowAccum:
    cuts = tuple(int(c) for c in cuts)
    caps = []
    below = int(batch)
    for c in cuts:
        caps.append(c + below)
        below = caps[-1]
    caps.append(top_capacity + below)
    return HierRowAccum(
        layers=tuple(empty(c, d) for c in caps),
        cascades=jnp.zeros((len(caps),), jnp.int32),
    )


def hier_update(
    h: HierRowAccum, ids: jax.Array, rows: jax.Array, cuts: Sequence[int]
) -> HierRowAccum:
    """Ingest one microbatch of (id, grad_row) pairs; cascade on cut
    overflow — the paper's HierAdd with row payloads."""
    cuts = tuple(int(c) for c in cuts)
    layers = list(h.layers)
    cascades = h.cascades
    batch = from_pairs(ids, rows, cap=ids.shape[0])
    layers[0] = merge(layers[0], batch, cap=layers[0].capacity)
    for i, cut in enumerate(cuts):
        src, dst = layers[i], layers[i + 1]
        pred = src.nnz > cut

        def do(src=src, dst=dst):
            return merge(dst, src, cap=dst.capacity), empty(
                src.capacity, src.dim, src.rows.dtype
            )

        def dont(src=src, dst=dst):
            return dst, src

        merged, cleared = lax.cond(pred, do, dont)
        layers[i + 1] = merged
        layers[i] = cleared
        cascades = cascades.at[i + 1].add(pred.astype(jnp.int32))
    return HierRowAccum(layers=tuple(layers), cascades=cascades)


def hier_flush(h: HierRowAccum) -> RowAccum:
    """Collapse all layers into one sorted RowAccum (optimizer handoff)."""
    out = h.layers[-1]
    for layer in reversed(h.layers[:-1]):
        out = merge(out, layer, cap=h.layers[-1].capacity)
    return out


def hier_reset(h: HierRowAccum) -> HierRowAccum:
    return HierRowAccum(
        layers=tuple(empty(l.capacity, l.dim, l.rows.dtype) for l in h.layers),
        cascades=jnp.zeros_like(h.cascades),
    )


def hier_overflowed(h: HierRowAccum) -> jax.Array:
    out = h.layers[0].overflow
    for l in h.layers[1:]:
        out = out | l.overflow
    return out
