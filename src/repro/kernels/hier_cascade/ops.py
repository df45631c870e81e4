"""Jit'd wrapper: one packed ``D4MStream`` update step through the
lane-skipping cascade kernel.

``cascade_update(h, rows, cols, vals, cuts, caps, sr)`` is the drop-in
equivalent of ``multistream.packed_update`` for a pow2-padded packed
hierarchy (``multistream.init_packed(..., pad_pow2=True)``): bit-identical
snapshots / nnz / cascade counters / overflow flags.

The session keeps its state in the kernel's own layout instead
(:func:`tile_state`: each lane ``[w / 128, 128]``) and steps it with
:func:`cascade_update_tiled`, so the layer buffers go into the kernel as
they are and come out aliased in place: a step moves the batch and the
layers whose cuts fired, not the whole state.  :func:`untile_state` gives
the ``[K, w]`` layout back for snapshots and checkpoints.

The batch is canonicalized *outside* the kernel with the exact
``assoc.from_triples`` the cond and branchless engines use, so all three
paths fold duplicate batch keys identically — that, plus the kernel's
``sr.add(dst, src)`` fold order, is what makes the parity bit-exact.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import assoc as assoc_mod
from repro.core import multistream
from repro.core.assoc import PAD
from repro.core.hierarchical import HierAssoc
from repro.core.semiring import PLUS_TIMES, Semiring

from .. import common
from .kernel import check_fits, hier_cascade_pallas, tile, untile


def check_plan(caps: Sequence[int], batch_size: int, dtype=jnp.float32) -> int:
    """Refuse, with a ``ValueError``, a plan the kernel cannot hold: values
    that are not 32-bit, or layers whose widest merge overflows the VMEM
    budget.  Returns the VMEM bytes the kernel will claim."""
    widths = [common.next_pow2(int(c)) for c in caps] + [common.next_pow2(batch_size)]
    return check_fits(widths, dtype)


def _pad_axis1(x, width, fill):
    k, n = x.shape
    if n == width:
        return x
    return jnp.concatenate(
        [x, jnp.full((k, width - n), fill, x.dtype)], axis=1
    )


def tile_state(h: HierAssoc, sr: Semiring = PLUS_TIMES) -> HierAssoc:
    """A pow2-padded packed hierarchy (``[K, w]`` lanes) in the kernel's
    ``[K, w' / 128, 128]`` layout; counters are unchanged."""
    layers = []
    for l in h.layers:
        w = l.rows.shape[1]
        if w & (w - 1):
            raise ValueError(
                f"layer buffers must be pow2-padded (pad_layers_pow2), got width {w}"
            )
        zero = jnp.asarray(sr.zero, l.vals.dtype)
        layers.append(dataclasses.replace(
            l, rows=tile(l.rows, PAD), cols=tile(l.cols, PAD), vals=tile(l.vals, zero)
        ))
    return HierAssoc(layers=tuple(layers), cascades=h.cascades)


def untile_state(h: HierAssoc, widths: Sequence[int]) -> HierAssoc:
    """Inverse of :func:`tile_state` for layers of ``widths`` entries."""
    layers = tuple(
        dataclasses.replace(
            l, rows=untile(l.rows, w), cols=untile(l.cols, w), vals=untile(l.vals, w)
        )
        for l, w in zip(h.layers, widths)
    )
    return HierAssoc(layers=layers, cascades=h.cascades)


def cascade_update_tiled(
    h: HierAssoc,
    rows: jax.Array,  # [K, B] int32
    cols: jax.Array,
    vals: jax.Array,
    cuts: Sequence[int],
    caps: Sequence[int],
    sr: Semiring = PLUS_TIMES,
    interpret: bool | None = None,
) -> HierAssoc:
    """One streaming update on every packed instance of a :func:`tile_state`
    hierarchy; returns it in the same layout.  ``caps`` are the true
    telescoped capacities (``hierarchical.telescoped_caps`` /
    ``StreamConfig.plan().layer_caps``)."""
    cuts = tuple(int(c) for c in cuts)
    caps = tuple(int(c) for c in caps)
    b = rows.shape[1]
    # named scopes (op metadata only) name each phase in a profiler trace
    with jax.named_scope("d4m.batch"):
        # same canonicalization as update_triples: sort + fold duplicates
        batch = jax.vmap(
            lambda r, c, v: assoc_mod.from_triples(r, c, v, cap=b, sr=sr)
        )(rows, cols, vals)
        qb = common.next_pow2(b)
        batch_bufs = (
            _pad_axis1(batch.rows, qb, PAD),
            _pad_axis1(batch.cols, qb, PAD),
            _pad_axis1(batch.vals, qb, jnp.asarray(sr.zero, batch.vals.dtype)),
        )
    with jax.named_scope("d4m.cascade"):
        layer_bufs, nnz, cascades, overflow = multistream.flat_layer_state(h)
        # a malformed batch surfaces on layer 1 exactly as assoc.add would
        overflow = overflow.at[:, 0].set(overflow[:, 0] | batch.overflow)
        nnz_o, casc_o, ov_o, layers_o = hier_cascade_pallas(
            batch_bufs,
            nnz,
            cascades,
            overflow,
            layer_bufs,
            cuts=cuts,
            caps=caps,
            sr=sr,
            interpret=interpret,
        )
        return multistream.from_flat_layer_state(layers_o, nnz_o, casc_o, ov_o)


def cascade_update(
    h: HierAssoc,
    rows: jax.Array,  # [K, B] int32
    cols: jax.Array,
    vals: jax.Array,
    cuts: Sequence[int],
    caps: Sequence[int],
    sr: Semiring = PLUS_TIMES,
    interpret: bool | None = None,
) -> HierAssoc:
    """One streaming update on every packed instance via the Pallas kernel.

    ``h`` must be pow2-padded (``init_packed(pad_pow2=True)``) in the
    ``[K, w]`` layout; it is tiled in and out around the kernel (the
    session avoids that by keeping :func:`tile_state` state).
    """
    widths = [l.rows.shape[1] for l in h.layers]
    out = cascade_update_tiled(
        tile_state(h, sr), rows, cols, vals, cuts, caps, sr, interpret
    )
    return untile_state(out, widths)


def build_step(
    cuts: Sequence[int],
    caps: Sequence[int],
    sr: Semiring = PLUS_TIMES,
    donate: bool = True,
    interpret: bool | None = None,
    tiled: bool = False,
):
    """A jitted ``(h, rows, cols, vals) -> h`` kernel step, over ``[K, w]``
    state or, with ``tiled``, over :func:`tile_state` state.

    Tiled and donated, the layer buffers stay in place across steps — with
    ``input_output_aliases`` inside the kernel the no-cascade path is an
    in-place O(batch) update, no Σ-cap copies.
    """
    cuts = tuple(int(c) for c in cuts)
    caps = tuple(int(c) for c in caps)
    update = cascade_update_tiled if tiled else cascade_update

    def step(h: HierAssoc, rows, cols, vals) -> HierAssoc:
        return update(h, rows, cols, vals, cuts, caps, sr, interpret)

    return jax.jit(step, donate_argnums=(0,) if donate else ())


def init_state(
    n_instances: int,
    cuts: Sequence[int],
    top_capacity: int,
    batch_size: int,
    sr: Semiring = PLUS_TIMES,
    dtype=jnp.float32,
) -> Tuple[HierAssoc, Tuple[int, ...]]:
    """Pow2-padded packed state (``[K, w]`` lanes; :func:`tile_state` gives
    the kernel's layout) + the true capacities to drive it with."""
    from repro.core.hierarchical import telescoped_caps

    caps = telescoped_caps(tuple(int(c) for c in cuts), top_capacity, batch_size)
    h = multistream.init_packed(
        n_instances, cuts, top_capacity, batch_size, sr, dtype, pad_pow2=True
    )
    return h, caps
