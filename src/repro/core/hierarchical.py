"""Hierarchical associative arrays (paper Section III).

An N-layer cascade ``A_1 ... A_N`` with cut values ``c_1 < ... < c_{N-1}``:
updates are added to ``A_1`` (the smallest, fastest layer); whenever
``nnz(A_i) > c_i`` the whole layer is semiring-added into ``A_{i+1}`` and
cleared.  The full array is ``A = sum_i A_i``.  Because the semiring add is
associative and commutative, the cascade is plain addition — the exact
``HierAdd`` loop from the paper, expressed with ``lax.cond`` so both branches
have identical (static) shapes.

Capacity discipline (static shapes): a layer may hold up to its cut ``c_i``
*and* absorb a full cascade from the layer below before its own cut check,
so capacities telescope::

    cap_1 = c_1 + batch_size        (layer 1 absorbs the ingest batch)
    cap_i = c_i + cap_{i-1}         (absorbs a full lower-layer cascade)
    cap_N = top_capacity + cap_{N-1}

With a geometric cut schedule (ratio >= 2) this is ~``2*c_i + batch_size``
per layer.  The top layer has no cut — ``top_capacity`` bounds the total
distinct keys, exactly like the paper's experiments where the last cut is
chosen above the total entry count.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from . import assoc
from .assoc import Assoc
from .semiring import PLUS_TIMES, Semiring


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class HierAssoc:
    """N-layer hierarchical associative array."""

    layers: Tuple[Assoc, ...]
    # number of cascades that reached each layer (telemetry; [N] int32)
    cascades: jax.Array

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        caps = [l.capacity for l in self.layers]
        return f"HierAssoc(caps={caps})"


def geometric_cuts(c1: int, ratio: int, n_layers: int) -> Tuple[int, ...]:
    """The paper's cut schedule: ``c_i = c1 * ratio^(i-1)`` (Fig. 3)."""
    return tuple(int(c1 * ratio**i) for i in range(n_layers - 1))


def telescoped_caps(
    cuts: Sequence[int], top_capacity: int, batch_size: int
) -> Tuple[int, ...]:
    """The telescoped per-layer capacities (module docstring): the single
    source of truth shared by :func:`init`, the ``d4m`` capacity planner,
    and the ``hier_cascade`` kernel's static shape contract."""
    caps = []
    below = int(batch_size)
    for c in cuts:
        caps.append(int(c) + below)
        below = caps[-1]
    caps.append(int(top_capacity) + below)
    return tuple(caps)


def _next_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def pad_layers_pow2(h: HierAssoc, sr: Semiring = PLUS_TIMES) -> HierAssoc:
    """Grow every layer buffer to the next power of two (PAD keys /
    semiring-zero values in the tail).

    This is the flat layout the ``hier_cascade`` Pallas kernel consumes: its
    bitonic merge/sort networks need power-of-two lanes, and padding *once at
    init* keeps the streaming hot loop free of per-step reshapes.  Padding
    never changes Assoc semantics — the live prefix, ``nnz``, and ``overflow``
    are untouched, and every operation masks on PAD — so snapshots off a
    padded hierarchy are bit-identical to the exact-capacity ones.
    """
    layers = []
    for l in h.layers:
        cap = l.capacity
        q = _next_pow2(cap)
        if q == cap:
            layers.append(l)
            continue
        pad = q - cap
        layers.append(
            Assoc(
                rows=jnp.concatenate([l.rows, jnp.full((pad,), assoc.PAD, jnp.int32)]),
                cols=jnp.concatenate([l.cols, jnp.full((pad,), assoc.PAD, jnp.int32)]),
                vals=jnp.concatenate(
                    [l.vals, jnp.full((pad,), sr.zero, l.vals.dtype)]
                ),
                nnz=l.nnz,
                overflow=l.overflow,
            )
        )
    return HierAssoc(layers=tuple(layers), cascades=h.cascades)


def init(
    cuts: Sequence[int],
    top_capacity: int,
    batch_size: int,
    sr: Semiring = PLUS_TIMES,
    dtype=jnp.float32,
) -> HierAssoc:
    """Initialize an N-layer hierarchy.

    ``cuts`` are ``c_1..c_{N-1}``; the top layer holds up to ``top_capacity``
    distinct keys.  ``batch_size`` is the ingest-batch granularity (the
    paper's "groups of 100,000"), which layer 1 must absorb before its cut
    check.  ``len(cuts) == 0`` gives the non-hierarchical baseline (0 cuts).
    """
    cuts = tuple(int(c) for c in cuts)
    if any(b <= a for a, b in zip(cuts, cuts[1:])):
        raise ValueError(f"cuts must be strictly increasing, got {cuts}")
    caps = telescoped_caps(cuts, top_capacity, batch_size)
    layers = tuple(assoc.empty(cap, sr, dtype) for cap in caps)
    return HierAssoc(
        layers=layers, cascades=jnp.zeros((len(caps),), jnp.int32)
    )


def _select_assoc(pred: jax.Array, a: Assoc, b: Assoc) -> Assoc:
    """Per-leaf ``where(pred, a, b)`` — the branchless analogue of
    ``lax.cond`` for whole associative arrays.  ``pred`` may be a traced
    scalar (e.g. a per-instance predicate under ``vmap``)."""
    return jax.tree.map(lambda x, y: jnp.where(pred, x, y), a, b)


def update(
    h: HierAssoc,
    batch: Assoc,
    cuts: Sequence[int],
    sr: Semiring = PLUS_TIMES,
    *,
    branchless: bool = False,
) -> HierAssoc:
    """One streaming update: ``A_1 += batch`` then cascade (paper's HierAdd).

    ``cuts`` must be the same (static) schedule used at :func:`init`.

    ``branchless=True`` replaces each ``lax.cond`` with an unconditional
    cascade merge selected by ``jnp.where`` — both sides are always computed,
    but the program contains no control flow, so it vectorizes cleanly under
    ``jax.vmap`` (the instance-packed engine in :mod:`.multistream`), with each
    vmap lane cascading independently of its neighbours.
    """
    cuts = tuple(int(c) for c in cuts)
    layers = list(h.layers)
    cascades = h.cascades
    # named scopes (op metadata only) name each phase in a profiler trace
    with jax.named_scope("d4m.insert"):
        layers[0] = assoc.add(layers[0], batch, cap=layers[0].capacity, sr=sr)
    for i, cut in enumerate(cuts):
        src, dst = layers[i], layers[i + 1]
        pred = src.nnz > cut

        def do_cascade(src=src, dst=dst, sr=sr, i=i):
            # layer i + 1 (1-based) merged into layer i + 2
            with jax.named_scope(f"d4m.merge.l{i + 1}"):
                merged = assoc.add(dst, src, cap=dst.capacity, sr=sr)
                cleared = assoc.empty(src.capacity, sr, src.vals.dtype)
            return merged, cleared

        def no_cascade(src=src, dst=dst):
            return dst, src

        if branchless:
            merged_c, cleared_c = do_cascade()
            merged = _select_assoc(pred, merged_c, dst)
            cleared = _select_assoc(pred, cleared_c, src)
        else:
            merged, cleared = lax.cond(pred, do_cascade, no_cascade)
        layers[i + 1] = merged
        layers[i] = cleared
        cascades = cascades.at[i + 1].add(pred.astype(jnp.int32))
    return HierAssoc(layers=tuple(layers), cascades=cascades)


def update_triples(
    h: HierAssoc,
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    cuts: Sequence[int],
    sr: Semiring = PLUS_TIMES,
    valid: jax.Array | None = None,
    *,
    branchless: bool = False,
) -> HierAssoc:
    """Ingest a raw triple batch (sorts/combines it, then :func:`update`)."""
    with jax.named_scope("d4m.batch"):
        batch = assoc.from_triples(rows, cols, vals, cap=rows.shape[0], sr=sr, valid=valid)
    return update(h, batch, cuts, sr, branchless=branchless)


def snapshot(h: HierAssoc, cap: int, sr: Semiring = PLUS_TIMES) -> Assoc:
    """``A = sum_i A_i`` — materialize the full array for analysis."""
    out = h.layers[-1]
    for layer in reversed(h.layers[:-1]):
        out = assoc.add(out, layer, cap=cap, sr=sr)
    return out


def nnz_total(h: HierAssoc) -> jax.Array:
    """Upper bound on distinct keys: sum of per-layer nnz (keys may repeat
    across layers until a cascade folds them)."""
    return sum(l.nnz for l in h.layers)


def overflowed(h: HierAssoc) -> jax.Array:
    return functools.reduce(jnp.logical_or, [l.overflow for l in h.layers])


def memory_bytes(h: HierAssoc) -> int:
    """Static memory footprint of the hierarchy (for the Fig. 3 trade-off)."""
    total = 0
    for l in h.layers:
        total += l.rows.size * 4 + l.cols.size * 4 + l.vals.size * l.vals.dtype.itemsize
    return total
