"""Shared primitives for the Pallas kernels: lexicographic compare-exchange
networks (bitonic sort / bitonic merge) over (row, col, payload...) lanes.

Why bitonic networks on TPU: the CPU implementation of D4M merges sorted
triple lists with data-dependent pointer chasing, and a merge by rank lowers
to gathers and scatters — both hostile to the TPU's vector unit.
A bitonic network is oblivious: a fixed sequence of strided compare-exchange
passes, each expressible as a reshape + vectorized select over VMEM-resident
lanes.  No gathers, no scatters, no data-dependent control flow.

Every helper below operates on flat arrays whose length is a power of two
(callers pad with ``PAD`` sentinel keys, which sort to the end).  The
pair-at-distance-d pattern is realized with ``reshape(n // (2d), 2, d)`` —
strided vector moves, not element shuffles.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import assoc


def resolve_interpret(interpret: bool | None) -> bool:
    """Whether a Pallas call runs in interpret mode.

    ``None`` (every kernel entry point's default) interprets only off the
    TPU: there the kernels are compiled, and a kernel the compiler refuses
    raises instead of silently falling back to the interpreter.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def lex_less(ar, ac, as_, br, bc, bs):
    """Strict lexicographic (row, col, src) order.

    The ``src`` lane makes the order total when the same (row, col) key
    appears in both inputs of a merge — required for exactness of tiled
    merge-path selection and harmless elsewhere.
    """
    return (
        (ar < br)
        | ((ar == br) & (ac < bc))
        | ((ar == br) & (ac == bc) & (as_ < bs))
    )


def compare_exchange(lanes: Sequence[jnp.ndarray], d: int, asc_mask: jnp.ndarray):
    """One compare-exchange pass at pair distance ``d``.

    ``lanes`` = (rows, cols, src, *payloads); the first three define the key
    order.  ``asc_mask`` has the flat shape and is True where the pair block
    sorts ascending.  Returns the updated lanes.
    """
    n = lanes[0].shape[0]
    shaped = [x.reshape(n // (2 * d), 2, d) for x in lanes]
    los = [x[:, 0, :] for x in shaped]
    his = [x[:, 1, :] for x in shaped]
    asc = asc_mask.reshape(n // (2 * d), 2, d)[:, 0, :]
    hi_lt_lo = lex_less(his[0], his[1], his[2], los[0], los[1], los[2])
    lo_lt_hi = lex_less(los[0], los[1], los[2], his[0], his[1], his[2])
    swap = jnp.where(asc, hi_lt_lo, lo_lt_hi)
    out = []
    for lo, hi in zip(los, his):
        new_lo = jnp.where(swap, hi, lo)
        new_hi = jnp.where(swap, lo, hi)
        out.append(jnp.stack([new_lo, new_hi], axis=1).reshape(n))
    return out


def bitonic_sort(lanes: Sequence[jnp.ndarray]) -> list:
    """Full bitonic sort of flat power-of-two lanes by (row, col, src)."""
    n = lanes[0].shape[0]
    assert n & (n - 1) == 0, f"bitonic_sort needs power-of-two length, got {n}"
    idx = jnp.arange(n, dtype=jnp.int32)
    lanes = list(lanes)
    k = 2
    while k <= n:
        asc = ((idx // k) % 2) == 0  # alternate direction per k-block
        j = k // 2
        while j >= 1:
            lanes = compare_exchange(lanes, j, asc)
            j //= 2
        k *= 2
    return lanes


def bitonic_merge(lanes: Sequence[jnp.ndarray]) -> list:
    """Ascending merge of a *bitonic* flat sequence (e.g. sortedA ++ reversed
    sortedB) — only the final ``log2 n`` passes of the full sort."""
    n = lanes[0].shape[0]
    assert n & (n - 1) == 0, f"bitonic_merge needs power-of-two length, got {n}"
    asc = jnp.ones((n,), jnp.bool_)
    lanes = list(lanes)
    j = n // 2
    while j >= 1:
        lanes = compare_exchange(lanes, j, asc)
        j //= 2
    return lanes


def run_combine(rows, cols, vals, add_fn) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Segmented inclusive combine over runs of equal (row, col) keys in a
    *sorted* sequence: Hillis-Steele doubling, ``log2 n`` shift passes.

    Returns ``(vals_scanned, is_run_end)`` — the run-end element carries the
    full ``add_fn``-fold of its run.  Shift-based: no gathers.
    """
    vals = assoc.run_fold(rows, cols, vals, lambda prev, cur: add_fn(cur, prev))
    nr = jnp.concatenate([rows[1:], rows[-1:] * 0 - 1])
    nc = jnp.concatenate([cols[1:], cols[-1:] * 0 - 1])
    is_end = (rows != nr) | (cols != nc)
    return vals, is_end


def compact_monotone(lanes: Sequence[jnp.ndarray], keep: jnp.ndarray, fills):
    """Stable oblivious compaction in ``log2 n`` strided-shift passes.

    Moves the ``keep``-flagged elements of each lane to the prefix (original
    order preserved) and fills everything behind them with ``fills``.  Each
    survivor must travel left by the number of dead slots before it; that
    distance is non-decreasing in position, so moving it bit-by-bit (LSB
    first, one whole-array shift-by-``2^b`` + select per pass) is
    collision-free — the cheap-to-compile alternative to a full bitonic sort
    for the cascade kernel's per-merge compaction (``log n`` passes instead
    of ``log^2 n / 2``).  Like the other helpers: no gathers, no scatters,
    only constant-stride moves and selects.
    """
    n = lanes[0].shape[0]
    keep = keep.astype(jnp.bool_)
    dead = jnp.logical_not(keep).astype(jnp.int32)
    # exclusive prefix count of dead slots = how far each survivor travels
    shift = assoc.prefix_sum(dead) - dead
    cur = [jnp.where(keep, x, f) for x, f in zip(lanes, fills)]
    live = keep
    s = jnp.where(keep, shift, 0)
    d = 1
    while d < n:

        def shl(x, fill):
            return jnp.concatenate(
                [x[d:], jnp.full((d,), fill, x.dtype)]
            )

        moving = live & ((s & d) != 0)
        staying = live & ((s & d) == 0)
        arriving = shl(moving, False)  # element at i+d lands on i
        cur = [
            jnp.where(arriving, shl(x, f), jnp.where(staying, x, f))
            for x, f in zip(cur, fills)
        ]
        s = jnp.where(arriving, shl(s, 0), jnp.where(staying, s, 0))
        live = arriving | staying
        d *= 2
    return cur


def next_pow2(n: int) -> int:
    p = 1
    while p < n:
        p *= 2
    return p
