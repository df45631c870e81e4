"""Static-shape hypersparse associative arrays (the paper's core object).

An :class:`Assoc` stores the nonzero triples of a 2-D associative array
``A : K1 x K2 -> V`` in sorted-COO form:

* ``rows``/``cols`` — ``int32[cap]`` key pair, sorted lexicographically by
  ``(row, col)``; dead slots are padded with ``PAD = INT32_MAX``.
* ``vals`` — ``f32[cap]`` values; dead slots hold the semiring zero.
* ``nnz`` — scalar count of live entries.

Why static shapes: XLA (and the TPU target) cannot reallocate on device, so
every array has a fixed *capacity* and a dynamic *count*, with all operations
masked.  This is the one structural assumption changed from the paper's
CPU/Matlab implementation (see DESIGN.md section 2); all algebraic semantics
are preserved exactly.

Keys are device-side ``int32`` pairs (IPv4 src/dst fit exactly; strings are
dictionary-encoded host-side in :mod:`repro.data.dictionary`).  We deliberately
avoid int64: JAX defaults to 32-bit and TPU vector lanes are 32-bit native.
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax

from .semiring import PLUS_TIMES, Semiring

PAD = jnp.iinfo(jnp.int32).max  # sentinel key for dead slots (sorts last)


@dataclasses.dataclass(frozen=True)
class OpPolicy:
    """Cap policy for operator-overloaded Assoc algebra (``A + B``, ``A @ B``…).

    Every Assoc operation needs a static output capacity; the module
    functions take it explicitly, the operators read it from the active
    policy (see :func:`cap_policy`).  ``None`` caps mean "derive from the
    operands": ``add_cap = a.cap + b.cap``, ``mul_cap = min(a.cap, b.cap)``,
    ``matmul_cap = a.cap + b.cap``, ``row_cap = a.cap``.
    """

    sr: Semiring = PLUS_TIMES
    add_cap: int | None = None
    mul_cap: int | None = None
    matmul_cap: int | None = None
    max_fanout: int = 32
    row_cap: int | None = None


_DEFAULT_POLICY = OpPolicy()
# ContextVar (not a module-global stack): each thread / async task scopes
# its own policy, so concurrent cap_policy blocks cannot corrupt each other
_policy_var: contextvars.ContextVar[OpPolicy] = contextvars.ContextVar(
    "assoc_op_policy", default=_DEFAULT_POLICY
)


def current_policy() -> OpPolicy:
    """The innermost active :func:`cap_policy`, or the defaults."""
    return _policy_var.get()


@contextlib.contextmanager
def cap_policy(**overrides):
    """Scope an :class:`OpPolicy` for operator-overloaded algebra::

        with assoc.cap_policy(matmul_cap=4096, max_fanout=24, sr=MAX_MIN):
            C = (A @ B) & A

    Overrides stack: nested ``cap_policy`` blocks start from the enclosing
    policy, not the defaults.
    """
    token = _policy_var.set(dataclasses.replace(current_policy(), **overrides))
    try:
        yield _policy_var.get()
    finally:
        _policy_var.reset(token)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class Assoc:
    """Sorted-COO hypersparse associative array with static capacity.

    Beyond the module functions, Assoc carries the paper's spreadsheet-style
    operator algebra (Fig. 1 one-liners), reading output capacities and the
    semiring from the active :func:`cap_policy`:

    * ``A + B``  — element-wise semiring add  (:func:`add`, table union)
    * ``A & B``  — element-wise semiring mul  (:func:`elem_mul`, intersection)
    * ``A @ B``  — semiring array multiply    (:func:`matmul`)
    * ``A.T``    — transpose
    * ``A[r, :]`` / ``A[:, c]`` / ``A[r, c]`` — row slice / col slice / point query
    * ``A.topk(k)`` — k heaviest entries (ids, values)
    """

    rows: jax.Array  # int32[cap]
    cols: jax.Array  # int32[cap]
    vals: jax.Array  # f32[cap]
    nnz: jax.Array  # int32[]
    overflow: jax.Array  # bool[] — sticky: some op exceeded an output capacity

    @property
    def capacity(self) -> int:
        return self.rows.shape[0]

    def __repr__(self) -> str:  # pragma: no cover - cosmetic
        return f"Assoc(cap={self.capacity})"

    # -- operator algebra (delegates to module functions via cap_policy) ----
    def __add__(self, other: "Assoc") -> "Assoc":
        p = current_policy()
        cap = p.add_cap if p.add_cap is not None else self.capacity + other.capacity
        return add(self, other, cap=cap, sr=p.sr)

    def __and__(self, other: "Assoc") -> "Assoc":
        p = current_policy()
        cap = p.mul_cap if p.mul_cap is not None else min(self.capacity, other.capacity)
        return elem_mul(self, other, cap=cap, sr=p.sr)

    def __matmul__(self, other: "Assoc") -> "Assoc":
        p = current_policy()
        cap = (
            p.matmul_cap
            if p.matmul_cap is not None
            else self.capacity + other.capacity
        )
        return matmul(self, other, cap=cap, max_fanout=p.max_fanout, sr=p.sr)

    @property
    def T(self) -> "Assoc":
        return transpose(self, sr=current_policy().sr)

    def __getitem__(self, key):
        if not (isinstance(key, tuple) and len(key) == 2):
            raise TypeError(
                "Assoc indexing is 2-D: A[r, :], A[:, c], or A[r, c]"
            )
        p = current_policy()
        r, c = key
        for s in (r, c):
            if isinstance(s, slice) and s != slice(None):
                raise TypeError(
                    "Assoc slicing supports only the full ':' slice "
                    "(bounded/stepped slices would silently drop keys); use "
                    "extract_row / elem_mul masks for bounded selections"
                )
        r_all = isinstance(r, slice)
        c_all = isinstance(c, slice)
        if r_all and c_all:
            return self
        if r_all:  # column slice via the transpose, keys stay (row, col)
            got = extract_row(
                transpose(self, sr=p.sr), c,
                cap=p.row_cap if p.row_cap is not None else self.capacity,
                sr=p.sr,
            )
            return transpose(got, sr=p.sr)
        if c_all:
            return extract_row(
                self, r,
                cap=p.row_cap if p.row_cap is not None else self.capacity,
                sr=p.sr,
            )
        return get(self, r, c, sr=p.sr)

    def topk(self, k: int) -> Tuple[jax.Array, jax.Array]:
        """The ``k`` largest values: ``(row_ids [k], values [k])``.

        On a degree array (keys ``(vertex, 0)``) this is the paper's
        heavy-hitters query; dead slots rank ``-inf`` so they never place.
        """
        ranked = jnp.where(self.rows != PAD, self.vals, -jnp.inf)
        top_vals, idx = lax.top_k(ranked, k)
        return self.rows[idx], top_vals


# ---------------------------------------------------------------------------
# construction
# ---------------------------------------------------------------------------

def empty(cap: int, sr: Semiring = PLUS_TIMES, dtype=jnp.float32) -> Assoc:
    """An all-zero associative array with room for ``cap`` nonzeros."""
    return Assoc(
        rows=jnp.full((cap,), PAD, jnp.int32),
        cols=jnp.full((cap,), PAD, jnp.int32),
        vals=jnp.full((cap,), sr.zero, dtype),
        nnz=jnp.zeros((), jnp.int32),
        overflow=jnp.zeros((), jnp.bool_),
    )


def from_triples(
    rows: jax.Array,
    cols: jax.Array,
    vals: jax.Array,
    cap: int,
    sr: Semiring = PLUS_TIMES,
    valid: jax.Array | None = None,
) -> Assoc:
    """Build an Assoc from (possibly duplicated, unsorted) triples.

    Duplicated keys are combined with ``sr.add`` — this is the paper's
    ``A = Assoc(k1, k2, v)`` constructor semantics.  ``valid`` optionally
    masks input slots (invalid slots are dropped).
    """
    rows = rows.astype(jnp.int32)
    cols = cols.astype(jnp.int32)
    if valid is not None:
        rows = jnp.where(valid, rows, PAD)
        cols = jnp.where(valid, cols, PAD)
        vals = jnp.where(valid, vals, jnp.asarray(sr.zero, vals.dtype))
    order = jnp.lexsort((cols, rows))
    return _combine_sorted(rows[order], cols[order], vals[order], cap, sr)


# ---------------------------------------------------------------------------
# internal: combine runs of equal keys in a sorted triple list, then compact
# ---------------------------------------------------------------------------

# The log-step passes below run as ``fori_loop``s over a shift of
# ``d = 2**i``: one loop body per pass kind keeps the program (and XLA's TPU
# compile time, which grows with program size) independent of the width.

def _log_steps(n: int) -> int:
    """Passes of a doubling scan over ``n`` elements (``d`` up to < n)."""
    return max(0, n - 1).bit_length()


def prefix_sum(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum by log-step shifted adds.  Same result as
    ``jnp.cumsum`` on integers, but XLA's TPU compiler builds it in about
    3 s where ``cumsum`` takes 12-20 s at every width, once per merge."""
    idx = jnp.arange(x.shape[0], dtype=jnp.int32)

    def step(i, x):
        d = jnp.left_shift(1, i)
        return x + jnp.where(idx >= d, jnp.roll(x, d), 0)

    return lax.fori_loop(0, _log_steps(x.shape[0]), step, x)


def run_fold(rows: jax.Array, cols: jax.Array, vals: jax.Array, add) -> jax.Array:
    """Inclusive ``add``-fold over each run of equal (row, col) keys in
    lexicographically sorted triples; a run's last element carries the
    fold of the whole run.

    Log-step shifted adds (Hillis-Steele): ``log2 n`` passes, each a
    contiguous shift and a select.  Equal keys are contiguous, so "same key
    as the element ``d`` back" means "same run".  (``lax.associative_scan``
    is the textbook form, but its strided slices make XLA's TPU compiler
    take minutes at a million entries; this compiles in seconds at any
    width.)
    """
    idx = jnp.arange(rows.shape[0], dtype=jnp.int32)

    def step(i, vals):
        d = jnp.left_shift(1, i)
        same = (
            (rows == jnp.roll(rows, d)) & (cols == jnp.roll(cols, d)) & (idx >= d)
        )
        return jnp.where(same, add(jnp.roll(vals, d), vals), vals)

    return lax.fori_loop(0, _log_steps(rows.shape[0]), step, vals)


def _lex_less(xs, ys) -> jax.Array:
    """Strict lexicographic ``xs < ys`` over parallel key lanes."""
    less = xs[-1] < ys[-1]
    for x, y in zip(xs[-2::-1], ys[-2::-1]):
        less = (x < y) | ((x == y) & less)
    return less


def looped_bitonic_merge(lanes, n_keys: int) -> tuple:
    """Sort a bitonic sequence (non-increasing, then non-decreasing) into
    ascending lexicographic order of its first ``n_keys`` lanes; the other
    lanes travel with their keys.

    The width need not be a power of two: the sequence counts as padded to
    one with keys above every key, which never move, so a slot whose
    partner lies past the end keeps its element.  ``ceil(log2 n)``
    compare-exchange passes at halving distance ``d``: slot ``i`` meets
    ``i + d`` when ``i & d`` is 0 (and keeps the smaller) and ``i - d``
    otherwise (and keeps the larger), each partner one contiguous shift
    away.  The passes run as one ``fori_loop`` body, so the program does
    not grow with the width.
    """
    n = lanes[0].shape[0]
    steps = _log_steps(n)
    idx = jnp.arange(n, dtype=jnp.int32)
    top = 1 << max(0, steps - 1)

    def step(i, lanes):
        d = jnp.right_shift(top, i)
        upper = (idx & d) != 0
        partner = [jnp.where(upper, jnp.roll(x, d), jnp.roll(x, -d)) for x in lanes]
        own_k, par_k = lanes[:n_keys], partner[:n_keys]
        take = jnp.where(
            upper, _lex_less(own_k, par_k), _lex_less(par_k, own_k) & (idx + d < n)
        )
        return tuple(jnp.where(take, p, x) for p, x in zip(partner, lanes))

    return lax.fori_loop(0, steps, step, tuple(lanes))


def looped_compact(lanes, keep: jax.Array, fills) -> list:
    """Stable compaction: the ``keep``-flagged elements of each lane move to
    the prefix in their order, and ``fills`` fill the rest.

    Each survivor travels left by the number of dead slots before it, a
    distance that never falls along the array, so moving it by one bit of
    that distance a pass, least significant first, never lands two
    survivors on one slot.  ``ceil(log2 n)`` passes of one contiguous
    shift and a select, as one ``fori_loop`` body; the distance rides
    along as a lane, -1 on dead slots.
    """
    n = lanes[0].shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    dead = jnp.logical_not(keep).astype(jnp.int32)
    dist = jnp.where(keep, prefix_sum(dead) - dead, -1)
    lanes = tuple(jnp.where(keep, x, f) for x, f in zip(lanes, fills))

    def step(i, carry):
        dist, lanes = carry
        d = jnp.left_shift(1, i)
        stay = (dist >= 0) & ((dist & d) == 0)
        dist_in = jnp.where(idx + d < n, jnp.roll(dist, -d), -1)
        arrive = (dist_in >= 0) & ((dist_in & d) != 0)
        lanes = tuple(
            jnp.where(arrive, jnp.roll(x, -d), jnp.where(stay, x, f))
            for x, f in zip(lanes, fills)
        )
        return jnp.where(arrive, dist_in, jnp.where(stay, dist, -1)), lanes

    return list(lax.fori_loop(0, _log_steps(n), step, (dist, lanes))[1])


def _combine_sorted(
    rows: jax.Array, cols: jax.Array, vals: jax.Array, cap: int, sr: Semiring
) -> Assoc:
    """Given lexicographically sorted triples, fold duplicate keys with
    ``sr.add`` and compact the survivors into a fresh Assoc of capacity
    ``cap``.  PAD-keyed slots are dropped."""

    acc = run_fold(rows, cols, vals, sr.add)
    nxt_r = jnp.concatenate([rows[1:], jnp.full((1,), -1, jnp.int32)])
    nxt_c = jnp.concatenate([cols[1:], jnp.full((1,), -1, jnp.int32)])
    is_end = (rows != nxt_r) | (cols != nxt_c)  # last element of each key-run
    keep = is_end & (rows != PAD)
    return _compact(rows, cols, acc, keep, cap, sr)


def _compact(rows, cols, vals, keep, cap: int, sr: Semiring) -> Assoc:
    n_keep = keep.sum(dtype=jnp.int32)
    pos = prefix_sum(keep.astype(jnp.int32)) - 1
    pos = jnp.where(keep, pos, cap)  # out-of-range -> dropped by mode="drop"
    out = empty(cap, sr, vals.dtype)
    out_rows = out.rows.at[pos].set(rows, mode="drop")
    out_cols = out.cols.at[pos].set(cols, mode="drop")
    out_vals = out.vals.at[pos].set(vals, mode="drop")
    return Assoc(
        rows=out_rows,
        cols=out_cols,
        vals=out_vals,
        nnz=jnp.minimum(n_keep, cap),
        overflow=n_keep > cap,
    )


# ---------------------------------------------------------------------------
# lexicographic binary search over (row, col) key pairs
# ---------------------------------------------------------------------------

def lex_searchsorted(
    kr: jax.Array,
    kc: jax.Array,
    qr: jax.Array,
    qc: jax.Array,
    side: str = "left",
) -> jax.Array:
    """``jnp.searchsorted`` generalized to lexicographic (row, col) pairs.

    ``kr``/``kc`` must be lexicographically sorted.  Vectorized binary search:
    ``ceil(log2 n)`` rounds of gathered comparisons — no int64 packing needed.
    """
    n = kr.shape[0]
    qr = jnp.asarray(qr, jnp.int32)
    qc = jnp.asarray(qc, jnp.int32)
    lo = jnp.zeros(qr.shape, jnp.int32)
    hi = jnp.full(qr.shape, n, jnp.int32)

    def step(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) >> 1
        mr = kr[mid]
        mc = kc[mid]
        if side == "left":
            go_right = (mr < qr) | ((mr == qr) & (mc < qc))
        else:
            go_right = (mr < qr) | ((mr == qr) & (mc <= qc))
        # guard: once converged (lo == hi), clamped gathers must not move lo
        go_right = go_right & (mid < hi)
        return (
            jnp.where(go_right, mid + 1, lo),
            jnp.where(go_right, hi, jnp.minimum(hi, mid)),
        )

    rounds = max(1, int(math.ceil(math.log2(max(n, 2)))) + 1)
    return lax.fori_loop(0, rounds, step, (lo, hi))[0]


# ---------------------------------------------------------------------------
# element-wise addition  (database union — the hierarchy's only required op)
# ---------------------------------------------------------------------------

def _head(x: Assoc, cap: int) -> Assoc:
    """The first ``cap`` slots of ``x``: the only ones a merge into ``cap``
    slots can keep.  Keys are sorted and unique, so the entry in slot ``i``
    has ``i`` distinct keys before it in any merge; a live entry past the
    head therefore means the merge overflows ``cap``, and is flagged so."""
    if x.capacity <= cap:
        return x
    return Assoc(
        rows=x.rows[:cap],
        cols=x.cols[:cap],
        vals=x.vals[:cap],
        nnz=jnp.minimum(x.nnz, cap),
        overflow=x.overflow | (x.rows[cap] != PAD),
    )


def add(a: Assoc, b: Assoc, cap: int | None = None, sr: Semiring = PLUS_TIMES) -> Assoc:
    """``C = A (+) B`` — element-wise semiring addition (table union).

    Both inputs are sorted, so reversed A followed by B is a bitonic
    sequence: :func:`looped_bitonic_merge` sorts it in ``ceil(log2(m+n))``
    compare-exchange passes, each key shared by A and B lands as an
    adjacent pair that one pass folds, and :func:`looped_compact` closes
    the gaps.  Every element reaches its place through contiguous shifts
    and selects: no gather, scatter or sort, so the TPU streams each pass
    through HBM at any width.  A semiring whose ``add`` does not commute
    carries a source lane that puts A's copy of a shared key first.  An
    input wider than ``cap`` enters by its first ``cap`` slots
    (:func:`_head`), so the cost follows ``cap``: a snapshot of a 151M-slot
    top layer at ``cap`` 2M merges 2M.
    """
    if cap is None:
        cap = a.capacity + b.capacity
    a, b = _head(a, cap), _head(b, cap)
    m, n = a.capacity, b.capacity
    total = m + n
    # descending (A reversed, its PAD tail first) then ascending (B)
    keys = [jnp.concatenate([x[::-1], y]) for x, y in ((a.rows, b.rows), (a.cols, b.cols))]
    if not sr.add_commutes:
        keys.append(jnp.concatenate([jnp.zeros((m,), jnp.int32), jnp.ones((n,), jnp.int32)]))
    vals = jnp.concatenate([a.vals[::-1], b.vals])
    *keys, vals = looped_bitonic_merge([*keys, vals], len(keys))
    rows, cols = keys[:2]
    # keys are unique in each input, so a run of equal keys is at most a pair
    idx = jnp.arange(total, dtype=jnp.int32)
    prev_same = (rows == jnp.roll(rows, 1)) & (cols == jnp.roll(cols, 1)) & (idx > 0)
    next_same = (
        (rows == jnp.roll(rows, -1)) & (cols == jnp.roll(cols, -1)) & (idx < total - 1)
    )
    vals = jnp.where(prev_same, sr.add(jnp.roll(vals, 1), vals), vals)
    keep = jnp.logical_not(next_same) & (rows != PAD)
    n_keep = keep.sum(dtype=jnp.int32)
    fills = (PAD, PAD, jnp.asarray(sr.zero, vals.dtype))
    out = looped_compact((rows, cols, vals), keep, fills)
    if total >= cap:
        out = [x[:cap] for x in out]
    else:
        out = [
            jnp.concatenate([x, jnp.full((cap - total,), f, x.dtype)])
            for x, f in zip(out, fills)
        ]
    return Assoc(
        rows=out[0],
        cols=out[1],
        vals=out[2],
        nnz=jnp.minimum(n_keep, cap),
        overflow=(n_keep > cap) | a.overflow | b.overflow,
    )


# ---------------------------------------------------------------------------
# element-wise multiplication  (database intersection)
# ---------------------------------------------------------------------------

def elem_mul(
    a: Assoc, b: Assoc, cap: int | None = None, sr: Semiring = PLUS_TIMES
) -> Assoc:
    """``C = A (x) B`` — element-wise semiring multiplication (intersection)."""
    if cap is None:
        cap = min(a.capacity, b.capacity)
    idx = lex_searchsorted(b.rows, b.cols, a.rows, a.cols, side="left")
    idx_c = jnp.minimum(idx, b.capacity - 1)
    hit = (b.rows[idx_c] == a.rows) & (b.cols[idx_c] == a.cols) & (a.rows != PAD)
    vals = jnp.where(hit, sr.mul(a.vals, b.vals[idx_c]), jnp.asarray(sr.zero, a.vals.dtype))
    rows = jnp.where(hit, a.rows, PAD)
    cols = jnp.where(hit, a.cols, PAD)
    # already sorted (subset of A's ordering) — just combine/compact
    out = _combine_sorted(rows, cols, vals, cap, sr)
    return dataclasses.replace(out, overflow=out.overflow | a.overflow | b.overflow)


# ---------------------------------------------------------------------------
# array multiplication  C = A (+).(x) B   (table transformation)
# ---------------------------------------------------------------------------

def matmul(
    a: Assoc,
    b: Assoc,
    cap: int,
    max_fanout: int,
    sr: Semiring = PLUS_TIMES,
) -> Assoc:
    """Semiring spGEMM via sort-merge join on the inner key.

    Static-shape contract: each A-entry may join with at most ``max_fanout``
    B-entries sharing its inner key; if any key's true fanout exceeds the
    bound, the result's ``overflow`` flag is set (entries beyond the bound are
    dropped).  ``cap`` bounds the output nonzeros.  This is the honest price
    of hypersparse spGEMM under XLA static shapes and is documented API.
    """
    at = transpose(a, sr=sr)  # sorted by (inner key = A's col, A's row)
    # run of B rows equal to each AT entry's inner key
    lo = jnp.searchsorted(b.rows, at.rows, side="left").astype(jnp.int32)
    hi = jnp.searchsorted(b.rows, at.rows, side="right").astype(jnp.int32)
    fan = hi - lo
    clipped = jnp.any((fan > max_fanout) & (at.rows != PAD))
    m = at.capacity
    f = max_fanout
    idx = lo[:, None] + jnp.arange(f, dtype=jnp.int32)[None, :]  # [m, f]
    ok = (idx < hi[:, None]) & (at.rows[:, None] != PAD)
    idx_c = jnp.minimum(idx, b.capacity - 1)
    prod_rows = jnp.where(ok, at.cols[:, None], PAD)  # AT.col is A's row key
    prod_cols = jnp.where(ok, b.cols[idx_c], PAD)
    prod_vals = jnp.where(
        ok, sr.mul(at.vals[:, None], b.vals[idx_c]), jnp.asarray(sr.zero, a.vals.dtype)
    )
    out = from_triples(
        prod_rows.reshape(m * f),
        prod_cols.reshape(m * f),
        prod_vals.reshape(m * f),
        cap,
        sr,
    )
    return dataclasses.replace(
        out, overflow=out.overflow | clipped | a.overflow | b.overflow
    )


# ---------------------------------------------------------------------------
# transpose, reductions, queries
# ---------------------------------------------------------------------------

def transpose(a: Assoc, sr: Semiring = PLUS_TIMES) -> Assoc:
    """``A^T`` — swap row/col keys and re-sort (keys unique, nothing combines)."""
    order = jnp.lexsort((a.rows, a.cols))
    out = Assoc(
        rows=a.cols[order],
        cols=a.rows[order],
        vals=a.vals[order],
        nnz=a.nnz,
        overflow=a.overflow,
    )
    return out


def reduce_rows(a: Assoc, cap: int | None = None, sr: Semiring = PLUS_TIMES) -> Assoc:
    """Fold each row with ``sr.add`` (out-degree when values count edges).

    Returns an Assoc whose keys are ``(row, 0)``.
    """
    if cap is None:
        cap = a.capacity
    rows = a.rows
    cols = jnp.where(rows != PAD, 0, PAD).astype(jnp.int32)
    return _combine_sorted(rows, cols, a.vals, cap, sr)


def reduce_cols(a: Assoc, cap: int | None = None, sr: Semiring = PLUS_TIMES) -> Assoc:
    """Fold each column with ``sr.add`` (in-degree); keys become ``(col, 0)``."""
    if cap is None:
        cap = a.capacity
    t = transpose(a, sr)
    return reduce_rows(t, cap, sr)


def get(a: Assoc, r, c, sr: Semiring = PLUS_TIMES) -> jax.Array:
    """Point query ``A(r, c)`` — semiring zero when absent."""
    r = jnp.asarray(r, jnp.int32)
    c = jnp.asarray(c, jnp.int32)
    scalar = r.ndim == 0
    rq = jnp.atleast_1d(r)
    cq = jnp.atleast_1d(c)
    idx = lex_searchsorted(a.rows, a.cols, rq, cq, side="left")
    idx_c = jnp.minimum(idx, a.capacity - 1)
    hit = (a.rows[idx_c] == rq) & (a.cols[idx_c] == cq)
    out = jnp.where(hit, a.vals[idx_c], jnp.asarray(sr.zero, a.vals.dtype))
    return out[0] if scalar else out


def extract_row(a: Assoc, r, cap: int, sr: Semiring = PLUS_TIMES) -> Assoc:
    """Row slice ``A(r, :)`` (e.g. nearest-neighbours of a vertex, Fig. 1)."""
    keep = a.rows == jnp.asarray(r, jnp.int32)
    rows = jnp.where(keep, a.rows, PAD)
    cols = jnp.where(keep, a.cols, PAD)
    vals = jnp.where(keep, a.vals, jnp.asarray(sr.zero, a.vals.dtype))
    return _combine_sorted(rows, cols, vals, cap, sr)


def nnz(a: Assoc) -> jax.Array:
    return a.nnz


def to_dense(a: Assoc, nrows: int, ncols: int, sr: Semiring = PLUS_TIMES) -> jax.Array:
    """Materialize as dense (small arrays / tests only).

    A well-formed Assoc has unique keys, so a plain scatter-set suffices;
    pad slots carry out-of-range PAD keys and are dropped by ``mode="drop"``.
    """
    dense = jnp.full((nrows, ncols), sr.zero, a.vals.dtype)
    return dense.at[a.rows, a.cols].set(a.vals, mode="drop")


def is_sorted_unique(a: Assoc) -> jax.Array:
    """Invariant check used by property tests: live keys strictly increasing,
    live entries a prefix, pads consistent, nnz matches."""
    r, c = a.rows, a.cols
    ok_pairs = (r[:-1] < r[1:]) | ((r[:-1] == r[1:]) & (c[:-1] < c[1:]))
    live = (r[:-1] != PAD) & (r[1:] != PAD)
    within = jnp.all(jnp.where(live, ok_pairs, True))
    idx = jnp.arange(r.shape[0], dtype=jnp.int32)
    count_ok = jnp.sum((r != PAD).astype(jnp.int32)) == a.nnz
    prefix_ok = jnp.all((r != PAD) == (idx < a.nnz))  # live entries are a prefix
    pad_ok = jnp.all((r == PAD) == (c == PAD))
    return within & count_ok & prefix_ok & pad_ok
