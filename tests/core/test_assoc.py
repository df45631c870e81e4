"""Unit + property tests for the associative-array algebra.

The property tests check the paper's Section II guarantees — commutativity,
associativity, distributivity, identities — which are exactly what licenses
the hierarchical cascade and out-of-order parallel updates.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ModuleNotFoundError:  # container without hypothesis: deterministic replay
    from _hypothesis_fallback import given, settings, st

from repro.core import assoc, semiring
from repro.core.assoc import PAD

SPACE = 16  # small key space to force collisions


def dense(a, sr=semiring.PLUS_TIMES):
    return np.asarray(assoc.to_dense(a, SPACE, SPACE, sr))


def mk(rng_seed, n, cap=None, sr=semiring.PLUS_TIMES, space=SPACE):
    rng = np.random.default_rng(rng_seed)
    r = rng.integers(0, space, n)
    c = rng.integers(0, space, n)
    v = rng.normal(size=n).astype(np.float32)
    a = assoc.from_triples(jnp.asarray(r), jnp.asarray(c), jnp.asarray(v), cap or 2 * n, sr)
    ref = np.full((space, space), sr.zero, np.float32)
    for i in range(n):
        ref[r[i], c[i]] = sr.add(ref[r[i], c[i]], v[i])
    return a, np.asarray(ref)


def test_from_triples_combines_duplicates():
    a, ref = mk(0, 64)
    np.testing.assert_allclose(dense(a), ref, rtol=1e-5)
    assert bool(assoc.is_sorted_unique(a))


def test_from_triples_respects_valid_mask():
    r = jnp.array([1, 2, 3], jnp.int32)
    c = jnp.array([1, 2, 3], jnp.int32)
    v = jnp.array([1.0, 2.0, 3.0])
    a = assoc.from_triples(r, c, v, cap=4, valid=jnp.array([True, False, True]))
    assert int(a.nnz) == 2
    assert float(assoc.get(a, 2, 2)) == 0.0


def test_add_matches_dense():
    a, ra = mk(1, 40)
    b, rb = mk(2, 40)
    c = assoc.add(a, b, cap=128)
    np.testing.assert_allclose(dense(c), ra + rb, rtol=1e-5)
    assert bool(assoc.is_sorted_unique(c))


def test_add_empty_is_identity():
    a, ra = mk(3, 30)
    z = assoc.empty(16)
    c = assoc.add(a, z, cap=a.capacity + 16)
    np.testing.assert_allclose(dense(c), ra, rtol=1e-6)


def test_elem_mul_matches_dense():
    a, ra = mk(4, 50)
    b, rb = mk(5, 50)
    c = assoc.elem_mul(a, b, cap=64)
    np.testing.assert_allclose(dense(c), ra * rb, rtol=1e-5, atol=1e-6)


def test_matmul_matches_dense():
    a, ra = mk(6, 30)
    b, rb = mk(7, 30)
    c = assoc.matmul(a, b, cap=512, max_fanout=SPACE)
    assert not bool(c.overflow)
    np.testing.assert_allclose(dense(c), ra @ rb, rtol=1e-4, atol=1e-5)


def test_matmul_fanout_overflow_flag():
    # B has a row with more entries than max_fanout -> flag must trip
    r = jnp.zeros((8,), jnp.int32)
    c = jnp.arange(8, dtype=jnp.int32)
    v = jnp.ones((8,))
    b = assoc.from_triples(r, c, v, cap=8)
    a = assoc.from_triples(jnp.array([0], jnp.int32), jnp.array([0], jnp.int32), jnp.array([1.0]), cap=1)
    out = assoc.matmul(a, b, cap=16, max_fanout=4)
    assert bool(out.overflow)


def test_transpose():
    a, ra = mk(8, 40)
    np.testing.assert_allclose(dense(assoc.transpose(a)), ra.T, rtol=1e-6)


def test_matmul_transpose_identity():
    # (AB)^T == B^T A^T  (paper Section II)
    a, _ = mk(9, 25)
    b, _ = mk(10, 25)
    ab_t = assoc.transpose(assoc.matmul(a, b, cap=512, max_fanout=SPACE))
    bt_at = assoc.matmul(
        assoc.transpose(b), assoc.transpose(a), cap=512, max_fanout=SPACE
    )
    np.testing.assert_allclose(dense(ab_t), dense(bt_at), rtol=1e-4, atol=1e-5)


def test_reduce_rows_degrees():
    a, ra = mk(11, 40)
    deg = assoc.reduce_rows(a)
    want = ra.sum(axis=1)
    got = np.asarray(assoc.to_dense(deg, SPACE, 1))[:, 0]
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


def test_get_and_extract_row():
    a, ra = mk(12, 40)
    for r in range(4):
        row = assoc.extract_row(a, r, cap=SPACE)
        np.testing.assert_allclose(dense(row)[r], ra[r], rtol=1e-6)
        for c in range(4):
            assert abs(float(assoc.get(a, r, c)) - ra[r, c]) < 1e-5


def test_overflow_flag_on_capacity():
    a, _ = mk(13, 64, cap=128)
    b, _ = mk(14, 64, cap=128)
    out = assoc.add(a, b, cap=4)  # deliberately too small
    assert bool(out.overflow)
    assert int(out.nnz) == 4


@settings(deadline=None, max_examples=25)
@given(
    seed_a=st.integers(0, 1000),
    seed_b=st.integers(0, 1000),
    seed_c=st.integers(0, 1000),
    srn=st.sampled_from(["plus.times", "max.plus", "min.plus", "max.min"]),
)
def test_property_add_commutative_associative(seed_a, seed_b, seed_c, srn):
    sr = semiring.get(srn)
    a, ra = mk(seed_a, 20, sr=sr)
    b, rb = mk(seed_b + 2000, 20, sr=sr)
    c, rc = mk(seed_c + 4000, 20, sr=sr)
    ab = assoc.add(a, b, cap=128, sr=sr)
    ba = assoc.add(b, a, cap=128, sr=sr)
    np.testing.assert_allclose(dense(ab, sr), dense(ba, sr), rtol=1e-5)
    ab_c = assoc.add(ab, c, cap=256, sr=sr)
    a_bc = assoc.add(a, assoc.add(b, c, cap=128, sr=sr), cap=256, sr=sr)
    np.testing.assert_allclose(dense(ab_c, sr), dense(a_bc, sr), rtol=1e-5)


@settings(deadline=None, max_examples=15)
@given(seed_a=st.integers(0, 1000), seed_b=st.integers(0, 1000), seed_c=st.integers(0, 1000))
def test_property_distributivity(seed_a, seed_b, seed_c):
    # A (x) (B (+) C) == (A (x) B) (+) (A (x) C)
    sr = semiring.PLUS_TIMES
    a, _ = mk(seed_a, 25)
    b, _ = mk(seed_b + 2000, 25)
    c, _ = mk(seed_c + 4000, 25)
    lhs = assoc.elem_mul(a, assoc.add(b, c, cap=128), cap=128)
    rhs = assoc.add(
        assoc.elem_mul(a, b, cap=64), assoc.elem_mul(a, c, cap=64), cap=128
    )
    np.testing.assert_allclose(dense(lhs), dense(rhs), rtol=1e-5, atol=1e-6)


@settings(deadline=None, max_examples=20)
@given(seed=st.integers(0, 10_000), n=st.integers(1, 200))
def test_property_invariants_hold(seed, n):
    a, _ = mk(seed, n, cap=2 * n)
    assert bool(assoc.is_sorted_unique(a))


def test_lex_searchsorted_matches_numpy():
    rng = np.random.default_rng(0)
    keys = np.sort(rng.integers(0, 50, 64).astype(np.int64) * 100 + rng.integers(0, 50, 64))
    kr = (keys // 100).astype(np.int32)
    kc = (keys % 100).astype(np.int32)
    q = rng.integers(0, 5500, 128)
    qr = (q // 100).astype(np.int32)
    qc = (q % 100).astype(np.int32)
    for side in ("left", "right"):
        got = np.asarray(
            assoc.lex_searchsorted(jnp.asarray(kr), jnp.asarray(kc), jnp.asarray(qr), jnp.asarray(qc), side)
        )
        want = np.searchsorted(keys, q, side=side)
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("cap", [8, 24, 40, 200])
def test_add_into_narrower_cap_matches_full_merge(cap):
    """Inputs wider than ``cap`` merge by their heads; the result (entries,
    nnz, overflow flag) is the full-width merge cut to ``cap``."""
    a, _ = mk(3, 48, cap=64)
    b, _ = mk(4, 48, cap=64)
    full = assoc.add(a, b, cap=128)
    got = assoc.add(a, b, cap=cap)
    n_full = int(full.nnz)
    keep = min(cap, 128)
    for f in ("rows", "cols", "vals"):
        want = np.asarray(getattr(full, f))[:keep]
        if cap > 128:
            want = np.concatenate([want, np.asarray(getattr(assoc.empty(cap - 128), f))])
        np.testing.assert_array_equal(np.asarray(getattr(got, f)), want)
    assert int(got.nnz) == min(n_full, cap)
    assert bool(got.overflow) == (n_full > cap)


# ---------------------------------------------------------------------------
# add against the rank merge it replaced
# ---------------------------------------------------------------------------

def _rank_merge_add(a, b, cap, sr=semiring.PLUS_TIMES):
    """Reference: merge by rank (two lex-searchsorted passes, one scatter per
    lane), fold runs and compact; A's copy of a shared key comes first."""
    a, b = assoc._head(a, cap), assoc._head(b, cap)
    m, n = a.capacity, b.capacity
    pos = jnp.concatenate([
        jnp.arange(m, dtype=jnp.int32)
        + assoc.lex_searchsorted(b.rows, b.cols, a.rows, a.cols, side="left"),
        jnp.arange(n, dtype=jnp.int32)
        + assoc.lex_searchsorted(a.rows, a.cols, b.rows, b.cols, side="right"),
    ])
    lanes = [
        jnp.zeros((m + n,), x.dtype).at[pos].set(jnp.concatenate([x, y]))
        for x, y in ((a.rows, b.rows), (a.cols, b.cols), (a.vals, b.vals))
    ]
    out = assoc._combine_sorted(*lanes, cap, sr)
    return assoc.Assoc(
        rows=out.rows, cols=out.cols, vals=out.vals, nnz=out.nnz,
        overflow=out.overflow | a.overflow | b.overflow,
    )


def _sorted_assoc(keys, cap, rng, sr=semiring.PLUS_TIMES, overflow=False):
    """An Assoc of capacity ``cap`` holding the unique ``keys`` (code
    ``row * 64 + col``) with random values, built without a device sort."""
    keys = np.unique(np.asarray(keys, np.int64))
    rows = np.full(cap, PAD, np.int32)
    cols = np.full(cap, PAD, np.int32)
    vals = np.full(cap, sr.zero, np.float32)
    rows[: keys.size], cols[: keys.size] = keys // 64, keys % 64
    vals[: keys.size] = rng.normal(size=keys.size)
    return assoc.Assoc(
        rows=jnp.asarray(rows), cols=jnp.asarray(cols), vals=jnp.asarray(vals),
        nnz=jnp.asarray(keys.size, jnp.int32), overflow=jnp.asarray(overflow),
    )


# (m, n, cap, live keys in A, in B, shared, semiring, inputs flagged)
_ADD_CASES = {
    "non_pow2_widths": (45, 39, 84, 30, 25, 10, "plus.times", False),
    "empty_a": (16, 37, 53, 0, 37, 0, "plus.times", False),
    "empty_b": (37, 16, 53, 37, 0, 0, "max.plus", False),
    "all_keys_shared": (40, 40, 80, 33, 33, 33, "plus.times", False),
    "no_keys_shared": (33, 31, 64, 33, 31, 0, "min.plus", False),
    "cap_below_total": (48, 40, 21, 40, 30, 5, "plus.times", False),
    "cap_above_total": (20, 9, 40, 17, 9, 4, "plus.times", False),
    "inputs_flagged_overflow": (24, 24, 48, 20, 20, 8, "plus.times", True),
    "order_sensitive_add": (45, 39, 84, 30, 25, 20, "union.first", False),
}


def _add_case_inputs(name, seed):
    m, n, cap, na, nb, shared, srn, flagged = _ADD_CASES[name]
    rng = np.random.default_rng(seed)
    space = rng.permutation(64 * 64)
    ka = space[:na]
    kb = np.concatenate([ka[:shared], space[na : na + nb - shared]])
    sr = semiring.get(srn)
    a = _sorted_assoc(ka, m, rng, sr, overflow=flagged)
    b = _sorted_assoc(kb, n, rng, sr)
    return a, b, cap, sr


def _assert_same_assoc(got, want):
    for f in ("rows", "cols", "vals", "nnz", "overflow"):
        np.testing.assert_array_equal(
            np.asarray(getattr(got, f)), np.asarray(getattr(want, f)), err_msg=f
        )


@pytest.mark.parametrize("name", [*_ADD_CASES, "vmap"])
def test_add_bit_exact_against_rank_merge(name):
    """The bitonic merge returns what the rank merge returned, bit for bit:
    entries, values, nnz and the overflow flag."""
    if name == "vmap":
        ins = [_add_case_inputs("non_pow2_widths", s) for s in range(3)]
        a = jax.tree.map(lambda *x: jnp.stack(x), *[i[0] for i in ins])
        b = jax.tree.map(lambda *x: jnp.stack(x), *[i[1] for i in ins])
        cap = ins[0][2]
        got = jax.vmap(lambda a, b: assoc.add(a, b, cap=cap))(a, b)
        want = jax.vmap(lambda a, b: _rank_merge_add(a, b, cap))(a, b)
    else:
        a, b, cap, sr = _add_case_inputs(name, 0)
        got = jax.jit(lambda a, b: assoc.add(a, b, cap=cap, sr=sr))(a, b)
        want = jax.jit(lambda a, b: _rank_merge_add(a, b, cap, sr))(a, b)
    _assert_same_assoc(got, want)
    if name == "cap_below_total":
        assert bool(got.overflow) and int(got.nnz) == cap
    if name == "inputs_flagged_overflow":
        assert bool(got.overflow)


def test_add_lowers_without_gather_scatter_or_sort():
    a, b, cap, sr = _add_case_inputs("non_pow2_widths", 0)
    text = jax.jit(lambda a, b: assoc.add(a, b, cap=cap, sr=sr)).lower(a, b).as_text()
    for op in ("gather", "scatter", "sort"):
        assert f"stablehlo.{op}" not in text, op
