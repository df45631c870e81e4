"""Per-kernel validation: sweep shapes/dtypes/semirings and assert_allclose
against the pure-jnp ref.py oracles (kernels run in interpret mode on CPU)."""
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
from repro.kernels import common
from repro.kernels.merge_add import ops as merge_ops
from repro.kernels.merge_add.ref import merge_add_ref
from repro.kernels.scatter_add import ops as scatter_ops
from repro.kernels.scatter_add.ref import scatter_add_ref
from repro.kernels.sort_dedup import ops as sort_ops


def _mk(seed, n, cap, space, sr=semiring.PLUS_TIMES, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.integers(0, space, n), jnp.int32)
    c = jnp.asarray(rng.integers(0, space, n), jnp.int32)
    v = jnp.asarray(rng.normal(size=n), dtype)
    return assoc.from_triples(r, c, v, cap, sr)


# ---------------------------------------------------------------- merge_add
@pytest.mark.parametrize("capa,capb", [(8, 8), (16, 48), (64, 64), (128, 384), (256, 256)])
@pytest.mark.parametrize("srn", ["plus.times", "max.plus", "min.plus"])
def test_merge_add_shapes_semirings(capa, capb, srn):
    sr = semiring.get(srn)
    a = _mk(capa, capa // 2, capa, 64, sr)
    b = _mk(capb + 1, capb // 2, capb, 64, sr)
    got = merge_ops.merge_add(a, b, cap=capa + capb, sr=sr)
    want_r, want_c, want_v, want_nnz, _ = merge_add_ref(
        a.rows, a.cols, a.vals, b.rows, b.cols, b.vals, capa + capb, sr
    )
    np.testing.assert_array_equal(np.asarray(got.rows), np.asarray(want_r))
    np.testing.assert_array_equal(np.asarray(got.cols), np.asarray(want_c))
    np.testing.assert_allclose(np.asarray(got.vals), np.asarray(want_v), rtol=1e-5)
    assert int(got.nnz) == int(want_nnz)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_merge_add_dtypes(dtype):
    sr = semiring.PLUS_TIMES
    a = _mk(3, 16, 32, 16, sr, dtype)
    b = _mk(4, 16, 32, 16, sr, dtype)
    got = merge_ops.merge_add(a, b, cap=64, sr=sr)
    want = merge_add_ref(a.rows, a.cols, a.vals, b.rows, b.cols, b.vals, 64, sr)
    np.testing.assert_allclose(
        np.asarray(got.vals, np.float32), np.asarray(want[2], np.float32), rtol=2e-2
    )


def test_merge_add_empty_inputs():
    sr = semiring.PLUS_TIMES
    a = _mk(5, 8, 16, 16, sr)
    z = assoc.empty(16, sr)
    got = merge_ops.merge_add(a, z, cap=32, sr=sr)
    np.testing.assert_allclose(
        np.asarray(assoc.to_dense(got, 16, 16)), np.asarray(assoc.to_dense(a, 16, 16))
    )
    got2 = merge_ops.merge_add(z, z, cap=8, sr=sr)
    assert int(got2.nnz) == 0


@settings(deadline=None, max_examples=15)
@given(
    seed=st.integers(0, 10_000),
    na=st.integers(0, 64),
    nb=st.integers(0, 64),
    space=st.sampled_from([4, 32, 1024]),
)
def test_property_merge_add_matches_oracle(seed, na, nb, space):
    sr = semiring.PLUS_TIMES
    a = _mk(seed, na, 64, space, sr)
    b = _mk(seed + 77, nb, 64, space, sr)
    got = merge_ops.merge_add(a, b, cap=128, sr=sr)
    ref = assoc.add(a, b, cap=128, sr=sr)
    np.testing.assert_array_equal(np.asarray(got.rows), np.asarray(ref.rows))
    np.testing.assert_allclose(np.asarray(got.vals), np.asarray(ref.vals), rtol=1e-5)
    assert int(got.nnz) == int(ref.nnz)


# ---------------------------------------------------------------- sort_dedup
@pytest.mark.parametrize("n", [8, 32, 100, 256, 1000])
@pytest.mark.parametrize("srn", ["plus.times", "max.plus"])
def test_sort_dedup_shapes(n, srn):
    sr = semiring.get(srn)
    rng = np.random.default_rng(n)
    r = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
    c = jnp.asarray(rng.integers(0, 50, n), jnp.int32)
    v = jnp.asarray(rng.normal(size=n), jnp.float32)
    got = sort_ops.from_triples(r, c, v, cap=n, sr=sr)
    ref = assoc.from_triples(r, c, v, cap=n, sr=sr)
    np.testing.assert_array_equal(np.asarray(got.rows), np.asarray(ref.rows))
    np.testing.assert_allclose(np.asarray(got.vals), np.asarray(ref.vals), rtol=1e-5)
    assert int(got.nnz) == int(ref.nnz)


def test_sort_dedup_all_same_key():
    n = 64
    r = jnp.zeros((n,), jnp.int32)
    c = jnp.zeros((n,), jnp.int32)
    v = jnp.ones((n,), jnp.float32)
    got = sort_ops.from_triples(r, c, v, cap=n)
    assert int(got.nnz) == 1
    assert float(got.vals[0]) == n


@settings(deadline=None, max_examples=12)
@given(
    seed=st.integers(0, 10_000),
    n=st.sampled_from([1, 37, 128, 300]),  # fixed shapes: avoid recompile churn
    space=st.sampled_from([2, 64, 4096]),
)
def test_property_sort_dedup_matches_oracle(seed, n, space):
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.integers(0, space, n), jnp.int32)
    c = jnp.asarray(rng.integers(0, space, n), jnp.int32)
    v = jnp.asarray(rng.normal(size=n), jnp.float32)
    got = sort_ops.from_triples(r, c, v, cap=n)
    ref = assoc.from_triples(r, c, v, cap=n)
    np.testing.assert_array_equal(np.asarray(got.rows), np.asarray(ref.rows))
    np.testing.assert_array_equal(np.asarray(got.cols), np.asarray(ref.cols))
    np.testing.assert_allclose(np.asarray(got.vals), np.asarray(ref.vals), rtol=1e-5)


# ---------------------------------------------------------------- scatter_add
@pytest.mark.parametrize("v,d,k", [(32, 8, 4), (64, 16, 8), (128, 128, 32), (1000, 64, 100)])
def test_scatter_add_shapes(v, d, k):
    rng = np.random.default_rng(v + d + k)
    table = jnp.asarray(rng.normal(size=(v, d)), jnp.float32)
    ids_np = np.sort(rng.choice(v, size=k, replace=False)).astype(np.int32)
    ids_np[k // 2 :] = np.sort(ids_np[k // 2 :])
    ids = jnp.asarray(ids_np)
    rows = jnp.asarray(rng.normal(size=(k, d)), jnp.float32)
    want = np.asarray(scatter_add_ref(ids, rows, table))
    got = scatter_ops.scatter_add(ids, rows, table)  # donates the table
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5)


def test_scatter_add_pad_ids_skipped():
    table = jnp.zeros((16, 4))
    ids = jnp.asarray([2, 5, PAD, PAD], jnp.int32)
    rows = jnp.ones((4, 4))
    got = np.asarray(scatter_ops.scatter_add(ids, rows, table))
    assert got[2].sum() == 4 and got[5].sum() == 4
    assert got.sum() == 8  # PAD rows must not land anywhere


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_scatter_add_dtypes(dtype):
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(size=(64, 32)), dtype)
    ids = jnp.asarray([1, 7, 9], jnp.int32)
    rows = jnp.asarray(rng.normal(size=(3, 32)), dtype)
    want = np.asarray(scatter_add_ref(ids, rows, table), np.float32)
    got = scatter_ops.scatter_add(ids, rows, table)  # donates the table
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=2e-2)


# ---------------------------------------------------------------- primitives
def test_bitonic_sort_sorts():
    rng = np.random.default_rng(1)
    n = 128
    r = jnp.asarray(rng.integers(0, 20, n), jnp.int32)
    c = jnp.asarray(rng.integers(0, 20, n), jnp.int32)
    s = jnp.zeros((n,), jnp.int32)
    v = jnp.asarray(rng.normal(size=n), jnp.float32)
    sr_, sc_, _, sv_ = common.bitonic_sort((r, c, s, v))
    keys = np.asarray(sr_).astype(np.int64) * 100 + np.asarray(sc_)
    assert (np.diff(keys) >= 0).all()
    # multiset of values preserved
    np.testing.assert_allclose(np.sort(np.asarray(sv_)), np.sort(np.asarray(v)))


def test_run_combine_is_exact_inclusive_fold():
    r = jnp.asarray([0, 0, 0, 1, 1, 2, 3, 3], jnp.int32)
    c = jnp.zeros((8,), jnp.int32)
    v = jnp.asarray([1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0])
    vals, is_end = common.run_combine(r, c, v, lambda x, y: x + y)
    np.testing.assert_allclose(np.asarray(vals)[np.asarray(is_end)], [6.0, 9.0, 6.0, 15.0])


# ------------------------------------------------------- interpret default
def test_interpret_default_follows_the_backend(monkeypatch):
    """Kernels are interpreted only off the TPU; an explicit choice wins."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert common.resolve_interpret(None) is False
    assert common.resolve_interpret(True) is True
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert common.resolve_interpret(None) is True
    assert common.resolve_interpret(False) is False
