"""The prefilled state the window starts from: valid layers, routed like the
program routes, a function of the seed, equal to what the reference starts
from, and refused when a layer would stand over its cut."""
import jax
import numpy as np
import pytest

import prefill
import reference
import run
from repro import d4m
from repro.serve.router import instance_of_numpy

RMAT = {"scale": 20, "a": 0.57, "b": 0.19, "c": 0.19}
FILL = {"records_per_instance": [100, 1500, 6000]}
LIMITS = [256, 2048, 16384]


def _filled(engine, k, seed):
    sess = d4m.D4MStream(d4m.StreamConfig(
        cuts=(256, 2048), top_capacity=16384, batch_size=256,
        instances_per_device=k, engine=engine))
    parts, counts = prefill.fill(sess, FILL, RMAT, seed, LIMITS)
    return sess, parts, counts


@pytest.mark.parametrize("engine,k", [("single", 1), ("packed", 4), ("pallas", 4)])
def test_layers_are_sorted_unique_and_routed(engine, k):
    sess, parts, counts = _filled(engine, k, 2**33 + 5)
    host = jax.device_get(sess.flat_state())
    for layer, want, records in zip(host.layers, counts, FILL["records_per_instance"]):
        nnz = np.atleast_1d(np.asarray(layer.nnz))
        np.testing.assert_array_equal(nnz, want)
        assert records * k * 0.9 < nnz.sum() <= records * k
        rows = np.asarray(layer.rows).reshape(k, -1)
        cols = np.asarray(layer.cols).reshape(k, -1)
        vals = np.asarray(layer.vals).reshape(k, -1)
        for j in range(k):
            n = nnz[j]
            key = (rows[j, :n].astype(np.int64) << 32) | cols[j, :n]
            assert np.all(np.diff(key) > 0)
            assert np.all(rows[j, n:] == prefill.PAD) and np.all(vals[j, n:] == 0)
            assert np.all(vals[j, :n] > 0)
            assert np.all(instance_of_numpy(rows[j, :n], cols[j, :n], k) == j)
    # the reference starts from exactly what the state holds
    got, _ = run.read_state(sess)
    assert reference.compare_state(reference.state_dok(got), reference.state_dok(parts)) == \
        {"keys_wrong": 0, "value_rel_err": 0.0}
    # and the program goes on from it
    before = sess.nnz()
    r = np.arange(256, dtype=np.int32)
    sess.ingest(r, r, np.ones(256, np.float32))
    assert sess.nnz() == before + 256 and not sess.overflowed()


def test_fill_is_a_function_of_the_seed():
    a = jax.device_get(_filled("packed", 4, 77)[0].state)
    b = jax.device_get(_filled("packed", 4, 77)[0].state)
    c = jax.device_get(_filled("packed", 4, 78)[0].state)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a.layers[2].rows, c.layers[2].rows)


def test_values_count_the_records_of_a_key():
    got = prefill.build_layer(3, 2, 50_000, 1, RMAT, prefill.jnp.asarray(np.arange(1 << 20, dtype=np.int32)))
    w = prefill.weight_of(got.rows, got.cols)
    assert np.all((w > 0) & (w <= 1))
    ratio = got.vals / w
    np.testing.assert_allclose(ratio, np.round(ratio), rtol=1e-6)
    assert np.round(ratio).sum() == 50_000 and ratio.max() > 1  # R-MAT repeats hot keys


def test_a_layer_over_its_limit_is_refused():
    sess = d4m.D4MStream(d4m.StreamConfig(cuts=(256, 2048), top_capacity=16384,
                                          batch_size=256, instances_per_device=4, engine="packed"))
    with pytest.raises(RuntimeError, match="layer 2"):
        prefill.fill(sess, FILL, RMAT, 5, [256, 1000, 16384])
