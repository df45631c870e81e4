"""Parity suite for the unified session facade.

The load-bearing property licensing the redesign: a D4MStream must be
*bit-identical* to the legacy entry points it replaces — same snapshot
triples, same telemetry — on every engine (single lax.cond at K=1,
vmap-packed at K>1, shard_map mesh at D>1), plus facade plumbing
(ingest routing, query namespace, checkpoint/restore, stream scan).
"""
import dataclasses
import os
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import d4m
from repro.core import analytics, assoc, hierarchical, multistream

SPACE = 64


def _stream(seed, steps, batch, space=SPACE):
    rng = np.random.default_rng(seed)
    r = jnp.asarray(rng.integers(0, space, (steps, batch)), jnp.int32)
    c = jnp.asarray(rng.integers(0, space, (steps, batch)), jnp.int32)
    v = jnp.ones((steps, batch), jnp.float32)
    return r, c, v


def _assert_bit_identical(got: assoc.Assoc, want: assoc.Assoc):
    np.testing.assert_array_equal(np.asarray(got.rows), np.asarray(want.rows))
    np.testing.assert_array_equal(np.asarray(got.cols), np.asarray(want.cols))
    np.testing.assert_array_equal(np.asarray(got.vals), np.asarray(want.vals))
    assert int(got.nnz) == int(want.nnz)
    assert bool(got.overflow) == bool(want.overflow)


# ---------------------------------------------------------------------------
# K=1, D=1: session == legacy hierarchical path, bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cuts", [(), (32,), (16, 128)])
def test_parity_single_vs_legacy_hierarchical(cuts):
    steps, batch = 10, 32
    r, c, v = _stream(0, steps, batch)
    sess = d4m.D4MStream(d4m.StreamConfig(
        cuts=cuts, top_capacity=1024, batch_size=batch
    ))
    assert sess.kind == "single"
    h = hierarchical.init(cuts, top_capacity=1024, batch_size=batch)
    for t in range(steps):
        sess.update(r[t], c[t], v[t])
        h = hierarchical.update_triples(h, r[t], c[t], v[t], cuts)
    cap = 2048
    _assert_bit_identical(sess.snapshot(cap=cap), hierarchical.snapshot(h, cap=cap))
    assert sess.nnz() == int(hierarchical.nnz_total(h))
    np.testing.assert_array_equal(
        np.asarray(sess.telemetry()["cascades"]), np.asarray(h.cascades)
    )


# ---------------------------------------------------------------------------
# K=8, D=1: session ingest == legacy packed path on the same routed stream
# ---------------------------------------------------------------------------

def test_parity_packed_vs_legacy_multistream():
    k, steps, batch = 8, 10, 64
    cuts = (16, 64)
    r, c, v = _stream(1, steps, batch)
    sess = d4m.D4MStream(d4m.StreamConfig(
        cuts=cuts, top_capacity=1024, batch_size=batch, instances_per_device=k
    ))
    assert sess.kind == "packed" and sess.n_instances == k
    hp = multistream.init_packed(k, cuts, top_capacity=1024, batch_size=batch)
    for t in range(steps):
        dropped = sess.ingest(r[t], c[t], v[t])
        assert int(dropped) == 0
        br, bc, bv, d2 = multistream.route_to_instances(r[t], c[t], v[t], k, batch)
        assert int(d2) == 0
        hp = multistream.packed_update(hp, br, bc, bv, cuts)
    cap = 2048
    # per-instance snapshots bit-identical...
    got_per = sess.snapshot(cap=cap, per_instance=True)
    want_per = multistream.snapshot_packed(hp, cap=cap)
    for inst in range(k):
        _assert_bit_identical(
            jax.tree.map(lambda x: x[inst], got_per),
            jax.tree.map(lambda x: x[inst], want_per),
        )
    # ...and so is the merged global array
    _assert_bit_identical(
        sess.snapshot(cap=cap), multistream.merge_snapshots(want_per, cap=cap)
    )
    np.testing.assert_array_equal(
        np.asarray(sess.telemetry()["cascades_per_instance"]),
        np.asarray(hp.cascades),
    )


# ---------------------------------------------------------------------------
# D=4: mesh engine parity (subprocess: forces 4 host devices before jax)
# ---------------------------------------------------------------------------

def test_parity_mesh_d4_subprocess():
    script = os.path.join(os.path.dirname(__file__), "_mesh_parity_main.py")
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, script], env=env, capture_output=True, text=True,
        timeout=600,
    )
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PARITY_OK" in out.stdout


# ---------------------------------------------------------------------------
# facade plumbing
# ---------------------------------------------------------------------------

def test_ingest_stream_scan_matches_update_loop():
    cuts = (16,)
    steps, batch = 8, 32
    r, c, v = _stream(2, steps, batch)
    cfg = d4m.StreamConfig(cuts=cuts, top_capacity=1024, batch_size=batch)
    scan_sess = d4m.D4MStream(cfg)
    trace = scan_sess.ingest_stream(r, c, v)
    assert trace.shape == (steps,)
    loop_sess = d4m.D4MStream(cfg)
    for t in range(steps):
        loop_sess.update(r[t], c[t], v[t])
    _assert_bit_identical(scan_sess.snapshot(), loop_sess.snapshot())
    assert int(trace[-1]) == scan_sess.nnz()


def test_legacy_ingest_and_snapshot_instances_path():
    """Satellite: streaming.ingest_and_snapshot must now support packed K."""
    k, steps, batch = 4, 6, 32
    cuts = (16,)
    r, c, v = _stream(3, steps, batch)
    routed = [
        multistream.route_to_instances(r[t], c[t], v[t], k, batch)
        for t in range(steps)
    ]
    R = jnp.stack([x[0] for x in routed])
    C = jnp.stack([x[1] for x in routed])
    V = jnp.stack([x[2] for x in routed])
    h0 = multistream.init_packed(k, cuts, top_capacity=1024, batch_size=batch)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        from repro.core import streaming

        h2, snap, trace = streaming.ingest_and_snapshot(
            h0, R, C, V, cuts, cap=2048, instances=k
        )
    assert trace.shape == (steps, k)
    ref = np.zeros((SPACE, SPACE), np.float32)
    np.add.at(
        ref,
        (np.asarray(r).ravel(), np.asarray(c).ravel()),
        np.asarray(v).ravel(),
    )
    np.testing.assert_allclose(
        np.asarray(assoc.to_dense(snap, SPACE, SPACE)), ref
    )


def test_legacy_streaming_shims_warn_and_match():
    """make_update_fn / ingest_stream must stay bit-identical through the
    deprecation shim (and actually warn)."""
    cuts = (16,)
    steps, batch = 6, 32
    r, c, v = _stream(4, steps, batch)
    with pytest.warns(DeprecationWarning):
        from repro.core import streaming

        step = streaming.make_update_fn(cuts, donate=False)
    h = hierarchical.init(cuts, top_capacity=1024, batch_size=batch)
    sess = d4m.D4MStream(d4m.StreamConfig(
        cuts=cuts, top_capacity=1024, batch_size=batch
    ))
    for t in range(steps):
        h = step(h, r[t], c[t], v[t])
        sess.update(r[t], c[t], v[t])
    _assert_bit_identical(
        sess.snapshot(cap=1024), hierarchical.snapshot(h, cap=1024)
    )


def test_query_namespace_matches_direct_analytics():
    steps, batch = 6, 32
    r, c, v = _stream(5, steps, batch)
    sess = d4m.D4MStream(d4m.StreamConfig(
        cuts=(16,), top_capacity=1024, batch_size=batch, max_fanout=16
    ))
    for t in range(steps):
        sess.update(r[t], c[t], v[t])
    snap = sess.snapshot()
    cap = sess.plan.snapshot_cap
    out_deg, in_deg = sess.query.degrees()
    want_out, want_in = analytics.degrees(snap, cap=cap)
    _assert_bit_identical(out_deg, want_out)
    _assert_bit_identical(in_deg, want_in)
    ids, counts = sess.query.top_k(3)
    wids, wcounts = analytics.top_k_vertices(want_out, 3)
    np.testing.assert_array_equal(np.asarray(ids), np.asarray(wids))
    np.testing.assert_array_equal(np.asarray(counts), np.asarray(wcounts))
    u, w = int(np.asarray(snap.rows)[0]), int(np.asarray(snap.rows)[1])
    assert float(sess.query.jaccard(u, w)) == float(
        analytics.jaccard(snap, u, w, cap=cap)
    )
    _assert_bit_identical(
        sess.query.row(u), assoc.extract_row(snap, u, cap=cap)
    )
    assert float(sess.query.get(u, int(np.asarray(snap.cols)[0]))) == float(
        assoc.get(snap, u, int(np.asarray(snap.cols)[0]))
    )


def test_checkpoint_restore_roundtrip(tmp_path):
    cfg = d4m.StreamConfig(cuts=(16,), top_capacity=512, batch_size=32)
    sess = d4m.D4MStream(cfg, checkpoint_dir=str(tmp_path))
    r, c, v = _stream(6, 4, 32)
    for t in range(2):
        sess.update(r[t], c[t], v[t])
    saved = sess.snapshot(cap=512)
    sess.checkpoint(2, extra={"cursor": 2})
    sess.wait_checkpoint()
    for t in range(2, 4):
        sess.update(r[t], c[t], v[t])
    # the stream genuinely moved past the checkpoint before the restore
    assert not np.array_equal(
        np.asarray(sess.snapshot(cap=512).vals), np.asarray(saved.vals)
    )
    extra = sess.restore()
    assert extra["cursor"] == 2 and extra["step"] == 2
    _assert_bit_identical(sess.snapshot(cap=512), saved)


def test_update_rejects_after_reset_shape_change():
    """Packed sessions validate the instance-major batch shape."""
    sess = d4m.D4MStream(d4m.StreamConfig(
        cuts=(16,), top_capacity=512, batch_size=32, instances_per_device=2
    ))
    bad = jnp.zeros((3, 32), jnp.int32)  # 3 != K=2
    with pytest.raises(Exception):
        jax.block_until_ready(
            sess.update(bad, bad, jnp.ones((3, 32))).state
        )


def test_triangles_correct_under_nondefault_semiring():
    """Triangle counting is a count: it must not inherit the session
    semiring's identities (max.plus sr.one = 0.0 would zero every product)."""
    r = jnp.asarray([0, 1, 2], jnp.int32)
    c = jnp.asarray([1, 2, 0], jnp.int32)  # directed 3-cycle = one triangle
    v = jnp.ones((3,), jnp.float32)
    for srn in ("plus.times", "max.plus", "max.min"):
        sess = d4m.D4MStream(d4m.StreamConfig(
            cuts=(16,), top_capacity=64, batch_size=8, semiring=srn,
            max_fanout=8,
        ))
        sess.update(
            jnp.pad(r, (0, 5), constant_values=assoc.PAD),
            jnp.pad(c, (0, 5), constant_values=assoc.PAD),
            jnp.pad(v, (0, 5)),
        )
        assert float(sess.query.triangles()) == 1.0, srn


def test_snapshot_truncation_warns():
    """A snapshot cap smaller than the live key set must warn, not silently
    drop entries (the state itself did not overflow)."""
    sess = d4m.D4MStream(d4m.StreamConfig(
        cuts=(16,), top_capacity=64, batch_size=32
    ))
    ks = jnp.arange(32, dtype=jnp.int32)
    sess.update(ks, ks, jnp.ones((32,)))
    assert not sess.overflowed()
    with pytest.warns(RuntimeWarning, match="truncated"):
        snap = sess.snapshot(cap=8)
    assert bool(snap.overflow)


def test_ingest_stream_rejected_on_mesh_kind():
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("data",))
    sess = d4m.D4MStream(
        d4m.StreamConfig(cuts=(16,), top_capacity=256, batch_size=16),
        mesh=mesh,
    )
    assert sess.kind == "mesh"
    z = jnp.zeros((2, 1, 16), jnp.int32)
    with pytest.raises(NotImplementedError):
        sess.ingest_stream(z, z, jnp.ones((2, 1, 16)))


def test_overflow_surfaces_in_telemetry():
    sess = d4m.D4MStream(d4m.StreamConfig(
        cuts=(), top_capacity=8, batch_size=32
    ))
    ks = jnp.arange(32, dtype=jnp.int32)
    sess.update(ks, ks, jnp.ones((32,)))
    sess.update(ks + 100, ks, jnp.ones((32,)))
    assert sess.overflowed()
    assert sess.telemetry()["overflowed"]


# ---------------------------------------------------------------- TPU rules
def _on_tpu(monkeypatch):
    """Steer backend-dependent choices as a TPU host would see them (no
    device is touched: nothing here runs a step)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_auto_engine_on_tpu_is_the_compiled_kernel(monkeypatch):
    _on_tpu(monkeypatch)
    sess = d4m.D4MStream(
        d4m.StreamConfig(cuts=(8, 32), top_capacity=256, batch_size=16,
                         instances_per_device=4)
    )
    assert sess.kind == "pallas"


@pytest.mark.parametrize("engine", ["pallas", "auto"])
def test_pallas_over_vmem_budget_raises_on_tpu(monkeypatch, engine):
    from repro.kernels.hier_cascade import kernel as cascade_kernel

    _on_tpu(monkeypatch)
    # a top layer wider than any merge the kernel's VMEM can hold
    cfg = d4m.StreamConfig(
        cuts=(1024, 8192), top_capacity=1 << 23, batch_size=512,
        instances_per_device=4, engine=engine,
    )
    with pytest.raises(ValueError, match="MiB of VMEM") as err:
        d4m.D4MStream(cfg)
    assert f"{cascade_kernel.VMEM_BUDGET_BYTES / 2**20:.0f} MiB limit" in str(err.value)


def test_pallas_needs_32bit_values(monkeypatch):
    _on_tpu(monkeypatch)
    with pytest.raises(ValueError, match="32-bit"):
        d4m.D4MStream(d4m.StreamConfig(
            cuts=(8,), top_capacity=64, batch_size=8,
            instances_per_device=2, engine="pallas", dtype="bfloat16",
        ))


def test_pallas_state_stays_tiled_and_checkpoints_flat(tmp_path):
    """The pallas engine keeps its layers in the kernel's [K, w/128, 128]
    layout across steps; checkpoints hold the [K, w] form the packed engine
    uses, and a restore tiles it back."""
    cfg = d4m.StreamConfig(
        cuts=(8, 32), top_capacity=256, batch_size=16,
        instances_per_device=2, engine="pallas",
    )
    sess = d4m.D4MStream(cfg, checkpoint_dir=str(tmp_path))
    packed = d4m.D4MStream(dataclasses.replace(cfg, engine="packed"))
    rng = np.random.default_rng(3)
    for _ in range(6):
        r = jnp.asarray(rng.integers(0, 48, 16), jnp.int32)
        c = jnp.asarray(rng.integers(0, 48, 16), jnp.int32)
        v = jnp.ones((16,), jnp.float32)
        sess.ingest(r, c, v)
        packed.ingest(r, c, v)
    assert all(l.rows.ndim == 3 and l.rows.shape[2] == 128 for l in sess.state.layers)
    flat = sess.flat_state()
    for lf, lp in zip(flat.layers, packed.state.layers):
        n = lp.rows.shape[1]
        np.testing.assert_array_equal(np.asarray(lf.rows)[:, :n], np.asarray(lp.rows))
        np.testing.assert_array_equal(np.asarray(lf.vals)[:, :n], np.asarray(lp.vals))
    sess.checkpoint(6, extra={"cursor": 6})
    sess.wait_checkpoint()
    fresh = d4m.D4MStream(cfg, checkpoint_dir=str(tmp_path))
    assert fresh.restore()["cursor"] == 6
    for a, b in zip(jax.tree.leaves(fresh.state), jax.tree.leaves(sess.state)):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    A, B = fresh.snapshot(cap=512), packed.snapshot(cap=512)
    np.testing.assert_array_equal(np.asarray(A.rows), np.asarray(B.rows))
    np.testing.assert_array_equal(np.asarray(A.vals), np.asarray(B.vals))
