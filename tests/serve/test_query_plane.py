"""Acceptance suite for the online query plane.

The load-bearing property: every query op answered over a *published*
:class:`~repro.d4m.session.StreamView` — captured mid-stream, with forced
cascades and engaged backpressure, or after a worker crash and a
checkpoint-restore replay — must be bit-identical to draining the same
record prefix into a fresh session and querying its snapshot.  Plus the
wire layer underneath: the versioned op-coded protocol must decode legacy
v0 insert frames bit-identically and round-trip queries/replies bit-exact
in both encodings.
"""
import os
import subprocess
import sys
import threading
import time
import warnings
from pathlib import Path

import numpy as np
import pytest

from repro import d4m, serve
from repro.core import analytics
from repro.core.assoc import PAD
from repro.core.semiring import FIRST, MAX_TIMES, PLUS_TIMES
from repro.serve import wire

BATCH = 32
CUTS = (8, 32)  # tiny cuts so cascades fire constantly


def _records(seed, n, space=48):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, space, n).astype(np.int32),
        rng.integers(0, space, n).astype(np.int32),
        np.ones(n, np.float32),
    )


def _session(k, **kw):
    return d4m.D4MStream(d4m.StreamConfig(
        cuts=CUTS, top_capacity=4096, batch_size=BATCH,
        instances_per_device=k, snapshot_cap=8192,
    ), **kw)


def _slow_step(sess, delay_s=0.002):
    orig = sess._step

    def step(h, rows, cols, vals):
        time.sleep(delay_s)
        return orig(h, rows, cols, vals)

    sess._step = step
    return sess


def _assert_assoc_identical(got, want):
    np.testing.assert_array_equal(np.asarray(got.rows), np.asarray(want.rows))
    np.testing.assert_array_equal(np.asarray(got.cols), np.asarray(want.cols))
    np.testing.assert_array_equal(np.asarray(got.vals), np.asarray(want.vals))
    assert int(got.nnz) == int(want.nnz)


def _reference_view(r, c, v, k, n_prefix):
    """Drain the first ``n_prefix`` records into a fresh session offline
    and return its read view — what a published view must equal."""
    assert n_prefix % BATCH == 0
    ref = _session(k)
    for lo in range(0, n_prefix, BATCH):
        dropped = ref.ingest(
            r[lo:lo + BATCH], c[lo:lo + BATCH], v[lo:lo + BATCH]
        )
        assert int(dropped) == 0
    return ref, ref.view(publish=False)


def _assert_views_answer_identically(got, want):
    """Every query op, bit-identical across the two views (``records``
    metadata is deliberately NOT compared: a replay's view meters only the
    records fed since the restore)."""
    g_out, g_in = got.degrees()
    w_out, w_in = want.degrees()
    _assert_assoc_identical(g_out, w_out)
    _assert_assoc_identical(g_in, w_in)
    for by in ("out", "in"):
        gi, gv = got.top_k(5, by)
        wi, wv = want.top_k(5, by)
        np.testing.assert_array_equal(np.asarray(gi), np.asarray(wi))
        np.testing.assert_array_equal(np.asarray(gv), np.asarray(wv))
    nnz = int(want.snap.nnz)
    rpick = int(np.asarray(want.snap.rows)[0]) if nnz else 0
    cpick = int(np.asarray(want.snap.cols)[0]) if nnz else 0
    _assert_assoc_identical(got.row(rpick), want.row(rpick))
    np.testing.assert_array_equal(
        np.asarray(got.get(rpick, cpick)), np.asarray(want.get(rpick, cpick))
    )
    np.testing.assert_array_equal(
        np.asarray(got.triangles()), np.asarray(want.triangles())
    )
    assert int(got.nnz) == int(want.nnz)


def _live(a):
    """(keys, vals) of an Assoc's live entries as host arrays."""
    n = int(a.nnz)
    return np.asarray(a.rows)[:n], np.asarray(a.vals)[:n]


# ---------------------------------------------------------------------------
# wire: the versioned op-coded protocol
# ---------------------------------------------------------------------------

def test_v0_insert_frames_decode_bit_identical():
    """Legacy D4MB frames are the INSERT op at version 0: the default
    encoder still emits them byte-compatibly and both decoders parse them
    bit-identically, including across a torn frame boundary."""
    r, c, v = _records(seed=1, n=100)
    f1 = wire.encode_binary(r[:60], c[:60], v[:60])
    f2 = wire.encode_binary(r[60:], c[60:], v[60:])
    assert f1.startswith(wire.BINARY_MAGIC)

    (rr, cc, vv), leftover, bad = wire.decode_binary(f1 + f2)
    assert leftover == b"" and bad == 0
    np.testing.assert_array_equal(rr, r)
    np.testing.assert_array_equal(cc, c)
    np.testing.assert_array_equal(
        vv.view(np.uint32), v.view(np.uint32)  # bit-exact float32
    )
    assert rr.dtype == np.int32 and vv.dtype == np.float32

    # the op-coded decoder sees the same frames as typed insert messages
    msgs, leftover, bad = wire.decode_messages(f1 + f2, "binary")
    assert [k for k, _ in msgs] == ["insert", "insert"] and bad == 0
    np.testing.assert_array_equal(msgs[0][1][0], r[:60])

    # torn mid-frame: everything complete parses, the tail waits in
    # leftover and completes on the next call — nothing lost, nothing torn
    torn = f1 + f2[:7]
    msgs, leftover, bad = wire.decode_messages(torn, "binary")
    assert len(msgs) == 1 and leftover == f2[:7] and bad == 0
    msgs, leftover, bad = wire.decode_messages(leftover + f2[7:], "binary")
    assert len(msgs) == 1 and leftover == b""
    np.testing.assert_array_equal(msgs[0][1][0], r[60:])


def test_v1_insert_frames_interleave_with_v0():
    r, c, v = _records(seed=2, n=64)
    v1 = wire.encode_binary(r[:32], c[:32], v[:32], version=1)
    v0 = wire.encode_binary(r[32:], c[32:], v[32:], version=0)
    assert v1.startswith(wire.FRAME_MAGIC)

    # both shapes of the INSERT op interleave freely on one connection
    (rr, cc, vv), leftover, bad = wire.decode_binary(v1 + v0)
    assert leftover == b"" and bad == 0
    np.testing.assert_array_equal(rr, r)
    np.testing.assert_array_equal(vv.view(np.uint32), v.view(np.uint32))

    with pytest.raises(ValueError, match="version"):
        wire.encode_binary(r, c, v, version=2)


@pytest.mark.parametrize("encoding", ["binary", "text"])
def test_query_reply_round_trip_bit_exact(encoding):
    req = wire.QueryRequest(op="top_k", args={"k": 5, "by": "in"}, id=7)
    msgs, leftover, bad = wire.decode_messages(
        wire.encode_request(req, encoding), encoding
    )
    assert leftover == b"" and bad == 0
    assert msgs == [("query", req)]

    # awkward float32s: non-representable decimals, denormal-adjacent,
    # near-max, negative zero — all must survive both encodings bit-exact
    awkward = np.array([0.1, 1e-7, np.pi, 3.4e38, -0.0], np.float32)
    rep = wire.QueryReply(
        id=7, ok=True, view_seq=3, view_records=96, staleness=32,
        scalars={"triangles": 253.1666717529297, "engine": "packed",
                 "overflowed": False, "records": None},
        arrays={"ids": np.arange(5, dtype=np.int32), "vals": awkward},
    )
    msgs, leftover, bad = wire.decode_messages(
        wire.encode_reply(rep, encoding), encoding
    )
    assert leftover == b"" and bad == 0 and len(msgs) == 1
    kind, got = msgs[0]
    assert kind == "reply" and got.ok and got.id == 7
    assert (got.view_seq, got.view_records, got.staleness) == (3, 96, 32)
    assert got.scalars == rep.scalars
    assert got.arrays["ids"].dtype == np.int32
    assert got.arrays["vals"].dtype == np.float32
    np.testing.assert_array_equal(got.arrays["ids"], rep.arrays["ids"])
    np.testing.assert_array_equal(
        got.arrays["vals"].view(np.uint32), awkward.view(np.uint32)
    )

    # error replies carry the failure, never an exception on the wire
    err = wire.QueryReply(id=9, ok=False, error="unknown query op 'nope'")
    msgs, _, _ = wire.decode_messages(wire.encode_reply(err, encoding), encoding)
    assert msgs[0][1].ok is False and "nope" in msgs[0][1].error


@pytest.mark.parametrize("encoding", ["binary", "text"])
def test_mixed_plane_messages_decode_in_arrival_order(encoding):
    r, c, v = _records(seed=3, n=20)
    req = wire.QueryRequest(op="stats", id=1)
    rep = wire.QueryReply(id=1, ok=True, scalars={"nnz": 12})
    buf = (
        wire.encode(r[:10], c[:10], v[:10], encoding)
        + wire.encode_request(req, encoding)
        + wire.encode(r[10:], c[10:], v[10:], encoding)
        + wire.encode_reply(rep, encoding)
    )
    msgs, leftover, bad = wire.decode_messages(buf, encoding)
    assert leftover == b"" and bad == 0
    assert [k for k, _ in msgs] == ["insert", "query", "insert", "reply"]
    np.testing.assert_array_equal(msgs[0][1][0], r[:10])
    np.testing.assert_array_equal(msgs[2][1][0], r[10:])
    assert msgs[1][1] == req and msgs[3][1].scalars == {"nnz": 12}


def test_binary_salvage_then_desync_error():
    """Frames parsed before a bad header are salvaged (TCP coalescing must
    not lose them); the next call, seeing the bad header first, raises —
    a desynchronized binary stream cannot be resynchronized."""
    r, c, v = _records(seed=4, n=10)
    good = wire.encode_binary(r, c, v)
    junk = b"JUNKJUNKJUNKJUNKJUNK"
    msgs, leftover, bad = wire.decode_messages(good + junk, "binary")
    assert len(msgs) == 1 and leftover == junk
    with pytest.raises(ValueError, match="desynchronized"):
        wire.decode_messages(leftover, "binary")


def test_oversized_frames_rejected_both_directions():
    # a length field promising more than MAX_FRAME_RECORDS is a desync
    hdr = wire._HEADER.pack(wire.BINARY_MAGIC, wire.MAX_FRAME_RECORDS + 1)
    with pytest.raises(ValueError, match="desynchronized"):
        wire.decode_messages(hdr + b"\x00" * 16, "binary")
    # ... and so is a control frame beyond its op's bound
    qh = wire._V1_HEADER.pack(
        wire.FRAME_MAGIC, wire.PROTOCOL_VERSION, wire.OP_QUERY, 0,
        wire.MAX_CONTROL_BYTES + 1,
    )
    with pytest.raises(ValueError, match="desynchronized"):
        wire.decode_messages(qh, "binary")
    # the encoder refuses to emit what its decoder would reject
    with pytest.raises(ValueError, match="MAX_CONTROL_BYTES"):
        wire.encode_request(
            wire.QueryRequest(op="x", args={"pad": "y" * wire.MAX_CONTROL_BYTES})
        )


def test_malformed_control_payloads_skip_never_poison():
    """A framing-valid but semantically bad control payload is counted and
    skipped — the stream stays synchronized and later messages parse."""
    good = wire.encode_request(wire.QueryRequest(op="stats", id=2))
    msgs, leftover, bad = wire.decode_messages(
        wire._frame(wire.OP_QUERY, b"{not json") + good, "binary"
    )
    assert bad == 1 and leftover == b""
    assert [k for k, _ in msgs] == ["query"] and msgs[0][1].id == 2

    msgs, leftover, bad = wire.decode_messages(
        b"?{not json\n" + wire.encode_request(
            wire.QueryRequest(op="stats", id=3), "text"
        ), "text"
    )
    assert bad == 1 and [k for k, _ in msgs] == ["query"]


def test_insert_only_decoder_rejects_control_frames():
    """The v0-compat triple decoders cannot answer a query: a control
    frame on that path is a desync error (binary) or a malformed line
    (text), exactly like before the protocol existed."""
    with pytest.raises(ValueError, match="insert-only"):
        wire.decode_binary(
            wire.encode_request(wire.QueryRequest(op="stats"))
        )
    (rr, _, _), _, bad = wire.decode_text(
        b"1\t2\t3\n" + wire.encode_request(wire.QueryRequest(op="stats"), "text")
    )
    assert rr.shape[0] == 1 and bad == 1


# ---------------------------------------------------------------------------
# the incremental degree tracker
# ---------------------------------------------------------------------------

def test_degree_tracker_matches_host_reference_with_pad_slots():
    rng = np.random.default_rng(0)
    tracker = serve.DegreeTracker(PLUS_TIMES)
    assert tracker.supported
    out_ref, in_ref = {}, {}
    live_total = 0
    for _ in range(5):
        r = rng.integers(0, 20, (4, 16)).astype(np.int32)
        c = rng.integers(0, 20, (4, 16)).astype(np.int32)
        v = rng.integers(1, 5, (4, 16)).astype(np.float32)
        r[rng.random((4, 16)) < 0.3] = PAD  # routed batches carry dead slots
        tracker.feed(r, c, v)
        for i, j, w in zip(r.ravel(), c.ravel(), v.ravel()):
            if i == PAD:
                continue
            out_ref[int(i)] = out_ref.get(int(i), 0.0) + float(w)
            in_ref[int(j)] = in_ref.get(int(j), 0.0) + float(w)
            live_total += 1
    assert tracker.records == live_total
    out_ids, out_vals, in_ids, in_vals = tracker.arrays()
    assert out_ids.tolist() == sorted(out_ref)
    np.testing.assert_array_equal(
        out_vals, np.array([out_ref[i] for i in sorted(out_ref)], np.float32)
    )
    assert in_ids.tolist() == sorted(in_ref)
    np.testing.assert_array_equal(
        in_vals, np.array([in_ref[i] for i in sorted(in_ref)], np.float32)
    )


def test_degree_tracker_semiring_folds():
    # max-family semirings fold with np.maximum, order-independent outright
    tracker = serve.DegreeTracker(MAX_TIMES)
    assert tracker.supported
    tracker.feed(np.array([1, 1, 2]), np.array([5, 6, 5]),
                 np.array([2.0, 7.0, 3.0], np.float32))
    tracker.feed(np.array([1]), np.array([7]), np.array([4.0], np.float32))
    out_ids, out_vals, _, _ = tracker.arrays()
    assert out_ids.tolist() == [1, 2]
    np.testing.assert_array_equal(out_vals, np.array([7.0, 3.0], np.float32))
    # non-commutative adds have no host fold: views reduce on demand instead
    assert not serve.DegreeTracker(FIRST).supported


# ---------------------------------------------------------------------------
# StreamView / sess.query binding
# ---------------------------------------------------------------------------

def test_view_snapshot_isolation_across_later_ingest():
    n = 2 * BATCH
    r, c, v = _records(seed=31, n=n)
    sess = _session(1)
    sess.ingest(r[:BATCH], c[:BATCH], v[:BATCH])
    v1 = sess.view()
    assert v1.seq == 1 and sess.latest_view() is v1
    rows_then = np.array(np.asarray(v1.snap.rows), copy=True)
    vals_then = np.array(np.asarray(v1.snap.vals), copy=True)
    d_out_then, _ = v1.degrees()

    sess.ingest(r[BATCH:], c[BATCH:], v[BATCH:])
    v2 = sess.view()
    assert v2.seq == 2 and sess.latest_view() is v2

    # v1 is frozen: same buffers, same answers, cached degrees untouched
    np.testing.assert_array_equal(np.asarray(v1.snap.rows), rows_then)
    np.testing.assert_array_equal(np.asarray(v1.snap.vals), vals_then)
    assert v1.degrees()[0] is d_out_then
    assert int(v2.nnz) >= int(v1.nnz)

    s = v1.stats()
    assert s["seq"] == 1 and s["engine"] == "single"
    assert s["records"] is None  # library mode does not meter records
    assert s["nnz"] == int(v1.nnz) and s["overflowed"] is False


def test_query_namespace_binds_published_view_while_serving():
    n = 2 * BATCH
    r, c, v = _records(seed=32, n=n)
    sess = _session(1)
    sess.ingest(r[:BATCH], c[:BATCH], v[:BATCH])
    v1 = sess.view()
    sess.ingest(r[BATCH:], c[BATCH:], v[BATCH:])  # live state moves past v1

    sess._serving = True  # what D4MServer sets while the feed loop runs
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)  # must NOT warn
            got_out, _ = sess.query.degrees()
    finally:
        sess._serving = False
    # answered over the published view, not the live (mutating) state
    _assert_assoc_identical(got_out, v1.degrees()[0])

    # outside a serve the namespace reads the live state again
    live_out, _ = sess.query.degrees()
    want_out, _ = analytics.degrees(
        sess.snapshot(), cap=sess.plan.snapshot_cap, sr=sess.sr
    )
    _assert_assoc_identical(live_out, want_out)


def test_live_query_during_viewless_serve_is_deprecated():
    sess = _session(1)
    r, c, v = _records(seed=33, n=BATCH)
    sess.ingest(r, c, v)
    sess._serving = True
    try:
        with pytest.warns(DeprecationWarning, match="publish_every"):
            sess.query.get(int(r[0]), int(c[0]))
    finally:
        sess._serving = False


def test_serve_config_publish_knobs_validate_and_round_trip():
    with pytest.raises(ValueError, match="publish_every"):
        d4m.ServeConfig(publish_every=0).validate()
    with pytest.raises(ValueError, match="publish_cap"):
        d4m.ServeConfig(publish_every=2, publish_cap=0).validate()
    with pytest.raises(ValueError, match="publish_cap is set but"):
        d4m.ServeConfig(publish_cap=1024).validate()
    cfg = d4m.ServeConfig(publish_every=3, publish_cap=4096,
                          track_degrees=False)
    assert d4m.ServeConfig.from_dict(cfg.to_dict()) == cfg


# ---------------------------------------------------------------------------
# THE acceptance test: views published mid-stream — under forced cascades
# and engaged backpressure — answer bit-identically to an offline replay
# of exactly the records they were published over
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 8])
def test_served_views_bit_identical_to_offline_replay(k):
    n = 40 * BATCH
    r, c, v = _records(seed=k, n=n)
    sess = _slow_step(_session(k))

    src = serve.TCPSource(port=0).start()
    sender = threading.Thread(
        target=serve.send_triples,
        args=("127.0.0.1", src.port, r, c, v),
        kwargs={"chunk_records": 256},
    )
    captured = {}
    stop = threading.Event()

    def poll():  # a reader thread watching publications race the feed loop
        while not stop.is_set():
            vw = sess.latest_view()
            if vw is not None and vw.seq not in captured:
                captured[vw.seq] = vw
            time.sleep(0.001)

    poller = threading.Thread(target=poll, daemon=True)
    poller.start()
    sender.start()
    report = sess.serve(
        src, max_latency_ms=1e9, queue_depth=1, publish_every=5
    )
    sender.join(timeout=30)
    stop.set()
    poller.join(timeout=10)

    assert report.drained and report.records_fed == n
    assert report.blocked_events >= 1, "backpressure never engaged"
    tel = report.telemetry["session"]
    cascades = np.asarray(
        tel["cascades"] if k == 1 else tel["cascades_per_instance"]
    )
    assert cascades.sum() > 0, "cascades never fired"
    # initial + 40/5 periodic + final drain view
    assert report.telemetry["views_published"] == 10
    assert report.telemetry["view_staleness_records"] == 0

    final = sess.latest_view()
    captured[final.seq] = final
    assert final.records == n and final.seq == 10

    seqs = sorted(captured)
    views = [captured[s] for s in seqs]
    recs = [vw.records for vw in views]
    assert recs == sorted(recs), "view records must be monotone in seq"
    assert all(rec % BATCH == 0 for rec in recs), \
        "views must publish on microbatch boundaries"
    mid = [vw for vw in views if 0 < vw.records < n]
    assert mid, "no view was captured mid-stream"

    # replay each captured prefix offline and interrogate both sides:
    # first mid-stream view, last mid-stream view, and the final one
    for vw in {id(x): x for x in (mid[0], mid[-1], final)}.values():
        _, want = _reference_view(r, c, v, k, vw.records)
        _assert_views_answer_identically(vw, want)


# ---------------------------------------------------------------------------
# one socket, both planes: inserts and queries interleaved over TCP
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("encoding", ["binary", "text"])
def test_socket_interleaves_inserts_and_queries(encoding):
    n = 12 * BATCH
    r, c, v = _records(seed=21, n=n)
    ref, ref_view = _reference_view(r, c, v, 1, n)
    want_out, want_in = ref_view.degrees()

    sess = _session(1)
    src = serve.TCPSource(port=0, encoding=encoding, linger=False)
    server = serve.D4MServer(
        sess, src,
        d4m.ServeConfig(max_latency_ms=1e9, publish_every=1,
                        drain_timeout_s=600.0),
    ).start()

    ok_queries = 0
    with serve.QueryClient("127.0.0.1", src.port, encoding=encoding) as qc:
        # the initial (pre-stream) view answers instead of erroring
        rep = qc.request("stats")
        assert rep.ok and rep.view_seq == 1 and rep.scalars["records"] == 0
        ok_queries += 1
        # an unknown op comes back as an error reply, not a dead socket
        bad = qc.request("frobnicate")
        assert bad.ok is False and "unknown query op" in bad.error

        for lo in range(0, n, BATCH):
            qc.insert(r[lo:lo + BATCH], c[lo:lo + BATCH], v[lo:lo + BATCH])
            if lo % (4 * BATCH) == 0:
                rep = qc.request("get", r=int(r[0]), c=int(c[0]))
                assert rep.ok and rep.view_records is not None
                assert rep.staleness >= 0
                ok_queries += 1

        # wait for a published view covering the whole stream (publication
        # races the socket reads; the contract is only that it arrives)
        deadline = time.monotonic() + 60
        while True:
            rep = qc.request("stats")
            assert rep.ok
            ok_queries += 1
            if rep.scalars["records"] == n:
                break
            assert time.monotonic() < deadline, \
                "no view covering the full stream was published"
            time.sleep(0.01)
        assert rep.staleness == 0

        # full-view degrees over the wire: bit-identical to the offline
        # reference, in both encodings
        rep = qc.request("degrees")
        assert rep.ok and rep.view_records == n
        ok_queries += 1
        ids, vals = _live(want_out)[0], _live(want_out)[1]
        np.testing.assert_array_equal(rep.arrays["out_ids"], ids)
        np.testing.assert_array_equal(
            rep.arrays["out_vals"].astype(np.float32).view(np.uint32),
            vals.astype(np.float32).view(np.uint32),
        )
        ids, vals = _live(want_in)
        np.testing.assert_array_equal(rep.arrays["in_ids"], ids)
        np.testing.assert_array_equal(rep.arrays["in_vals"], vals)

        rep = qc.request("top_k", k=5, by="in")
        assert rep.ok
        ok_queries += 1
        wi, wv = ref_view.top_k(5, "in")
        np.testing.assert_array_equal(rep.arrays["ids"], np.asarray(wi))
        np.testing.assert_array_equal(rep.arrays["vals"], np.asarray(wv))

    # closing the client ends the stream (linger=False): drain completes
    assert server.join(timeout=600)
    report = server.report()
    assert report.drained and report.records_fed == n
    assert report.malformed == 0
    assert src.queries_seen == ok_queries + 1  # + the unknown-op request
    tel = report.telemetry
    assert tel["queries_served"] == ok_queries
    assert tel["views_published"] >= 2  # initial + at least the final
    assert tel["view_seq"] == tel["views_published"]
    assert tel["view_staleness_records"] == 0
    _assert_views_answer_identically(sess.latest_view(), ref_view)


def test_arraysource_serve_publishes_views_with_telemetry():
    n = 8 * BATCH
    r, c, v = _records(seed=41, n=n)
    sess = _session(1)
    report = sess.serve(
        serve.ArraySource(r, c, v, chunk_records=BATCH),
        max_latency_ms=1e9, publish_every=2,
    )
    assert report.drained and report.records_fed == n
    tel = report.telemetry
    # initial + 8/2 periodic + final drain view
    assert tel["views_published"] == 6
    assert tel["view_seq"] == 6
    assert tel["view_staleness_records"] == 0
    assert tel["queries_served"] == 0  # no client ever asked

    final = sess.latest_view()
    assert final.records == n
    assert final._degree_cache, "the tracker must pre-seed the view"
    got_out, got_in = final.degrees()  # tracker-seeded, no reduction
    want_out, want_in = analytics.degrees(
        sess.snapshot(), cap=sess.plan.snapshot_cap, sr=sess.sr
    )
    _assert_assoc_identical(got_out, want_out)
    _assert_assoc_identical(got_in, want_in)

    # with tracking off, views still publish; degrees reduce on demand
    sess2 = _session(1)
    sess2.serve(
        serve.ArraySource(r, c, v, chunk_records=BATCH),
        max_latency_ms=1e9, publish_every=4, track_degrees=False,
    )
    final2 = sess2.latest_view()
    assert not final2._degree_cache
    _assert_assoc_identical(final2.degrees()[0], want_out)


# ---------------------------------------------------------------------------
# chaos: a worker crash mid-query, then restore -> replay -> re-query
# ---------------------------------------------------------------------------

def test_crash_mid_query_then_restore_replay_requery(tmp_path):
    """worker.crash_after_n_batches fires in a real subprocess while a
    query client is mid-flight: the client observes the crash (never a
    wrong answer), and a fresh session restored from the crashed server's
    checkpoints, after replaying the tail, answers every query op
    bit-identically to an uninterrupted run."""
    n = 16 * BATCH
    r, c, v = _records(seed=77, n=n)

    # the uninterrupted reference run
    ref = _session(1)
    ref_report = ref.serve(
        serve.ArraySource(r, c, v, chunk_records=BATCH),
        max_latency_ms=1e9, publish_every=1,
    )
    assert ref_report.drained and ref_report.records_fed == n
    want = ref.latest_view()

    src_root = Path(__file__).resolve().parents[2] / "src"
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(src_root)] + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    helper = Path(__file__).with_name("_query_crash_main.py")
    child = subprocess.Popen(
        [sys.executable, str(helper), str(tmp_path)],
        stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        env=env, text=True,
    )
    inserter = None
    try:
        port = None
        for line in child.stdout:
            if line.startswith("PORT "):
                port = int(line.split()[1])
                break
            assert not line.startswith("SURVIVED")
        assert port is not None, "child exited before listening"

        replies, errors = [], []

        def hammer():
            try:
                with serve.QueryClient("127.0.0.1", port, timeout_s=60.0) as qc:
                    while True:
                        replies.append(qc.request("stats"))
                        time.sleep(0.002)
            except (ConnectionError, OSError) as e:
                errors.append(e)

        hammerer = threading.Thread(target=hammer, daemon=True)
        hammerer.start()
        inserter = serve.QueryClient("127.0.0.1", port, timeout_s=60.0)
        try:
            for lo in range(0, n, BATCH):
                inserter.insert(
                    r[lo:lo + BATCH], c[lo:lo + BATCH], v[lo:lo + BATCH]
                )
                time.sleep(0.002)
        except (ConnectionError, OSError):
            pass  # the crash landed while we were still streaming

        assert child.wait(timeout=300) == 137, "the fault must hard-exit"
        hammerer.join(timeout=60)
        assert not hammerer.is_alive()
        assert errors, "the query client must observe the crash mid-flight"
        assert any(rep.ok for rep in replies), \
            "no query was answered before the crash"
    finally:
        if inserter is not None:
            inserter.close()
        child.kill()
        child.stdout.close()

    # restore the crashed server's checkpoint, replay the tail, re-query
    fresh = _session(1, checkpoint_dir=str(tmp_path))
    extra = fresh.restore()
    cursor = extra["cursor"]
    assert 0 < cursor < n and cursor % BATCH == 0
    replay = fresh.serve(
        serve.ArraySource(r[cursor:], c[cursor:], v[cursor:],
                          chunk_records=BATCH),
        max_latency_ms=1e9, publish_every=1,
    )
    assert replay.drained and replay.records_fed == n - cursor
    got = fresh.latest_view()
    assert got.records == n - cursor  # the replay meters only its own feed
    _assert_views_answer_identically(got, want)
    assert fresh.nnz() == ref.nnz()
