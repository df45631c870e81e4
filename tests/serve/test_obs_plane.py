"""Acceptance suite for the runtime observability plane (repro.obs).

Three contracts:

* **scrape bit-exactness** — a METRICS request over a live D4MF socket
  returns bucket arrays and integer percentile summaries identical to
  what the in-process registry reports for the same quiescent state;
* **conservation across the stack** — histograms ride TelemetrySnapshot
  and its ``merge()`` without losing a single event, and round-trip
  ``to_json`` bit-exactly;
* **disabled means absent** — with metrics off, no instrumentation site
  touches a registry (poisoned-class proof), the server carries no
  histograms, and a METRICS request answers with a typed error instead
  of a dead socket.
"""
import json
import time

import numpy as np
import pytest

from repro import d4m, serve
from repro.core.telemetry import TelemetrySnapshot
from repro.obs import LatencyHistogram, MetricsRegistry, TraceRing, Tracer, hist as obs_hist
from repro.serve import wire
from repro.serve.query import QUERY_OPS

BATCH = 32


def _records(seed, n, space=48):
    rng = np.random.default_rng(seed)
    return (
        rng.integers(0, space, n).astype(np.int32),
        rng.integers(0, space, n).astype(np.int32),
        np.ones(n, np.float32),
    )


def _session(k=1):
    return d4m.D4MStream(d4m.StreamConfig(
        cuts=(8, 32), top_capacity=4096, batch_size=BATCH,
        instances_per_device=k, snapshot_cap=8192,
    ))


def _serve_config(**kw):
    kw.setdefault("max_latency_ms", 1e9)
    kw.setdefault("publish_every", 1)
    kw.setdefault("drain_timeout_s", 600.0)
    return d4m.ServeConfig(**kw)


# ---------------------------------------------------------------------------
# wire: METRICS op round-trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("encoding", ["binary", "text"])
def test_metrics_request_round_trips(encoding):
    frame = wire.encode_metrics_request(9, {"format": "json"}, encoding)
    msgs, rest, malformed = wire.decode_messages(frame, encoding)
    assert rest == b"" and malformed == 0
    ((kind, req),) = msgs
    assert kind == "query"
    assert req.op == "metrics" and req.id == 9
    assert req.args == {"format": "json"}


def test_metrics_frame_is_op_04():
    frame = wire.encode_metrics_request(1, None, "binary")
    magic, version, op, _flags, _length = wire._V1_HEADER.unpack_from(frame)
    assert version == wire.PROTOCOL_VERSION
    assert op == wire.OP_METRICS == 0x04


def test_insert_only_decoder_rejects_metrics_frames():
    frame = wire.encode_metrics_request(1, None, "binary")
    with pytest.raises(Exception):
        wire.decode_binary(frame)


# ---------------------------------------------------------------------------
# live scrape: socket percentiles == in-process registry, bit for bit
# ---------------------------------------------------------------------------

def test_metrics_scrape_matches_registry_bit_exact():
    n = 8 * BATCH
    r, c, v = _records(seed=5, n=n)
    sess = _session()
    src = serve.TCPSource(port=0, encoding="binary", linger=False)
    server = serve.D4MServer(
        sess, src, _serve_config(metrics=True)
    ).start()
    assert server.metrics is not None

    with serve.QueryClient("127.0.0.1", src.port) as qc:
        for lo in range(0, n, BATCH):
            qc.insert(r[lo:lo + BATCH], c[lo:lo + BATCH], v[lo:lo + BATCH])
        # wait until the feed loop went quiescent over the whole stream
        deadline = time.monotonic() + 60
        while True:
            rep = qc.request("stats")
            assert rep.ok
            if rep.scalars["records"] == n:
                break
            assert time.monotonic() < deadline
            time.sleep(0.01)

        # compare only histograms the scrape itself cannot perturb: the
        # ingest-side stages are quiescent once all records published.
        # The feed thread swaps the covering view in BEFORE recording its
        # publish/view-build spans — wait for those last records to land.
        quiet = ("serve.update_dispatch_ns", "serve.publish_ns",
                 "router.flush_ns", "session.view_build_ns")
        prev, deadline = None, time.monotonic() + 30
        while time.monotonic() < deadline:
            cur = {nm: server.metrics.dump()["histograms"][nm]
                   for nm in quiet}
            if cur == prev:
                break
            prev = cur
            time.sleep(0.05)

        rep = qc.metrics()
        assert rep.ok
        local = server.metrics.dump()
        for name in quiet:
            st = local["histograms"][name]
            assert obs_hist.state_count(st) > 0, f"{name} never recorded"
            np.testing.assert_array_equal(
                rep.arrays[f"hist.{name}.counts"],
                np.asarray(st["counts"], np.int64),
            )
            assert rep.scalars["hist_max_ns"][name] == st["max_ns"]
            assert (rep.scalars["summaries"][name]
                    == obs_hist.summarize_state(st))

        # every dispatch fed one batch: count conservation down the stack
        dispatch = local["histograms"]["serve.update_dispatch_ns"]
        assert obs_hist.state_count(dispatch) == n // BATCH

        # wire decode + query latency histograms exist and grow
        assert rep.scalars["counters"] == local["counters"]
        assert any(k.startswith("hist.query.") for k in rep.arrays)
        assert obs_hist.state_count(
            server.metrics.dump()["histograms"]["wire.decode_ns"]
        ) > 0

        # prometheus form over the same socket
        prom = qc.metrics(format="prometheus")
        assert prom.ok
        assert "# TYPE repro_serve_update_dispatch_ns histogram" \
            in prom.scalars["text"]

        # unknown format: typed error, live socket
        bad = qc.metrics(format="xml")
        assert bad.ok is False and "unknown metrics format" in bad.error
        after = qc.request("stats")
        assert after.ok

    assert server.join(timeout=600)
    report = server.report()
    assert report.telemetry["records_fed"] == n

    # trace ring saw both stages, with batch ordinals and live counts
    stages = {e["stage"] for e in server.trace.events()}
    assert {"update", "publish"} <= stages
    upd = [e for e in server.trace.events() if e["stage"] == "update"]
    assert [e["batch"] for e in upd] == list(range(n // BATCH))
    assert all(e["live"] > 0 for e in upd)


def test_stats_reply_carries_staleness_and_query_latency():
    n = 4 * BATCH
    r, c, v = _records(seed=6, n=n)
    sess = _session()
    src = serve.TCPSource(port=0, encoding="binary", linger=False)
    server = serve.D4MServer(
        sess, src, _serve_config(metrics=True)
    ).start()
    with serve.QueryClient("127.0.0.1", src.port) as qc:
        for lo in range(0, n, BATCH):
            qc.insert(r[lo:lo + BATCH], c[lo:lo + BATCH], v[lo:lo + BATCH])
        deadline = time.monotonic() + 60
        while True:
            rep = qc.request("stats")
            assert rep.ok
            if rep.scalars["records"] == n:
                break
            assert time.monotonic() < deadline
            time.sleep(0.01)
        rep = qc.request("stats")
        assert rep.ok
        assert rep.scalars["view_staleness_records"] == 0
        lat = rep.scalars["query_latency"]
        assert "stats" in lat  # the polling stats calls themselves
        s = lat["stats"]
        assert set(s) == {"count", "p50_ns", "p90_ns", "p99_ns", "max_ns"}
        assert s["count"] >= 2
        assert set(lat) <= set(QUERY_OPS)
    assert server.join(timeout=600)


# ---------------------------------------------------------------------------
# spans: every host stage of the ingest path, joined by batch ordinal
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 2])
def test_ingest_spans_join_by_batch(k):
    n_batches = 300
    n = n_batches * BATCH
    r, c, v = _records(seed=12, n=n)
    sess = _session(k)
    src = serve.TCPSource(port=0, encoding="binary", linger=False)
    server = serve.D4MServer(
        sess, src, _serve_config(metrics=True, publish_every=None)
    ).start()
    wire.send_triples("127.0.0.1", src.port, r, c, v, encoding="binary",
                      chunk_records=1000)
    assert server.join(timeout=600)
    assert server.records_fed == n

    events = server.trace.events()
    assert server.trace.total == len(events)  # nothing evicted
    by = {}
    for e in events:
        by.setdefault(e["stage"], []).append(e)
    assert {"wire.decode", "router.route", "serve.wait", "update",
            "serve.h2d"} <= set(by)
    assert sum(e["records"] for e in by["wire.decode"]) == n
    slots = BATCH if k == 1 else k * BATCH
    assert all(e["slots"] == slots for e in by["router.route"])
    assert sum(e["live"] for e in by["router.route"]) == n
    assert sum(e["live"] for e in by["update"]) == n

    def one(stage):
        spans = {e["batch"]: e for e in by[stage]}
        assert len(spans) == len(by[stage])
        return spans

    route, upd, h2d = one("router.route"), one("update"), one("serve.h2d")
    wait = one("serve.wait")  # and one more, ended by the drain
    assert sorted(route) == sorted(upd) == sorted(h2d) == list(range(n_batches))
    assert sorted(wait) == list(range(n_batches + 1))
    for b in range(n_batches):
        assert route[b]["t1_ns"] <= upd[b]["t0_ns"]
        assert wait[b]["t1_ns"] <= upd[b]["t0_ns"]
        assert upd[b]["t0_ns"] <= h2d[b]["t0_ns"] <= h2d[b]["t1_ns"] <= upd[b]["t1_ns"]
        assert upd[b]["live"] == route[b]["live"]


# ---------------------------------------------------------------------------
# telemetry: histograms ride the snapshot and merge conservatively
# ---------------------------------------------------------------------------

def _registry_with(values):
    r = MetricsRegistry()
    h = r.histogram("serve.update_dispatch_ns")
    for v in values:
        h.record(v)
    return r


def test_telemetry_snapshot_histograms_merge_and_round_trip():
    snaps = []
    counts = [100, 250, 37]
    for i, n in enumerate(counts):
        reg = _registry_with(range(i, i + n))
        snaps.append(TelemetrySnapshot(
            records_fed=n, histograms=reg.dump()["histograms"]
        ))
    merged = TelemetrySnapshot.merge(snaps)
    st = merged.histograms["serve.update_dispatch_ns"]
    assert obs_hist.state_count(st) == sum(counts)
    assert st["max_ns"] == max(i + n - 1 for i, n in enumerate(counts))

    # wire form: to_json -> json text -> back, bit-exact
    back = json.loads(json.dumps(merged.to_json()))
    assert back["histograms"] == merged.histograms
    assert (obs_hist.summarize_state(
        back["histograms"]["serve.update_dispatch_ns"])
        == obs_hist.summarize_state(st))


def test_server_telemetry_exposes_histograms_when_enabled():
    n = 2 * BATCH
    r, c, v = _records(seed=8, n=n)
    sess = _session()
    src = serve.ArraySource(r, c, v, chunk_records=BATCH)
    server = serve.D4MServer(sess, src, _serve_config(metrics=True)).start()
    assert server.join(timeout=600)
    tel = server.telemetry()
    assert tel.histograms is not None
    assert obs_hist.state_count(
        tel.histograms["serve.update_dispatch_ns"]) == n // BATCH
    # the wire form a fleet worker sends is the same dump
    dump = server.metrics_dump()
    assert dump["histograms"].keys() == tel.histograms.keys()


# ---------------------------------------------------------------------------
# disabled path: no site may touch a registry at all
# ---------------------------------------------------------------------------

def _poison(monkeypatch):
    def boom(*a, **kw):
        raise AssertionError("instrumentation touched a registry while off")

    for name in ("counter", "gauge", "histogram", "dump", "summaries",
                 "to_prometheus"):
        monkeypatch.setattr(MetricsRegistry, name, boom)
    # nor may it resolve a span site, append a span or record a duration
    monkeypatch.setattr(Tracer, "site", boom)
    monkeypatch.setattr(TraceRing, "append", boom)
    monkeypatch.setattr(LatencyHistogram, "record", boom)


def test_disabled_path_never_touches_registry(monkeypatch):
    monkeypatch.delenv("REPRO_OBS", raising=False)
    _poison(monkeypatch)
    n = 2 * BATCH
    r, c, v = _records(seed=9, n=n)
    sess = _session()
    src = serve.ArraySource(r, c, v, chunk_records=BATCH)
    # config None + env unset resolves to off: a full serve must complete
    # without a single registry method call (they all raise)
    server = serve.D4MServer(sess, src, _serve_config()).start()
    assert server.join(timeout=600)
    assert server.metrics is None
    assert server.metrics_dump() is None
    assert server.trace is None
    assert server.telemetry().histograms is None
    assert server.report().telemetry["records_fed"] == n


def test_explicit_false_wins_over_env(monkeypatch):
    monkeypatch.setenv("REPRO_OBS", "1")
    _poison(monkeypatch)
    n = 2 * BATCH
    r, c, v = _records(seed=10, n=n)
    sess = _session()
    src = serve.ArraySource(r, c, v, chunk_records=BATCH)
    server = serve.D4MServer(
        sess, src, _serve_config(metrics=False)
    ).start()
    assert server.join(timeout=600)
    assert server.metrics is None


def test_metrics_query_while_disabled_is_typed_error():
    n = 2 * BATCH
    r, c, v = _records(seed=11, n=n)
    sess = _session()
    src = serve.TCPSource(port=0, encoding="binary", linger=False)
    server = serve.D4MServer(
        sess, src, _serve_config(metrics=False)
    ).start()
    with serve.QueryClient("127.0.0.1", src.port) as qc:
        qc.insert(r[:BATCH], c[:BATCH], v[:BATCH])
        rep = qc.metrics()
        assert rep.ok is False
        assert "metrics disabled" in rep.error
        # socket survives: a normal query still answers
        assert qc.request("stats").ok
    assert server.join(timeout=600)
