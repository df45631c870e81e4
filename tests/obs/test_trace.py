"""Unit suite for ``repro.obs.trace``: the bounded span ring and span sites."""
import json
import threading

from repro.obs import NO_SPAN, LatencyHistogram, MetricsRegistry, TraceRing, Tracer, jax_profile
from repro.obs import hist as obs_hist


def test_ring_keeps_newest_capacity_events():
    ring = TraceRing(capacity=4)
    for i in range(10):
        ring.append("stage", t0_ns=i, t1_ns=i + 1, idx=i)
    events = ring.events()
    assert ring.total == 10
    assert len(events) == 4
    assert [e["idx"] for e in events] == [6, 7, 8, 9]  # oldest first
    assert all(e["stage"] == "stage" for e in events)


def test_span_records_duration_and_fields():
    ring = TraceRing()
    site = Tracer(MetricsRegistry(), ring, worker="3").site("update", "serve.update_dispatch_ns")
    with site(batch=128):
        pass
    (e,) = ring.events()
    assert e["stage"] == "update"
    assert e["batch"] == 128
    assert e["worker"] == "3"
    assert e["t1_ns"] >= e["t0_ns"]


def test_dump_jsonl_round_trips(tmp_path):
    ring = TraceRing(capacity=8)
    for i in range(5):
        ring.append("publish", t0_ns=100 * i, t1_ns=100 * i + 50, records=i)
    path = tmp_path / "trace.jsonl"
    n = ring.dump_jsonl(path)
    assert n == 5
    lines = path.read_text().splitlines()
    assert len(lines) == 5
    back = [json.loads(ln) for ln in lines]
    assert back == ring.events()


def test_jax_profile_noop_when_disabled():
    # falsy log_dir: must be a true no-op, not a profiler start
    with jax_profile(None):
        pass
    with jax_profile(""):
        pass


# ---------------------------------------------------------------------------
# span sites: one call feeds the histogram, the ring and the profiler
# ---------------------------------------------------------------------------

class _Annotation:
    """Stands in for ``jax.profiler.TraceAnnotation``; logs what it is told."""

    log = []

    def __init__(self, name, **fields):
        self.log.append(("open", name, dict(fields)))

    def __enter__(self):
        self.log.append(("enter",))
        return self

    def set_metadata(self, **fields):
        self.log.append(("metadata", dict(fields)))

    def __exit__(self, *exc):
        self.log.append(("exit",))


def test_span_site_feeds_histogram_ring_and_annotation():
    registry, ring = MetricsRegistry(), TraceRing()
    site = Tracer(registry, ring, worker="3").site("wire.decode", "wire.decode_ns")
    site.annotation = _Annotation
    _Annotation.log.clear()
    with site(batch=7) as span:
        span.note(records=128)
    assert _Annotation.log == [
        ("open", "wire.decode", {"worker": "3", "batch": 7}),
        ("enter",),
        ("metadata", {"records": 128}),
        ("exit",),
    ]
    (ev,) = ring.events()
    assert ev["stage"] == "wire.decode" and ev["t1_ns"] >= ev["t0_ns"]
    assert (ev["worker"], ev["batch"], ev["records"]) == ("3", 7, 128)
    st = registry.dump()["histograms"]["wire.decode_ns"]
    assert obs_hist.state_count(st) == 1
    assert st["max_ns"] >= ev["t1_ns"] - ev["t0_ns"]


def test_span_lands_in_the_profiler_trace_on_its_thread(tmp_path):
    import jax
    from jax.profiler import ProfileData

    site = Tracer(MetricsRegistry(), TraceRing()).site("router.route", "router.flush_ns")

    def reader():
        with site(batch=2, slots=64) as span:
            span.note(live=5)

    jax.profiler.start_trace(str(tmp_path))
    try:
        t = threading.Thread(target=reader)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("**/*.xplane.pb")
    found = [
        dict(ev.stats)
        for plane in ProfileData.from_file(str(path)).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name == "router.route"
    ]
    assert found == [{"batch": 2, "slots": 64, "live": 5}]


def test_no_span_records_nothing_and_calls_nothing(monkeypatch):
    import jax

    def boom(*a, **kw):
        raise AssertionError("a span site recorded while off")

    monkeypatch.setattr(TraceRing, "append", boom)
    monkeypatch.setattr(LatencyHistogram, "record", boom)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    site = None  # what an observability-off site holds
    ran = []
    with (site(batch=1) if site is not None else NO_SPAN) as span:
        ran.append(1)
        span.note(records=3)
    assert ran == [1]
