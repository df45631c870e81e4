"""The readers of the program's host-stage spans (``bench/metrics/<name>.py``)
on a hand-made context: exact values, and ``None`` without their spans."""
import pytest

import run


def _span(stage, t0_ms, t1_ms, **fields):
    return {"stage": stage, "t0_ns": int(t0_ms * 1e6), "t1_ns": int(t1_ms * 1e6), **fields}


SPANS = [
    _span("wire.decode", 0, 1, records=500),
    _span("wire.decode", 1, 4, records=300),
    _span("wire.decode", 4, 6, records=200),
    _span("router.route", 6, 10, batch=0, live=600, slots=3200),
    _span("serve.wait", 0, 11, batch=0),
    _span("update", 11, 19, batch=0, live=600),
    _span("serve.h2d", 11, 14, batch=0),
    _span("router.route", 12, 18, batch=1, live=400, slots=3200),
    _span("serve.wait", 19, 20, batch=1),
    _span("update", 20, 26, batch=1, live=400),
    _span("serve.h2d", 20, 21, batch=1),
    _span("publish", 26, 30, records=1000),
]
CTX = {"window_s": 0.04, "spans": SPANS}


@pytest.mark.parametrize("name,want", [
    ("decode_ms.ingest", 3.0),  # 6 ms of decode over 2 routed microbatches
    ("route_ms.ingest", 5.0),  # (4 + 6) / 2
    ("batch_fill_pct.ingest", 15.625),  # 1000 live of 6400 slots
    ("feed_wait_pct.ingest", 30.0),  # 12 ms of waits in a 40 ms window
    ("h2d_ms.ingest", 2.0),  # (3 + 1) / 2
])
def test_span_reader(name, want):
    assert run.reader(name)(CTX) == pytest.approx(want)


@pytest.mark.parametrize("name,keep", [
    # the spans each reader needs, and one without them: the parent's
    # ring, which holds only update and publish spans
    ("decode_ms.ingest", ("wire.decode",)),
    ("decode_ms.ingest", ("router.route",)),
    ("route_ms.ingest", ("wire.decode", "update")),
    ("batch_fill_pct.ingest", ("update", "publish")),
    ("feed_wait_pct.ingest", ("update", "serve.h2d")),
    ("h2d_ms.ingest", ("update", "serve.wait")),
])
def test_span_reader_without_its_spans_returns_none(name, keep):
    spans = [s for s in SPANS if s["stage"] in keep]
    assert run.reader(name)(dict(CTX, spans=spans)) is None
    assert run.reader(name)(dict(CTX, spans=[])) is None
