"""Each metric's reader (``bench/metrics/<name>.py``) on a hand-made context."""
import pytest

import run

TRACE = {
    "window_s": 4.0, "busy_s": 3.0, "idle_pct": 25.0,
    "programs": {"jit_step": {"count": 4, "seconds": 0.02}},
    "ops": {"hier_cascade.1": {"count": 4, "seconds": 0.008}, "fusion.2": {"count": 4, "seconds": 0.01}},
}
CTX = {
    "setup_s": 21.5,
    "window_s": 40.0,
    "window_records": 1_000_000,
    "config": {"step_program": "jit_step"},
    "spans": [
        {"stage": "update", "t0_ns": 0, "t1_ns": 2_000_000},
        {"stage": "publish", "t0_ns": 0, "t1_ns": 9_000_000},
        {"stage": "update", "t0_ns": 5_000_000, "t1_ns": 9_000_000},
    ],
    "queue_samples": [8, 3, 8, 0],
    "queue_depth": 8,
    "trace": TRACE,
}


@pytest.mark.parametrize("name,want", [
    ("update_rate", 25_000.0),  # all records over the whole window, drain included
    ("setup_s", 21.5),
    ("router_full_pct.ingest", 50.0),
    ("dispatch_ms.ingest", 3.0),  # update spans only
    ("step_device_ms", 5.0),
    ("cascade_kernel_ms", 2.0),
    ("device_idle_pct.ingest", 25.0),
])
def test_reader(name, want):
    assert run.reader(name)(CTX) == pytest.approx(want)


@pytest.mark.parametrize("name", [
    "router_full_pct.ingest", "dispatch_ms.ingest", "step_device_ms",
    "cascade_kernel_ms", "device_idle_pct.ingest",
])
def test_reader_with_nothing_to_read_returns_none(name):
    empty = dict(CTX, spans=[], queue_samples=[], trace=None)
    assert run.reader(name)(empty) is None


def test_kernel_absent_from_the_trace_reads_nothing():
    single = dict(CTX, trace=dict(TRACE, ops={"fusion.2": {"count": 4, "seconds": 0.01}}))
    assert run.reader("cascade_kernel_ms")(single) is None
