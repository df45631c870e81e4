"""The reduction from a profiler trace to device numbers, on a small trace
recorded on one TPU v5e chip and on hand-made events."""
import json
from pathlib import Path

import numpy as np
import pytest

import devtrace

DATA = Path(__file__).resolve().parent / "data" / "pallas_trace_small.json"


def _brute_busy(events, w0, w1):
    """Busy nanoseconds by marking every nanosecond an op covers."""
    mark = np.zeros(int(w1 - w0), bool)
    for e in events:
        s = int(max(e["start_ns"], w0) - w0)
        t = int(min(e["start_ns"] + e["dur_ns"], w1) - w0)
        if t > s:
            mark[s:t] = True
    return mark.sum()


def test_recorded_pallas_trace():
    events = json.loads(DATA.read_text())["events"]
    got = devtrace.reduce(events)
    span = next(e for e in events if e["name"] == devtrace.WINDOW_SPAN)
    ops = [e for e in events if e["line"] == devtrace.OPS_LINE]
    busy = _brute_busy(ops, span["start_ns"], span["start_ns"] + span["dur_ns"])
    assert got["window_s"] == pytest.approx(span["dur_ns"] / 1e9)
    assert got["busy_s"] == pytest.approx(busy / 1e9, rel=1e-3)
    assert got["idle_pct"] == pytest.approx(100 * (1 - got["busy_s"] / got["window_s"]))
    # three executions of the pallas step, each with one hier_cascade call
    assert got["programs"]["jit_step"]["count"] == 3
    assert got["ops"]["hier_cascade.1"]["count"] == 3
    assert got["device_ops"][0][0] == "hier_cascade.1"
    assert len(got["device_ops"]) <= 10 and len(got["idle_gaps"]) <= 10
    gaps = sum(s for _, s in got["idle_gaps"])
    assert gaps == pytest.approx(got["window_s"] - got["busy_s"], rel=1e-6)


def _ev(line, name, start, dur, plane="/device:TPU:0"):
    return {"plane": plane, "line": line, "name": name, "start_ns": float(start), "dur_ns": float(dur)}


def test_hand_made_window():
    events = [
        _ev("python", devtrace.WINDOW_SPAN, 1000, 10_000_000, "/host:CPU"),
        _ev(devtrace.MODULES_LINE, "jit_step(42)", 0, 3_001_000),
        _ev(devtrace.OPS_LINE, "%fusion.1 = f32[8] fusion()", 0, 2_001_000),  # clipped to 2 ms
        _ev(devtrace.OPS_LINE, "%hier_cascade.1 = (s32[]) custom-call()", 1_500_000, 1_501_000),
        _ev(devtrace.MODULES_LINE, "jit_step(42)", 6_001_000, 1_000_000),
        _ev(devtrace.OPS_LINE, "%hier_cascade.1 = (s32[]) custom-call()", 6_001_000, 1_000_000),
        _ev("main/1", "PJRT_LoadedExecutable_Execute", 3_001_000, 2_500_000, "/host:CPU"),
    ]
    got = devtrace.reduce(events)
    assert got["window_s"] == 0.01
    assert got["busy_s"] == pytest.approx(0.004)  # 1k..3.001M and 6.001M..7.001M
    assert got["idle_pct"] == pytest.approx(60.0)
    # the first step began before the window: only the second is whole
    assert got["programs"]["jit_step"] == {"count": 1, "seconds": pytest.approx(0.001)}
    assert got["ops"]["hier_cascade.1"] == {"count": 2, "seconds": pytest.approx(0.002501)}
    assert "fusion.1" not in got["ops"]
    assert dict(got["device_ops"])["fusion.1"] == pytest.approx(0.001499)  # clipped, less hier_cascade
    # the gap 3.001..6.001 ms is mostly the host's Execute; the tail has no host event
    gaps = dict(got["idle_gaps"])
    assert gaps["PJRT_LoadedExecutable_Execute"] == pytest.approx(0.003)
    assert gaps["host: idle"] == pytest.approx(0.003)


def test_no_window_or_no_device_reads_nothing():
    assert devtrace.reduce([_ev(devtrace.OPS_LINE, "x", 0, 10)]) is None
    assert devtrace.reduce([_ev("python", devtrace.WINDOW_SPAN, 0, 10, "/host:CPU")]) is None


def test_enclosing_ops_keep_their_self_time():
    events = [
        _ev("python", devtrace.WINDOW_SPAN, 0, 100, "/host:CPU"),
        _ev(devtrace.OPS_LINE, "%while.7 = () while()", 10, 60),
        _ev(devtrace.OPS_LINE, "%fusion.1 = f32[8] fusion()", 15, 20),
        _ev(devtrace.OPS_LINE, "%fusion.2 = f32[8] fusion()", 40, 25),
        _ev(devtrace.OPS_LINE, "%copy.3 = f32[8] copy()", 80, 10),
    ]
    got = devtrace.reduce(events)
    assert got["busy_s"] == pytest.approx(70e-9)
    secs = {k: v["seconds"] for k, v in got["ops"].items()}
    assert secs == pytest.approx({"while.7": 15e-9, "fusion.1": 20e-9, "fusion.2": 25e-9, "copy.3": 10e-9})
    assert sum(s for _, s in got["device_ops"]) == pytest.approx(got["busy_s"])
