"""Reduce a JAX profiler trace to the benchmark's device numbers.

:func:`load_events` flattens an ``.xplane.pb`` into plain event dicts
(``plane``, ``line``, ``name``, ``start_ns``, ``dur_ns``); :func:`reduce`
works on those dicts alone, so the arithmetic is checked on a small
recorded trace without a chip.

* The traced window is the host span named :data:`WINDOW_SPAN`, which the
  harness opens after the profiler starts and closes before it stops.
* Device busy time is the union of the intervals in which an operation
  ran on a device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the window and averaged over the devices that ran any.
* Programs are the ``XLA Modules`` line's events, by module name (the
  ``(id)`` suffix dropped); ops are the ``XLA Ops`` line's events by HLO
  instruction name (``hier_cascade.1``, ``fusion.2``), each with its self
  time: an op that encloses others keeps what they leave uncovered.  A
  program's or op's count and time take only the executions that lie
  wholly inside the window, so a step cut off at its edge does not count
  as a whole step (the top ops of ``device_ops`` take every op's clipped
  time, as the busy union does).
* Idle gaps are the stretches of the window in which a device ran nothing,
  each named by the host event that overlaps it most (``host: idle`` when
  none does), summed per name; gaps under 10 us are summed as one.
"""
from __future__ import annotations

import re
from collections import defaultdict
from typing import Any, Dict, List, Optional

import numpy as np

WINDOW_SPAN = "bench.traced_window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_MODULE_ID = re.compile(r"\(\d+\)$")
# gaps shorter than this sit between the ops of one program; they are summed
# under one name instead of being matched to a host event
SHORT_GAP_NS = 10_000
SHORT_GAP = "device: between ops (< 10 us)"


def load_events(path: str) -> List[Dict[str, Any]]:
    """Every timed event of the trace at ``path`` as a plain dict."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append({
                    "plane": plane.name,
                    "line": line.name,
                    "name": ev.name,
                    "start_ns": float(ev.start_ns),
                    "dur_ns": float(ev.duration_ns),
                })
    return out


def _union(intervals) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _self_times(evs, ops) -> None:
    """Add each op's count and self time to ``ops``: an op that encloses
    others on the line (a ``while`` or ``cond`` around its body's fusions)
    keeps only the time none of them covers."""
    stack = []  # [end, name, self time] of the enclosing ops
    for s, t, name in sorted(evs, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            _, n, own = stack.pop()
            ops[n][1] += own
        if stack:
            stack[-1][2] -= min(t, stack[-1][0]) - s
        ops[name][0] += 1
        stack.append([t, name, t - s])
    for _, n, own in stack:
        ops[n][1] += own


def module_name(name: str) -> str:
    """``jit_step(5563667342079350457)`` -> ``jit_step``."""
    return _MODULE_ID.sub("", name).strip()


def op_name(name: str) -> str:
    """An HLO op event's instruction name: ``%hier_cascade.1 = ...`` ->
    ``hier_cascade.1``."""
    return name.split(" = ", 1)[0].lstrip("%").strip()


def reduce(events: List[Dict[str, Any]], top: int = 10) -> Optional[Dict[str, Any]]:
    """Device busy and idle time, per-program and per-op device time, and
    the idle gaps by host activity, over the traced window.  ``None`` when
    the trace holds no window span or no device operation."""
    spans = [e for e in events if e["name"] == WINDOW_SPAN]
    if not spans:
        return None
    w0 = spans[0]["start_ns"]
    w1 = w0 + spans[0]["dur_ns"]

    def clip(e):
        s, t = max(e["start_ns"], w0), min(e["start_ns"] + e["dur_ns"], w1)
        return (s, t) if t > s else None

    def whole(e):
        return e["start_ns"] >= w0 and e["start_ns"] + e["dur_ns"] <= w1

    per_device = defaultdict(list)
    inside = defaultdict(list)  # the ops that lie wholly inside the window
    programs = defaultdict(lambda: [0, 0.0])
    for e in events:
        if not _DEVICE_PLANE.match(e["plane"]):
            continue
        iv = clip(e)
        if iv is None:
            continue
        if e["line"] == OPS_LINE:
            per_device[e["plane"]].append((iv[0], iv[1], op_name(e["name"])))
            if whole(e):
                inside[e["plane"]].append((iv[0], iv[1], op_name(e["name"])))
        elif e["line"] == MODULES_LINE and whole(e):
            p = programs[module_name(e["name"])]
            p[0] += 1
            p[1] += iv[1] - iv[0]
    if not per_device:
        return None
    clipped = defaultdict(lambda: [0, 0.0])
    for evs in per_device.values():
        _self_times(evs, clipped)
    ops = defaultdict(lambda: [0, 0.0])
    for evs in inside.values():
        _self_times(evs, ops)
    window_ns = w1 - w0
    busy = {d: _union([(a, b) for a, b, _ in iv]) for d, iv in per_device.items()}
    busy_ns = sum(sum(t - s for s, t in b) for b in busy.values()) / len(busy)

    host = [
        e for e in events
        if e["plane"].startswith("/host:") and 0 < e["dur_ns"] < window_ns
        and e["name"] != WINDOW_SPAN
    ]
    hs = np.array([e["start_ns"] for e in host], np.float64)
    ht = hs + np.array([e["dur_ns"] for e in host], np.float64)
    gaps = defaultdict(float)
    for b in busy.values():
        edges = [w0] + [x for iv in b for x in iv] + [w1]
        for s, t in zip(edges[0::2], edges[1::2]):
            if t <= s:
                continue
            name = SHORT_GAP
            if t - s >= SHORT_GAP_NS:
                name = "host: idle"
                if host:
                    ov = np.minimum(t, ht) - np.maximum(s, hs)
                    i = int(np.argmax(ov))
                    if ov[i] > 0:
                        name = host[i]["name"]
            gaps[name] += (t - s) / len(busy)

    def ranked(table, key):
        return sorted(table.items(), key=key, reverse=True)[:top]

    return {
        "window_s": window_ns / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_pct": 100.0 * (1.0 - busy_ns / window_ns),
        "devices": len(busy),
        "programs": {k: {"count": c, "seconds": s / 1e9} for k, (c, s) in programs.items()},
        "ops": {k: {"count": c, "seconds": s / 1e9} for k, (c, s) in ops.items()},
        "device_ops": [[k, s / 1e9] for k, (c, s) in ranked(clipped, lambda kv: kv[1][1])],
        "idle_gaps": [[k, s / 1e9] for k, s in ranked(gaps, lambda kv: kv[1])],
    }
