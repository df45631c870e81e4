"""Bounded trace ring, span sites, and an opt-in JAX profiler hook.

:class:`TraceRing` keeps the last N structured span events (stage name,
start/end ``perf_counter_ns``, batch ordinal, worker id, ...) in a
fixed-size ring: appending is O(1), memory is bounded no matter how long
the server runs, and the whole ring dumps to JSONL for offline timeline
tools.  The ring takes a short lock per append — it is *not* on the
per-record hot path, only at decode calls and microbatch boundaries, so
the cost is amortized over the records they carry.

:class:`SpanSite` is one instrumented host stage, resolved once at wiring
time by :meth:`Tracer.site`.  One ``with site(**fields):`` records the
stage's duration into its histogram, appends the span to the ring, and
opens a ``jax.profiler.TraceAnnotation`` of the same name and fields, so
the span also lands on the profiler's clock on the thread that did the
work.  A site is ``None`` when observability is off; the call site then
enters :data:`NO_SPAN` instead, which records nothing::

    with (site(batch=b) if site is not None else NO_SPAN) as span:
        work()
        span.note(records=n)  # a field known only once the work is done

:func:`jax_profile` wraps ``jax.profiler.trace`` as a context manager that
degrades to a no-op when no directory is configured or jax's profiler is
unavailable — the serve loop can always wrap itself in it.
"""
from __future__ import annotations

import contextlib
import json
import threading
import time
from typing import Any, Dict, Iterator, List, Optional

# holds every span of a long traced window (thousands of decode calls and a
# few spans per microbatch) without evicting the window's first spans
DEFAULT_CAPACITY = 1 << 16


class TraceRing:
    """Fixed-capacity ring of span-event dicts (oldest evicted first)."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY):
        cap = int(capacity)
        if cap <= 0:
            raise ValueError(f"capacity must be positive, got {capacity}")
        self.capacity = cap
        self._lock = threading.Lock()
        self._buf: List[Optional[Dict[str, Any]]] = [None] * cap
        self._next = 0
        self.total = 0  # appends ever, including evicted

    def append(
        self,
        stage: str,
        t0_ns: int,
        t1_ns: int,
        **fields: Any,
    ) -> None:
        ev = {"stage": str(stage), "t0_ns": int(t0_ns), "t1_ns": int(t1_ns)}
        ev.update(fields)
        with self._lock:
            self._buf[self._next] = ev
            self._next = (self._next + 1) % self.capacity
            self.total += 1

    def events(self) -> List[Dict[str, Any]]:
        """Retained events, oldest first."""
        with self._lock:
            if self.total < self.capacity:
                kept = self._buf[: self._next]
            else:
                kept = self._buf[self._next:] + self._buf[: self._next]
            return [dict(e) for e in kept if e is not None]

    def dump_jsonl(self, path: str) -> int:
        """Write retained events as JSON lines; returns the line count."""
        events = self.events()
        with open(path, "w", encoding="utf-8") as fh:
            for ev in events:
                fh.write(json.dumps(ev, sort_keys=True) + "\n")
        return len(events)


class Tracer:
    """A server's metrics registry and trace ring, plus the fields every
    span it records carries (the fleet worker id); the wiring-time factory
    of its span sites."""

    def __init__(self, registry, ring: TraceRing, **fields: Any):
        self.registry = registry
        self.ring = ring
        self.fields = fields

    def site(self, stage: str, hist: str) -> "SpanSite":
        """The site of ``stage``, recording durations into the registry's
        ``hist`` histogram."""
        return SpanSite(stage, self.registry.histogram(hist), self.ring, self.fields)


class SpanSite:
    """One instrumented host stage: call it with the span's fields and
    enter the returned :class:`Span` around the stage's work."""

    __slots__ = ("stage", "hist", "ring", "fields", "annotation")

    def __init__(self, stage: str, hist, ring: TraceRing, fields: Dict[str, Any]):
        from jax.profiler import TraceAnnotation

        self.stage = stage
        self.hist = hist
        self.ring = ring
        self.fields = fields
        self.annotation = TraceAnnotation

    def __call__(self, **fields: Any) -> "Span":
        return Span(self, {**self.fields, **fields})


class Span:
    """One open span of a :class:`SpanSite` (a context manager)."""

    __slots__ = ("site", "fields", "_ann", "_t0")

    def __init__(self, site: SpanSite, fields: Dict[str, Any]):
        self.site = site
        self.fields = fields

    def __enter__(self) -> "Span":
        self._ann = self.site.annotation(self.site.stage, **self.fields)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def note(self, **fields: Any) -> None:
        """Add fields known only once the work is done."""
        self.fields.update(fields)
        self._ann.set_metadata(**fields)

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self.site.hist.record(t1 - self._t0)
        self.site.ring.append(self.site.stage, self._t0, t1, **self.fields)


class _NoSpan:
    """What a site enters when observability is off: records nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass

    def note(self, **fields: Any) -> None:
        pass


NO_SPAN = _NoSpan()


@contextlib.contextmanager
def jax_profile(log_dir: Optional[str]) -> Iterator[None]:
    """``jax.profiler.trace(log_dir)`` when a directory is configured and
    the profiler imports cleanly; a plain no-op otherwise."""
    if not log_dir:
        yield
        return
    try:
        import jax
        cm = jax.profiler.trace(str(log_dir))
    except Exception:
        yield
        return
    with cm:
        yield
