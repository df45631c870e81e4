"""repro.obs — runtime observability plane.

Low-overhead metrics (counters / gauges / log-bucketed latency
histograms), a bounded trace ring, and the enable plumbing shared by the
serve and fleet stacks, and span sites that put each host stage on the
profiler's clock.  Off by default: sites hold ``None`` and cost one
``is not None`` check (the ``repro.faults`` zero-overhead contract).
Enable with ``ServeConfig(metrics=True)`` or ``REPRO_OBS=1``.
"""
from repro.obs.hist import (
    NUM_BUCKETS,
    LatencyHistogram,
    bucket_index,
    bucket_upper_bound,
    merge_state_maps,
    merge_states,
    state_count,
    state_percentile,
    summarize_state,
)
from repro.obs.registry import (
    OBS_ENV_VAR,
    Counter,
    Gauge,
    MetricsRegistry,
    dump_to_prometheus,
    env_enabled,
)
from repro.obs.trace import NO_SPAN, SpanSite, TraceRing, Tracer, jax_profile

__all__ = [
    "NUM_BUCKETS",
    "LatencyHistogram",
    "bucket_index",
    "bucket_upper_bound",
    "merge_state_maps",
    "merge_states",
    "state_count",
    "state_percentile",
    "summarize_state",
    "OBS_ENV_VAR",
    "Counter",
    "Gauge",
    "MetricsRegistry",
    "dump_to_prometheus",
    "env_enabled",
    "NO_SPAN",
    "SpanSite",
    "TraceRing",
    "Tracer",
    "jax_profile",
]
