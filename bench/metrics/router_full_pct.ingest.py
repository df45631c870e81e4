"""router_full_pct.ingest (%, program counter): share of the 100 ms samples
of the router's routed-batch queue in which it held ``queue_depth`` batches,
that is, in which wire decode and routing were ahead of the feed loop."""


def read(ctx):
    samples = ctx["queue_samples"]
    if not samples:
        return None
    full = sum(1 for d in samples if d >= ctx["queue_depth"])
    return 100.0 * full / len(samples)
