"""batch_fill_pct.ingest (%, program counter): live records over the slots
they were routed into (``K x slot_cap`` per microbatch, ``max_batch`` in
single mode), summed over the router's ``router.route`` spans."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s["stage"] == "router.route"]
    slots = sum(s["slots"] for s in spans)
    if not slots:
        return None
    return 100.0 * sum(s["live"] for s in spans) / slots
