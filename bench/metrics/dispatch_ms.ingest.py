"""dispatch_ms.ingest (ms, program span): mean host time of the feed loop's
update dispatch (host-to-device copies and the step's enqueue), from the
server's ``update`` spans."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s["stage"] == "update"]
    if not spans:
        return None
    return sum(s["t1_ns"] - s["t0_ns"] for s in spans) / len(spans) / 1e6
