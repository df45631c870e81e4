"""route_ms.ingest (ms, program span): mean host time to assemble and route
one microbatch, from the router's ``router.route`` spans (pad-assemble plus
``route_numpy`` into ``[K, slot_cap]``; ``serve/router.py``)."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s["stage"] == "router.route"]
    if not spans:
        return None
    return sum(s["t1_ns"] - s["t0_ns"] for s in spans) / len(spans) / 1e6
