"""h2d_ms.ingest (ms, program span): mean host time of the feed loop's three
host-to-device copies of a microbatch, from its ``serve.h2d`` spans
(``serve/server.py``)."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s["stage"] == "serve.h2d"]
    if not spans:
        return None
    return sum(s["t1_ns"] - s["t0_ns"] for s in spans) / len(spans) / 1e6
