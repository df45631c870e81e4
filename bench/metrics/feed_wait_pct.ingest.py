"""feed_wait_pct.ingest (%, program span): share of the window in which the
feed loop waited for a routed batch, from its ``serve.wait`` spans
(``serve/server.py``)."""


def read(ctx):
    spans = [s for s in ctx["spans"] if s["stage"] == "serve.wait"]
    if not spans:
        return None
    return 100.0 * sum(s["t1_ns"] - s["t0_ns"] for s in spans) / 1e9 / ctx["window_s"]
