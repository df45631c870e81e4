"""decode_ms.ingest (ms, program span): wire decode time per routed
microbatch: the summed ``wire.decode`` spans of the reader thread
(``serve/sources.py``) over the number of ``router.route`` spans
(``serve/router.py``)."""


def read(ctx):
    decode = [s for s in ctx["spans"] if s["stage"] == "wire.decode"]
    routed = sum(1 for s in ctx["spans"] if s["stage"] == "router.route")
    if not decode or not routed:
        return None
    return sum(s["t1_ns"] - s["t0_ns"] for s in decode) / routed / 1e6
