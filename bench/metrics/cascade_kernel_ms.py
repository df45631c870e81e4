"""cascade_kernel_ms (ms, device trace): mean device time per call of the
``hier_cascade`` Pallas kernel."""

KERNEL = "hier_cascade"


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    calls = [v for k, v in tr["ops"].items() if KERNEL in k]
    n = sum(v["count"] for v in calls)
    if not n:
        return None
    return 1e3 * sum(v["seconds"] for v in calls) / n
