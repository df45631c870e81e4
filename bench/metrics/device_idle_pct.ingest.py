"""device_idle_pct.ingest (%, device trace): share of the traced window in
which no operation ran on the device."""


def read(ctx):
    tr = ctx["trace"]
    return None if tr is None else tr["idle_pct"]
