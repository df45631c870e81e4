"""update_rate (records/s, host clock): every record the measured serve
applied, over the time from its start to the end of its drain."""


def read(ctx):
    return ctx["window_records"] / ctx["window_s"]
