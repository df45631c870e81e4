"""step_device_ms (ms, device trace): mean device time per execution of the
session's update-step program (the configuration's ``step_program``)."""


def read(ctx):
    tr = ctx["trace"]
    if tr is None:
        return None
    p = tr["programs"].get(ctx["config"]["step_program"])
    if not p or not p["count"]:
        return None
    return 1e3 * p["seconds"] / p["count"]
