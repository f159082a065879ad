"""Host time in the benchmark's span around ``HDCEngine.admit_many``, per
scheduler step of the traced window (host clock)."""


def read(ctx):
    if not ctx["steps"] or not ctx["admit_s"]:
        return None
    return {"value": sum(ctx["admit_s"]) / ctx["steps"] * 1e3, "unit": "ms"}
