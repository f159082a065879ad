"""Share of the traced window in which no op ran on the device, averaged
over the chips: 1 - (union of op intervals) / window."""


def read(ctx):
    red = ctx["reduced"]
    busy = red.busy_s() if red is not None else None
    if busy is None or busy <= 0:
        return None
    return {"value": 100.0 * (1.0 - busy / red.window_s), "unit": "%"}
