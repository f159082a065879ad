"""Median, over every request due in the window, of the time from its due
time to its result on the host (host clock)."""
import numpy as np


def read(ctx):
    if not ctx["latencies"]:
        return None
    return {"value": float(np.percentile(ctx["latencies"], 50)) * 1e3, "unit": "ms"}
