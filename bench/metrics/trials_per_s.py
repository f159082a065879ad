"""Trials of every request completed in the window, over the time from the
window's start to the last completion (host clock)."""


def read(ctx):
    span = ctx["t_last_finish"] - ctx["t0"]
    if not ctx["trials_done"] or span <= 0:
        return None
    return {"value": ctx["trials_done"] / span, "unit": "trials/s"}
