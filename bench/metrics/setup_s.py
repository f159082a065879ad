"""Process start to the first timed request: imports, the channel state,
the banks and payloads, compilation and warm-up (host clock)."""


def read(ctx):
    return {"value": ctx["setup_s"], "unit": "s"}
