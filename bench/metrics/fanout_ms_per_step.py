"""Device time per serve step of the OTA bundle and the RX fan-out: the
serve-step module's time, less the search kernel and the all-reduce ops,
averaged over the chips. The module is found by the name of the program the
benchmark compiled for the step."""


def read(ctx):
    red, runs = ctx["reduced"], ctx["serve_runs"]
    if red is None or not runs:
        return None
    module = red.module_s(ctx["serve_module"])
    search = red.op_s("topk_banked")
    coll = red.op_s("all-reduce")
    rest = [m - s - c for m, s, c in zip(module, search, coll)]
    if not any(module) or not any(search):
        return None
    return {"value": sum(rest) / len(rest) / runs * 1e3, "unit": "ms"}
