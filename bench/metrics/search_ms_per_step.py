"""Device time per serve step of the search kernel: the ops whose name or
HLO metadata holds ``topk_banked``, averaged over the chips."""


def read(ctx):
    red, runs = ctx["reduced"], ctx["serve_runs"]
    if red is None or not runs:
        return None
    per_chip = red.op_s("topk_banked")
    if not any(per_chip):
        return None
    return {"value": sum(per_chip) / len(per_chip) / runs * 1e3, "unit": "ms"}
