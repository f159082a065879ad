"""Device time per serve step of the RX fan-out kernel, which builds each
core's bitplane-noisy copy of the bundle: the ops whose name holds
``bsc_bitplane_pallas``, averaged over the chips. None where no such op ran,
so it also says whether the kernel engaged."""


def read(ctx):
    red, runs = ctx["reduced"], ctx["serve_runs"]
    if red is None or not runs:
        return None
    per_chip = red.op_s("bsc_bitplane_pallas")
    if not any(per_chip):
        return None
    return {"value": sum(per_chip) / len(per_chip) / runs * 1e3, "unit": "ms"}
