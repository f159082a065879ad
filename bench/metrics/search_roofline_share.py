"""The search's least time over its measured time per step: the least time
counts the algorithm's operations and bytes (``work.py``) against the peaks
of the chip's ``device_kind`` (``peaks.py``)."""
from peaks import peaks
from work import least_time, search_work


def read(ctx):
    red, runs = ctx["reduced"], ctx["serve_runs"]
    if red is None or not runs:
        return None
    per_chip = red.op_s("topk_banked")
    measured = sum(per_chip) / len(per_chip) / runs
    if measured <= 0:
        return None
    s, chips = ctx["sizes"], ctx["model_size"]
    ops, nbytes = search_work(
        slots=s["slots"], trials_per_slot=s["trials_per_request"],
        classes_on_chip=s["n_classes"] // chips,
        cores_on_chip=s["n_rx_cores"] // chips, dim=s["dim"])
    least, bound = least_time(ops, nbytes, peaks(ctx["device_kind"]))
    ctx["notes"].append(f"search_roofline_share: {bound}-bound, least "
                        f"{least * 1e3:.6f} ms against {measured * 1e3:.6f} ms")
    return {"value": 100.0 * least / measured, "unit": "%"}
