"""The exchange's least time over its measured time per serve step: the
least time counts the algorithm's bytes a chip (``exchange.py``) against one
direction of the chip's interconnect (``peaks.py``). None on a one-chip
trace."""
import exchange
from peaks import peaks


def read(ctx):
    measured = exchange.measured_s_per_step(ctx)
    if measured is None:
        return None
    s = ctx["sizes"]
    nbytes = exchange.exchange_bytes(
        trials=s["slots"] * s["trials_per_request"], dim=s["dim"],
        m_tx=s["m_tx"], chips=ctx["model_size"])
    least = exchange.least_time(nbytes, peaks(ctx["device_kind"]))
    ctx["notes"].append(f"collective_roofline_share: {nbytes:.0f} bytes a "
                        f"chip, least {least * 1e3:.6f} ms against "
                        f"{measured * 1e3:.6f} ms")
    return {"value": 100.0 * least / measured, "unit": "%"}
