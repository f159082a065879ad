"""95th percentile, over the requests due in the traced window, of the time
from a request's due time to its admission into a ring slot
(``HDCCompletion.t_admit``)."""
import numpy as np


def read(ctx):
    waits = [t_admit - due for due, t_admit, _ in ctx["completions"]]
    if len(waits) < 20:
        return None
    return {"value": float(np.percentile(waits, 95)) * 1e3, "unit": "ms"}
