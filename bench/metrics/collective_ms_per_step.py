"""Device time per serve step in the cross-chip exchange (the OTA vote
all-reduce and the top-1 all-gather): the collective ops within the serve
step's runs, async pairs from start to done, averaged over the chips
(``exchange.py``). None on a one-chip trace."""
import exchange


def read(ctx):
    s = exchange.measured_s_per_step(ctx)
    if s is None:
        return None
    return {"value": s * 1e3, "unit": "ms"}
