"""Open loop: Poisson arrivals at the mix's ``rate_per_s``, tenants weighted
by its ``zipf_alpha``.

The arrivals are a seeded race between per-tenant exponential clocks (the
race of ``benchmarks/serving.py``), each tenant's clock running at its share
of the rate: the next arrival is the tenant whose clock fires first, so the
merged stream is Poisson at ``rate_per_s``. Every due time is fixed before
the window opens."""
from __future__ import annotations

import numpy as np

from traffic import tenant_weights


class Process:
    def __init__(self, mix, rng, seconds, n_slots, n_tenants):
        rates = float(mix["rate_per_s"]) * tenant_weights(mix, n_tenants)
        nxt = rng.exponential(1.0 / rates)
        self.arrivals = []
        while True:
            t = int(np.argmin(nxt))
            if nxt[t] >= seconds:
                break
            self.arrivals.append((float(nxt[t]), t, len(self.arrivals)))
            nxt[t] += rng.exponential(1.0 / rates[t])
        self.n_slots = n_slots

    def admit_sizes(self) -> list[int]:
        return list(range(1, self.n_slots + 1))

    def start(self) -> list[tuple[float, int, int]]:
        return list(self.arrivals)

    def on_done(self, client: int, now: float) -> list:
        return []
