"""The one traffic generator: a mix (``bench/traffic/<mix>.json``) names its
``process``, a module ``bench/traffic/<process>.py`` found by name, whose
``Process`` turns the mix's parameters and the seed into requests.

A request is planned as (due offset in seconds from the window's start,
tenant, client). ``Process.start()`` plans the first requests,
``Process.on_done(client, now)`` those a completion sets off (a closed loop
resubmits; an open loop plans everything at the start, so a slow service
receives the same load and its queue grows), and ``Process.admit_sizes()``
lists the admission batch sizes the traffic can produce, which set-up warms.
A new arrival process or client policy is a new module and a new mix file.
"""
from __future__ import annotations

import numpy as np

import spec
from deploy import STREAM_TRAFFIC, host_rng


def tenant_weights(mix: dict, n_tenants: int) -> np.ndarray:
    """Share of the traffic each tenant receives: Zipf with the mix's
    ``zipf_alpha`` over the tenants in order (0 is uniform)."""
    w = 1.0 / np.arange(1, n_tenants + 1) ** float(mix["zipf_alpha"])
    return w / w.sum()


def process(mix: dict, seed: int, seconds: float, n_slots: int, n_tenants: int):
    """The mix's arrival process over a window of ``seconds``, from the seed."""
    return spec.load_process(mix["process"])(
        mix, host_rng(seed, STREAM_TRAFFIC), seconds, n_slots, n_tenants)
