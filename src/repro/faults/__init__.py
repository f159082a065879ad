"""Fault-injection subsystem: hard faults the PHY re-fit can't recover.

See `repro.faults.model` for the `FaultState` pytree (memory / node / wire
fault surfaces), the evolution-law registry (`FAULTS`, mirroring
`phy.PROCESSES`), the host-side failover planner, and the combo-wire erasure
helpers. The OTA serve builder (`core.scaleout.make_mt_ota_serve` and its
one-slot view `make_ota_serve`) threads the state through the serve step
when built with a ``faults=`` model; the serving layer's `FaultController`
promotes persistently-dead rows from PHY quarantine to a failover remap at
the step barrier.
"""
from repro.faults.model import (
    FAULTS,
    FaultModel,
    FaultState,
    StaticFaults,
    TransientVoteFaults,
    WearoutFaults,
    fstate_shape_structs,
    fstate_spec,
    get_fault_model,
    healthy_for,
    healthy_state,
    inject,
    live_combo_mask,
    live_majority_labels,
    plan_failover,
    recenter_state,
    register_fault_model,
    sample_stuck_cells,
    sample_word_dropout,
)

__all__ = [
    "FAULTS",
    "FaultModel",
    "FaultState",
    "StaticFaults",
    "TransientVoteFaults",
    "WearoutFaults",
    "fstate_shape_structs",
    "fstate_spec",
    "get_fault_model",
    "healthy_for",
    "healthy_state",
    "inject",
    "live_combo_mask",
    "live_majority_labels",
    "plan_failover",
    "recenter_state",
    "register_fault_model",
    "sample_stuck_cells",
    "sample_word_dropout",
]
