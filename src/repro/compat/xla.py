"""Flat view of XLA's compiled-program cost analysis."""
from __future__ import annotations

from typing import Any


def normalized_cost_analysis(compiled: Any) -> dict:
    """``compiled.cost_analysis()`` as a fresh flat ``{metric: float}`` dict;
    ``{}`` from a backend without cost modelling."""
    return dict(compiled.cost_analysis() or {})
