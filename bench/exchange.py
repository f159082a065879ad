"""The cross-chip exchange of one serve step: its least bytes, counted from
the algorithm and not from any wire format, and its measured device time,
from the trace's collective ops.

Least bytes a chip, over a model axis of n chips:

- the OTA vote tally: every trial's d-bit vote sum, each field just wide
  enough for the M voters' span (``ceil(log2(2M + 1))`` bits), so
  ``trials * d * ceil(log2(2M + 1)) / 8`` bytes, moved as a ring all-reduce:
  ``2(n - 1)/n`` of it through each chip;
- the top-1 gather: each of the other n - 1 chips' (value, index) pair of
  4 bytes each, per trial.

Counted against one direction of the chip's interconnect
(``ici_bits_per_s / 8``), so the least time is a lower bound whether the
exchange runs as ``psum``, ``psum_packed`` or ``rs_ag``.

Measured time: per chip, the union, within the serve step's module runs, of
the intervals of its collective ops: names holding ``all-reduce``,
``all-gather``, ``reduce-scatter`` or ``collective-permute``, or ``psum``,
the name XLA gives the all-reduce that ``jax.lax.psum`` lowers to (the vote
tally's op in the compiled ring step is ``psum.<n>``). An async pair counts
from the start of its ``-start`` op to the end of its ``-done`` op, whatever
runs between them; a synchronous op counts its own duration.
"""
from __future__ import annotations

import bisect
import math

KINDS = ("all-reduce", "all-gather", "reduce-scatter", "collective-permute",
         "psum")


def field_bits(m_tx: int) -> int:
    """Bits of a vote-sum field that spans [-M, M]."""
    return math.ceil(math.log2(2 * m_tx + 1))


def exchange_bytes(*, trials: int, dim: int, m_tx: int, chips: int) -> float:
    """Least bytes through one chip for one serve step's exchange."""
    if chips < 2:
        return 0.0
    tally = trials * dim * field_bits(m_tx) / 8
    ring = 2 * (chips - 1) / chips * tally
    gather = (chips - 1) * trials * 8
    return ring + gather


def least_time(nbytes: float, peak: dict) -> float:
    return nbytes / (peak["ici_bits_per_s"] / 8)


def _kind(name: str) -> str | None:
    return next((k for k in KINDS if k in name), None)


def _intervals(ops) -> list[tuple[float, float]]:
    """Each collective's interval: an async ``-start`` is paired with the
    next ``-done`` of the same kind, in the order they started."""
    out, open_ = [], {}
    for e in sorted(ops, key=lambda e: e.start_ns):
        kind = _kind(e.name)
        if kind is None:
            continue
        if f"{kind}-start" in e.name:
            open_.setdefault(kind, []).append(e.start_ns)
        elif f"{kind}-done" in e.name:
            starts = open_.get(kind)
            out.append((starts.pop(0) if starts else e.start_ns, e.end_ns))
        else:
            out.append((e.start_ns, e.end_ns))
    return out


def _union_s(intervals) -> float:
    tot, end = 0.0, -math.inf
    for s, e in sorted(intervals):
        if e <= end:
            continue
        tot += e - max(s, end)
        end = e
    return tot * 1e-9


def collective_s(red, module: str) -> list[float]:
    """Per chip: seconds in the exchange's collectives during runs of the
    serve step's module ``module``."""
    per_chip = []
    for dev in red.devices:
        runs = sorted(red.module_runs(module, dev), key=lambda r: r.start_ns)
        starts = [r.start_ns for r in runs]

        def inside(e):
            i = bisect.bisect_right(starts, e.start_ns) - 1
            return i >= 0 and e.start_ns < runs[i].end_ns

        ops = [e for e in red.ops.get(dev, []) if inside(e)]
        per_chip.append(_union_s(_intervals(ops)))
    return per_chip


def measured_s_per_step(ctx) -> float | None:
    """The exchange's device time per serve step, averaged over the chips;
    None on a one-chip trace, or where no collective ran."""
    red, runs = ctx["reduced"], ctx["serve_runs"]
    if red is None or not runs or len(red.devices) < 2:
        return None
    per_chip = collective_s(red, ctx["serve_module"])
    if not any(per_chip):
        return None
    return sum(per_chip) / len(per_chip) / runs
