"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics read.

`load` turns the trace into plain events; `Reduced` works on those alone, so
it is checked on synthetic events as well as on recorded traces.

- device busy time: the union of the intervals of the device's XLA ops,
  clipped to the benchmark's ``bench.window`` span, averaged over the chips;
- time per XLA module and per op, and the number of runs of a module;
- a kernel's time: ops whose HLO instruction name holds a given string
  (``topk_banked`` for the search kernel, ``hamming_topk_banked_pallas``);
- collective time per chip (ops named ``all-reduce``);
- ``breakdown``: the device ops that took most time, and the longest idle
  gaps on the first chip, each named by the benchmark's host span that
  covers it (``bench.admit``, ``bench.dispatch``, ``bench.collect``,
  ``bench.wait``).
"""
from __future__ import annotations

import dataclasses
import glob
import os

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("bench.admit", "bench.dispatch", "bench.collect", "bench.wait")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str               # an op's HLO instruction name; a module's name
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Trace:
    """Device events per chip (ops and modules) and the host's spans."""
    ops: dict[str, list[Event]]
    modules: dict[str, list[Event]]
    host: list[Event]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _short(name: str) -> str:
    """A TPU trace names an op by its whole HLO instruction
    (``%name = type op(operands)``) and a module as ``name(fingerprint)``;
    keep the instruction's or the module's own name."""
    return name.split(" = ", 1)[0].lstrip("%").split("(", 1)[0]


def load(trace_dir: str) -> Trace:
    """Read the newest trace under ``trace_dir``: TPU planes give ops and
    modules; the host plane gives the benchmark's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:") and "TPU" in name and "CPU" not in name:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dest = ops.setdefault(name, [])
                elif line.name == MODULES_LINE:
                    dest = modules.setdefault(name, [])
                else:
                    continue
                for ev in line.events:
                    dest.append(Event(_short(ev.name), ev.start_ns,
                                      ev.duration_ns))
        elif name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host.append(Event(ev.name, ev.start_ns, ev.duration_ns))
    return Trace(ops, modules, host)


def _union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


class Reduced:
    """The trace within the benchmark's window span."""

    def __init__(self, trace: Trace):
        win = [e for e in trace.host if e.name == WINDOW_SPAN]
        if not win:
            raise ValueError(f"the trace holds no {WINDOW_SPAN} span")
        self.t0 = min(e.start_ns for e in win)
        self.t1 = max(e.end_ns for e in win)
        inside = lambda e: e.end_ns > self.t0 and e.start_ns < self.t1
        self.ops = {d: [e for e in evs if inside(e)]
                    for d, evs in sorted(trace.ops.items())}
        self.modules = {d: [e for e in evs if inside(e)]
                        for d, evs in sorted(trace.modules.items())}
        self.host = [e for e in trace.host if e.name in HOST_SPANS and inside(e)]
        self.devices = sorted(set(self.ops) | set(self.modules))

    @property
    def window_s(self) -> float:
        return (self.t1 - self.t0) * 1e-9

    def _busy(self, dev: str) -> list[tuple[float, float]]:
        evs = self.ops.get(dev) or self.modules.get(dev, [])
        return _union((max(e.start_ns, self.t0), min(e.end_ns, self.t1))
                      for e in evs)

    def busy_s(self) -> float | None:
        """Seconds in which an op ran, averaged over the chips; None when the
        trace holds no device."""
        if not self.devices:
            return None
        tot = [sum(e - s for s, e in self._busy(d)) for d in self.devices]
        return sum(tot) / len(tot) * 1e-9

    def module_runs(self, name: str, dev: str | None = None) -> list[Event]:
        dev = dev or (self.devices[0] if self.devices else None)
        return [e for e in self.modules.get(dev, []) if e.name == name]

    def module_s(self, name: str) -> list[float]:
        """Per chip: seconds in runs of the module ``name``."""
        return [sum(e.dur_ns for e in self.module_runs(name, d)) * 1e-9
                for d in self.devices]

    def ops_matching(self, needle: str, dev: str) -> list[Event]:
        return [e for e in self.ops.get(dev, []) if needle in e.name]

    def op_s(self, needle: str) -> list[float]:
        """Per chip: seconds in ops whose name holds ``needle``."""
        return [sum(e.dur_ns for e in self.ops_matching(needle, d)) * 1e-9
                for d in self.devices]

    def top_ops(self, n: int = 10) -> list[list]:
        """The device ops that took most time, summed over the chips' mean."""
        tot: dict[str, float] = {}
        for d in self.devices:
            for e in self.ops.get(d, []):
                tot[e.name] = tot.get(e.name, 0.0) + e.dur_ns
        k = max(len(self.devices), 1)
        ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns * 1e-9 / k] for name, ns in ranked]

    def idle_gaps(self, n: int = 10) -> list[list]:
        """The longest idle gaps on the first chip, each named by the host
        span that covers its midpoint (``host`` where none does)."""
        if not self.devices:
            return []
        busy = self._busy(self.devices[0])
        gaps, prev = [], self.t0
        for s, e in busy:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        if self.t1 > prev:
            gaps.append((prev, self.t1))
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:n]:
            mid = (s + e) / 2
            label = next((h.name for h in self.host
                          if h.start_ns <= mid <= h.end_ns), "host")
            out.append([label, (e - s) * 1e-9])
        return out

    def breakdown(self) -> dict:
        return {"device_ops": self.top_ops(), "idle_gaps": self.idle_gaps()}
