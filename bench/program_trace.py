"""The program's own layers in a profiler trace, beside what `trace_reduce`
reads from the benchmark's spans and the XLA op names.

- Device scopes. The serve step's stages run under ``jax.named_scope``
  (``core/scaleout.py``): ``ota_bundle`` (the vote collective),
  ``rx_copies`` (each core's noisy copy), ``search`` (bank gather, layout
  copies of the store, the ``topk_banked`` Pallas kernel, the core
  reductions) and ``top1_gather``. `scope_map` maps each instruction of the
  compiled step to its scope, read from the ``op_name`` of its HLO metadata.
  An instruction with no scope there (a layout copy the compiler put in, an
  argument's copy, the slot keys' fold) takes the scope of the first op that
  consumes it, else of the first that produces it; `ScopeMap.inherited`
  lists those.
- Host spans. ``SlotScheduler.step`` is ``scheduler.step`` around
  ``scheduler.admit``, ``scheduler.dispatch`` and ``scheduler.collect``; the
  HDC service adds ``hdc.admit_scatter`` (in ``admit_many``), ``hdc.fetch``
  and ``hdc.barrier`` (in ``_collect``). `load_spans` reads them.

`ProgramLayers` works on a `trace_reduce.Reduced`, those spans and a scope
map alone, so it is checked on synthetic events as well as on recorded
traces. Over a program without scopes or spans it finds nothing and reads
None.
"""
from __future__ import annotations

import collections
import dataclasses
import re
from bisect import bisect_right

import trace_reduce as tr
from trace_reduce import Event

SCOPES = ("ota_bundle", "rx_copies", "search", "top1_gather")
SPAN_PREFIXES = ("scheduler.", "hdc.")
KERNEL = "topk_banked"

_SCOPE = re.compile(r"(?:^|[/(])(" + "|".join(SCOPES) + r")(?=[/)]|$)")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([\w.\-]+)\s+=\s+(.*)$")
_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")


def scope_of(op_name: str) -> str | None:
    """The outermost of the four scopes in an ``op_name`` path (a scope
    inside a transformation reads ``vmap(rx_copies)``), or None."""
    m = _SCOPE.search(op_name)
    return m.group(1) if m else None


def _balanced(s: str, i: int) -> int:
    """Index just past the parenthesis that closes the one at ``s[i]``."""
    depth = 0
    for j in range(i, len(s)):
        depth += {"(": 1, ")": -1}.get(s[j], 0)
        if depth == 0:
            return j + 1
    return len(s)


def _parse(rest: str) -> tuple[str, list[str], str]:
    """(opcode, operand names, attributes) of an instruction's right-hand
    side ``type opcode(operands), attributes``; a tuple type is in
    parentheses, with parentheses inside its layouts."""
    i = _balanced(rest, 0) if rest.startswith("(") else rest.find(" ")
    body = rest[i:].lstrip()
    k = body.find("(")
    if k < 0:
        return body, [], ""
    end = _balanced(body, k)
    return body[:k], _OPERAND.findall(body[k:end]), body[end:]


@dataclasses.dataclass
class ScopeMap:
    """Instruction name -> scope (None where no op it touches has one)."""
    scope: dict[str, str | None]
    inherited: set[str]

    def get(self, name: str) -> str | None:
        return self.scope.get(name)


def scope_map(hlo_text: str) -> ScopeMap:
    """Map every instruction of the compiled module's non-fusion
    computations (those whose instructions run as ops of their own) to its
    scope. Instruction names are unique within a module."""
    comps: dict[str, list[tuple[str, str | None, list[str]]]] = {}
    fused: set[str] = set()
    comp = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m and comp is not None:
            name, rest = m.groups()
            opcode, operands, attrs = _parse(rest)
            if opcode == "fusion":
                fused.update(_CALLS.findall(attrs))
            op = _OP_NAME.search(attrs)
            comps[comp].append((name, scope_of(op.group(1)) if op else None,
                                operands))
            continue
        m = _COMP.match(line)
        if m and line.rstrip().endswith("{"):
            comp = m.group(1)
            comps[comp] = []
    own: dict[str, str | None] = {}
    users: dict[str, list[str]] = collections.defaultdict(list)
    producers: dict[str, list[str]] = {}
    for c, instrs in comps.items():
        if c in fused:
            continue
        for name, scope, operands in instrs:
            own[name] = scope
            producers[name] = operands
            for o in operands:
                users[o].append(name)

    def nearest(name: str, edges) -> str | None:
        seen, queue = {name}, collections.deque(edges.get(name, ()))
        while queue:
            n = queue.popleft()
            if n in seen or n not in own:
                continue
            seen.add(n)
            if own[n]:
                return own[n]
            queue.extend(edges.get(n, ()))
        return None

    scope, inherited = {}, set()
    for name, s in own.items():
        if s is None:
            s = nearest(name, users) or nearest(name, producers)
            if s is not None:
                inherited.add(name)
        scope[name] = s
    return ScopeMap(scope, inherited)


def load_spans(trace_dir: str) -> list[Event]:
    """The program's host spans (``scheduler.*``, ``hdc.*``) in the newest
    trace under ``trace_dir``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(tr.find_xplane(trace_dir))
    return [Event(ev.name, ev.start_ns, ev.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.name.startswith(SPAN_PREFIXES)]


def _intersect(a, b) -> list[tuple[float, float]]:
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost(spans: list[Event]) -> list[tuple[float, float, str]]:
    """Disjoint pieces of the time the spans cover, in order, each named by
    the innermost span over it (spans of one thread nest)."""
    events = sorted([(e.start_ns, 1, -e.end_ns, i) for i, e in enumerate(spans)]
                    + [(e.end_ns, 0, -e.start_ns, i)
                       for i, e in enumerate(spans)])
    out, open_, t = [], [], None
    for when, starts, _, i in events:
        if open_ and when > t:
            out.append((t, when, spans[open_[-1]].name))
        t = when
        if starts:
            open_.append(i)
        else:
            open_.remove(i)
    return out


class ProgramLayers:
    """The program's scopes and spans within the benchmark's window."""

    def __init__(self, red: tr.Reduced, spans: list[Event], module: str,
                 scopes: ScopeMap):
        self.red = red
        self.module = module
        self.scopes = scopes
        self.spans = [e for e in spans
                      if e.end_ns > red.t0 and e.start_ns < red.t1]
        self.runs = len(red.module_runs(module))

    # -- device scopes -------------------------------------------------------

    def serve_ops(self, dev: str) -> list[Event]:
        """The ops of ``dev`` that ran inside a run of the serve module
        (instruction names repeat across modules)."""
        runs = sorted((e.start_ns, e.end_ns)
                      for e in self.red.module_runs(self.module, dev))
        starts = [s for s, _ in runs]
        out = []
        for e in self.red.ops.get(dev, []):
            k = bisect_right(starts, e.start_ns) - 1
            if k >= 0 and e.end_ns <= runs[k][1]:
                out.append(e)
        return out

    def scope_s(self) -> dict:
        """Seconds of serve-module ops per scope, averaged over the chips:
        the four scopes, ``"search_kernel"`` (the ``topk_banked`` ops, also
        counted in ``search``), ``"inherited"`` (ops mapped through a
        neighbour) and None (ops of no scope)."""
        keys = SCOPES + ("search_kernel", "inherited", None)
        tot = dict.fromkeys(keys, 0.0)
        for d in self.red.devices:
            for e in self.serve_ops(d):
                tot[self.scopes.get(e.name)] += e.dur_ns
                if KERNEL in e.name:
                    tot["search_kernel"] += e.dur_ns
                if e.name in self.scopes.inherited:
                    tot["inherited"] += e.dur_ns
        k = max(len(self.red.devices), 1)
        return {s: ns * 1e-9 / k for s, ns in tot.items()}

    def scope_ms_per_run(self, scope: str) -> float | None:
        """Milliseconds a serve-module run in ops under ``scope`` (one of
        `SCOPES`, ``"search_prep"``: ``search`` less the kernel, or a key of
        `scope_s`); None where the trace holds no serve run or no scoped op."""
        s = self.scope_s()
        if not self.runs or not any(s[x] for x in SCOPES):
            return None
        v = s["search"] - s["search_kernel"] if scope == "search_prep" \
            else s[scope]
        return v / self.runs * 1e3

    # -- host spans and device idle time -------------------------------------

    def _idle(self, dev: str) -> list[tuple[float, float]]:
        """The complement, within the window, of the device's busy intervals
        (those `Reduced.busy_s` and ``device_idle_share`` count)."""
        out, prev = [], self.red.t0
        for s, e in self.red._busy(dev):   # sorted, disjoint, in the window
            if s > prev:
                out.append((prev, s))
            prev = e
        if self.red.t1 > prev:
            out.append((prev, self.red.t1))
        return out

    def idle_in_s(self, name: str) -> float | None:
        """Device idle seconds inside spans named ``name`` (their children
        included), averaged over the chips; None without such spans."""
        cover = tr._union((e.start_ns, e.end_ns) for e in self.spans
                          if e.name == name)
        if not cover or not self.red.devices:
            return None
        tot = [sum(e - s for s, e in _intersect(self._idle(d), cover))
               for d in self.red.devices]
        return sum(tot) / len(tot) * 1e-9

    def idle_by_span_s(self) -> dict:
        """Device idle seconds under each program span, counted under the
        innermost span, and under none (key None), averaged over the chips.
        They sum to the window's idle time."""
        pieces = innermost(self.spans)
        by_name = collections.defaultdict(list)
        for s, e, name in pieces:
            by_name[name].append((s, e))
        tot: dict = collections.defaultdict(float)
        for d in self.red.devices:
            idle = self._idle(d)
            left = sum(e - s for s, e in idle)
            for name, cover in by_name.items():
                ov = sum(e - s for s, e in _intersect(idle, cover))
                tot[name] += ov
                left -= ov
            tot[None] += left
        k = max(len(self.red.devices), 1)
        return {n: v * 1e-9 / k for n, v in tot.items()}

    def gap_spans(self, n: int = 10) -> list[list]:
        """The ``n`` longest idle gaps on the first chip, each named by the
        innermost program span at its midpoint (None where none is)."""
        if not self.red.devices:
            return []
        pieces = innermost(self.spans)
        gaps = sorted(self._idle(self.red.devices[0]),
                      key=lambda g: g[0] - g[1])[:n]
        out = []
        for s, e in gaps:
            mid = (s + e) / 2
            name = next((p for a, b, p in pieces if a <= mid < b), None)
            out.append([name, (e - s) * 1e-9])
        return out
