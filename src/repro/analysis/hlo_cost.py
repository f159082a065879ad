"""Trip-count-aware cost analysis of optimized HLO text.

XLA's cost analysis (see `xla_reported_cost`) counts a while-loop body ONCE, so any
`lax.scan`-based stack (every model here: layer stacks, flash-attention block
loops, loss chunks, microbatches) is undercounted by its trip count. This module
re-derives costs from `compiled.as_text()`:

1. parse the module into computations, ops and a per-computation symbol table
   (operands in optimized HLO are %name references, not inline shapes);
2. read each while loop's trip count from its backend_config
   ``known_trip_count`` (fallback: the s32 constant in its condition);
3. propagate execution multipliers through the call graph — while bodies
   multiply by the trip count, calls/fusions/conditionals inherit;
4. accumulate:
   * FLOPs        — dot/convolution ops: 2 * prod(result) * prod(contracting),
                    including dots inside fusion bodies;
   * HBM traffic  — operand + result bytes of top-level ops (fusion parameters
     and results are the materialized buffers; fusion-internal ops are free) —
     the same "sum of buffers" model XLA's cost analysis uses;
   * collectives  — operand bytes of all-gather / all-reduce / reduce-scatter /
                    all-to-all / collective-permute (+ async -start forms),
                    split by type.

Validated in tests/test_analysis.py against XLA's own numbers on scan-free
programs and against analytic FLOPs on scanned/shard_mapped ones.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict

from repro.compat import normalized_cost_analysis

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1, "s16": 2, "u16": 2,
    "bf16": 2, "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "token": 0, "opaque": 0, "f8e4m3fn": 1,
    "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9a-z]*|pred|token)\[([0-9,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w\.\-]+)\s*=\s*(.*)$")
_TRIP_RE = re.compile(r"known_trip_count.*?n\\?\":\\?\"(\d+)")
_CONST_S32 = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_CONTRACT = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERAND_NAME = re.compile(r"%([\w\.\-]+)")

COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all", "collective-permute",
)


def _dims(dims_str: str) -> list[int]:
    return [int(d) for d in dims_str.split(",") if d]


def _shape_bytes_from_spec(spec: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(spec):
        n = 1
        for d in _dims(dims):
            n *= d
        total += n * _DTYPE_BYTES.get(dt, 4)
    return total


@dataclasses.dataclass
class Op:
    name: str
    opcode: str
    shape_spec: str    # result type text (may be a tuple)
    args: list[str]    # operand %names
    attrs: str         # trailing attribute text
    operand_text: str = ""  # raw text inside the call parens
    is_root: bool = False


def _parse_op(body: str) -> Op | None:
    """body: text after '%name = '."""
    body = body.strip()
    # result shape spec: tuple '(...)' or single token
    if body.startswith("("):
        depth = 0
        for i, ch in enumerate(body):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        spec, rest = body[: i + 1], body[i + 1 :]
    else:
        sp = body.find(" ")
        if sp < 0:
            return None
        spec, rest = body[:sp], body[sp:]
    m = re.match(r"\s*([\w\-]+)\(", rest)
    if not m:
        return None
    opcode = m.group(1)
    # operand list: balanced paren group after opcode
    start = m.end() - 1
    depth = 0
    for i in range(start, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            break
    operand_text = rest[start + 1 : i]
    attrs = rest[i + 1 :]
    args = _OPERAND_NAME.findall(operand_text)
    return Op("", opcode, spec, args, attrs, operand_text)


def parse_module(text: str):
    """Returns ({comp: {'ops': [Op], 'table': {name: shape_spec}}}, entry)."""
    comps: dict[str, dict] = {}
    entry = None
    cur = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            if stripped.endswith("{") and "->" in stripped and (
                stripped.startswith("%") or stripped.startswith("ENTRY")
            ):
                name = stripped.split()[1 if stripped.startswith("ENTRY") else 0]
                name = name.split("(")[0].lstrip("%").rstrip()
                comps[name] = {"ops": [], "table": {}}
                cur = comps[name]
                if stripped.startswith("ENTRY"):
                    entry = name
            continue
        if stripped == "}":
            cur = None
            continue
        m = _OP_RE.match(line)
        if not m:
            continue
        op = _parse_op(m.group(2))
        if op is None:
            continue
        op.name = m.group(1)
        op.is_root = line.lstrip().startswith("ROOT")
        cur["ops"].append(op)
        cur["table"][op.name] = op.shape_spec
    return comps, entry


def _trip_count(op: Op, comps) -> int:
    m = _TRIP_RE.search(op.attrs)
    if m:
        return int(m.group(1))
    cm = re.search(r"condition=%([\w\.\-]+)", op.attrs)
    if cm and cm.group(1) in comps:
        best = 1
        for o in comps[cm.group(1)]["ops"]:
            for c in _CONST_S32.findall(o.shape_spec + o.attrs):
                best = max(best, int(c))
        return best
    return 1


_CALL_ATTR = re.compile(r"(?:body|calls|to_apply|branch_computations=\{[^}]*)=?%([\w\.\-]+)")


def _called(op: Op) -> list[tuple[str, str]]:
    """[(kind, computation)] referenced by this op."""
    out = []
    for attr, kind in (("body", "while_body"), ("condition", "while_cond"),
                       ("calls", "fusion"), ("to_apply", "apply")):
        for m in re.finditer(attr + r"=%([\w\.\-]+)", op.attrs):
            out.append((kind, m.group(1)))
    bm = re.search(r"branch_computations=\{([^}]*)\}", op.attrs)
    if bm:
        for c in bm.group(1).split(","):
            out.append(("branch", c.strip().lstrip("%")))
    return out


@dataclasses.dataclass
class HloCost:
    flops: float
    hbm_bytes: float
    collective: dict
    raw_flops: float = 0.0
    contributions: list | None = None   # [(bytes, comp, opcode, op_name)] when detail=True

    @property
    def coll_total(self) -> float:
        return float(self.collective.get("total", 0.0))


def analyze_compiled(compiled, detail: bool = False) -> HloCost:
    """Trip-count-aware analysis straight from a ``jax.stages.Compiled``."""
    return analyze(compiled.as_text(), detail=detail)


def xla_reported_cost(compiled) -> dict:
    """XLA's own cost_analysis as a flat dict.

    These are the *raw* numbers (scan bodies counted once — see module
    docstring); ``analyze_compiled`` is the trip-count-corrected view.
    """
    return normalized_cost_analysis(compiled)


def analyze(text: str, detail: bool = False) -> HloCost:
    comps, entry = parse_module(text)
    if entry is None:
        return HloCost(0.0, 0.0, {"total": 0, "count": 0})
    mult: dict[str, float] = defaultdict(float)
    no_bytes: set[str] = set()  # fusion/apply bodies: internals are not HBM traffic
    mult[entry] = 1.0
    stack = [entry]
    visited = set()
    while stack:
        name = stack.pop()
        if name in visited or name not in comps:
            continue
        visited.add(name)
        m = mult[name]
        for op in comps[name]["ops"]:
            trip = _trip_count(op, comps) if op.opcode == "while" else 1
            for kind, child in _called(op):
                f = trip if kind in ("while_body", "while_cond") else 1.0
                new = m * f
                if kind in ("fusion", "apply"):
                    no_bytes.add(child)
                if new > mult[child]:
                    mult[child] = new
                    visited.discard(child)
                stack.append(child)

    # Effective per-parameter traffic of fusion bodies: a parameter consumed only
    # by dynamic-slice/slice/gather reads just the sliced region (scan bodies
    # slice the [L, ...] stacked weights); anything else reads the full buffer.
    def _fusion_param_bytes(comp_name: str) -> dict[int, float | None]:
        out: dict[int, float | None] = {}
        comp = comps.get(comp_name)
        if comp is None:
            return out
        param_idx: dict[str, int] = {}
        for op in comp["ops"]:
            if op.opcode == "parameter" and op.operand_text.strip().isdigit():
                param_idx[op.name] = int(op.operand_text.strip())
        sliced: dict[int, float] = defaultdict(float)
        full: set[int] = set()
        for op in comp["ops"]:
            for ai, a in enumerate(op.args):
                if a not in param_idx:
                    continue
                i = param_idx[a]
                if op.opcode in ("dynamic-slice", "slice", "gather") and ai == 0:
                    sliced[i] += _shape_bytes_from_spec(op.shape_spec)
                elif op.opcode in ("dynamic-update-slice",) and ai == 0:
                    upd = _shape_bytes_from_spec(comp["table"].get(op.args[1], "")) if len(op.args) > 1 else 0
                    sliced[i] += upd
                elif op.opcode == "parameter":
                    continue
                else:
                    full.add(i)
        for i in sliced:
            if i not in full:
                out[i] = sliced[i]
        return out

    fusion_eff: dict[str, dict[int, float | None]] = {}

    def _fusion_result_bytes(comp_name: str, default: float) -> float:
        comp = comps.get(comp_name)
        if comp is None:
            return default
        byname = {o.name: o for o in comp["ops"]}
        root = next((o for o in comp["ops"] if o.is_root), None)
        if root is None:
            return default

        def resolve(op):
            seen = 0
            while op is not None and op.opcode in ("convert", "bitcast", "copy") and op.args and seen < 8:
                op = byname.get(op.args[0])
                seen += 1
            return op

        roots = [root]
        if root.opcode == "tuple":
            roots = [byname.get(a) for a in root.args]
        total = 0.0
        for r in roots:
            r = resolve(r)
            if r is None:
                return default
            if r.opcode == "dynamic-update-slice" and len(r.args) > 1:
                upd = byname.get(r.args[1])
                total += _shape_bytes_from_spec(
                    comp["table"].get(r.args[1], upd.shape_spec if upd else "")
                )
            else:
                total += _shape_bytes_from_spec(r.shape_spec)
        return min(total, default)

    fusion_res: dict[str, float] = {}

    flops = 0.0
    raw = 0.0
    hbm = 0.0
    coll: dict = defaultdict(float)
    ncoll = 0
    contributions: list = []
    skip_bytes = {
        "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
        "while", "after-all", "iota", "conditional", "call", "partition-id",
        "replica-id",
    }
    for name, comp in comps.items():
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        table = comp["table"]
        in_fused = name in no_bytes
        for op in comps[name]["ops"]:
            if op.opcode in ("dot", "convolution"):
                res = _shape_bytes_from_spec(op.shape_spec)
                res_elems = 0
                sm = _SHAPE_RE.search(op.shape_spec)
                if sm:
                    res_elems = 1
                    for d in _dims(sm.group(2)):
                        res_elems *= d
                k = 1
                cm = _CONTRACT.search(op.attrs)
                if cm and op.args:
                    lhs_spec = table.get(op.args[0], "")
                    lm = _SHAPE_RE.search(lhs_spec)
                    if lm:
                        dims = _dims(lm.group(2))
                        for ci in cm.group(1).split(","):
                            if ci and int(ci) < len(dims):
                                k *= dims[int(ci)]
                f = 2.0 * res_elems * k
                flops += m * f
                raw += f
            if in_fused:
                continue
            base = next((c for c in COLLECTIVES if op.opcode.startswith(c)), None)
            if base and not op.opcode.endswith("-done"):
                # operand + result bytes: an all-reduce moves ~2N per device, an
                # all-gather receives the full result (operand alone undercounts
                # by the gather factor), reduce-scatter sends the full operand.
                # One consistent send+receive model across all five collectives.
                nb = sum(_shape_bytes_from_spec(table.get(a, "")) for a in op.args)
                nb += _shape_bytes_from_spec(op.shape_spec)
                coll[base] += m * nb
                ncoll += 1
            if op.opcode in skip_bytes or op.opcode.endswith("-done"):
                continue
            if op.opcode in ("dynamic-slice", "slice", "gather", "broadcast", "reshape"):
                # reads only the sliced/gathered region ~= result bytes
                nb = 2 * _shape_bytes_from_spec(op.shape_spec)
            elif op.opcode in ("dynamic-update-slice", "scatter"):
                # reads + writes only the updated region (in-place inside loops)
                upd = (
                    _shape_bytes_from_spec(table.get(op.args[1], ""))
                    if len(op.args) > 1 else 0
                )
                nb = 2 * upd
            elif op.opcode == "fusion":
                cm = re.search(r"calls=%([\w\.\-]+)", op.attrs)
                eff = {}
                res_bytes = _shape_bytes_from_spec(op.shape_spec)
                if cm:
                    cname = cm.group(1)
                    if cname not in fusion_eff:
                        fusion_eff[cname] = _fusion_param_bytes(cname)
                        fusion_res[cname] = _fusion_result_bytes(cname, res_bytes)
                    eff = fusion_eff[cname]
                    res_bytes = fusion_res[cname]
                nb = res_bytes
                for i, a in enumerate(op.args):
                    e = eff.get(i)
                    nb += e if e is not None else _shape_bytes_from_spec(table.get(a, ""))
            else:
                nb = _shape_bytes_from_spec(op.shape_spec) + sum(
                    _shape_bytes_from_spec(table.get(a, "")) for a in op.args
                )
            hbm += m * nb
            if detail and m * nb > 0:
                contributions.append((m * nb, name, op.opcode, op.name))
    coll["total"] = sum(v for k, v in coll.items() if k != "total")
    coll["count"] = ncoll
    if detail:
        contributions.sort(key=lambda t: -t[0])
    return HloCost(flops=flops, hbm_bytes=hbm, collective=dict(coll), raw_flops=raw,
                   contributions=contributions if detail else None)
