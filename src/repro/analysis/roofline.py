"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), per the assignment:

    compute    = HLO_FLOPs      / (chips * 197e12  FLOP/s bf16)
    memory     = HLO_bytes      / (chips * 819e9   B/s HBM)
    collective = coll_bytes     / (chips * 50e9    B/s/link ICI)

HLO_FLOPs / HLO_bytes come from XLA's cost analysis, read through
compat.normalized_cost_analysis (a flat dict). Collective bytes are
NOT in cost_analysis: `collective_bytes` parses the optimized HLO text and sums
*operand* bytes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op (per-type breakdown kept for diagnosis).

Caveat recorded in EXPERIMENTS.md: XLA's cost analysis counts a while-loop body
once, so FLOPs of `lax.scan`d layer stacks are scaled by the trip count here
(we re-multiply using the scan metadata captured at lowering time is NOT
possible post-hoc; instead the dry-run lowers with scans unrolled=1 and we scale
by n_layers analytically via MODEL_FLOPS, reporting both raw and scaled).
"""
from __future__ import annotations

import dataclasses
import math
import re

from repro.compat import normalized_cost_analysis

HW = {"flops": 197e12, "hbm": 819e9, "link": 50e9}

_COLL_RE = re.compile(
    r"\b(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\s*\("
)
_SHAPE_RE = re.compile(r"\b([a-z]+\d+|pred)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> dict:
    """Sum operand bytes of collective ops in optimized HLO text.

    Returns {op_type: bytes, ..., 'total': bytes, 'count': n}. Counts each
    start/done pair once (the -start op carries the operands).
    """
    out: dict = {}
    count = 0
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[-1][:60]:
            continue
        op = m.group(1)
        # operands: shapes appearing inside the call parens
        paren = line[m.end() - 1 :]
        shapes = _SHAPE_RE.findall(paren)
        nbytes = sum(_shape_bytes(dt, dims) for dt, dims in shapes)
        if nbytes == 0:
            continue
        out[op] = out.get(op, 0) + nbytes
        count += 1
    out["total"] = sum(v for k, v in out.items() if k != "total")
    out["count"] = count
    return out


@dataclasses.dataclass(frozen=True)
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)


def roofline_terms(flops: float, bytes_accessed: float, coll_bytes: float, chips: int) -> Roofline:
    return Roofline(
        compute_s=flops / (chips * HW["flops"]),
        memory_s=bytes_accessed / (chips * HW["hbm"]),
        collective_s=coll_bytes / (chips * HW["link"]),
    )


def roofline_from_compiled(compiled, chips: int) -> Roofline:
    """Roofline terms straight from a compiled program, using XLA's own
    (raw, scan-body-counted-once) cost numbers plus the HLO-text collective
    scan. For trip-count-corrected inputs use hlo_cost.analyze_compiled and
    feed roofline_terms directly."""
    cost = normalized_cost_analysis(compiled)
    coll = collective_bytes(compiled.as_text())
    return roofline_terms(
        float(cost.get("flops", 0.0) or 0.0),
        float(cost.get("bytes accessed", 0.0) or 0.0),
        float(coll["total"]),
        chips=chips,
    )


# ---------------------------------------------------------------------------
# MODEL_FLOPS (the "useful FLOPs" yardstick)
# ---------------------------------------------------------------------------

def active_params(cfg, total_params: int) -> int:
    """Parameters touched per token (MoE: routed top-k + shared only)."""
    if cfg.moe is None:
        return total_params
    m = cfg.moe
    per_expert = 3 * cfg.d_model * m.d_expert
    inactive = cfg.n_layers * (m.n_experts - m.top_k) * per_expert
    return total_params - inactive


def model_flops(cfg, cell, total_params: int) -> float:
    """6·N·D (train), 2·N_active·D (prefill), 2·N_active·B (decode)."""
    n_act = active_params(cfg, total_params)
    if cell.kind == "train":
        return 6.0 * n_act * cell.batch * cell.seq  # N_active == N for dense
    if cell.kind == "prefill":
        return 2.0 * n_act * cell.batch * cell.seq
    return 2.0 * n_act * cell.batch  # decode: one token per sequence
