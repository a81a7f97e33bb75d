"""Loop-aware HLO cost analysis.

XLA's built-in `compiled.cost_analysis()` counts every while-loop BODY once —
under scan-over-layers + microbatch accumulation that undercounts flops,
bytes, and collective traffic by the product of trip counts (~100-1000x for
these programs).  This module re-derives the three roofline inputs from the
optimized HLO text with loop scaling:

  * computations are parsed into (ops, shapes) blocks,
  * every `while` op contributes multiplier = trip count (the loop-bound
    constant in its condition computation) to its body's subtree,
  * FLOPs: 2*prod(out_dims)*prod(contracting_dims) per dot (MXU convention),
  * HBM bytes: Σ (operands + outputs) over materializing top-level ops —
    fusion-internal ops are excluded (they live in registers/VMEM),
  * collective wire bytes: ring multipliers as in hlo_analysis, scaled.

Validated against closed-form 6·N·D model FLOPs in tests/test_roofline.py.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
}

_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\((.*?)\)\s*->")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_COND_RE = re.compile(r"condition=%?([\w.\-]+)")
_NAME_RE = re.compile(r"%([\w.\-]+)")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([0-9, ]*)\}")

# ops that don't touch HBM (aliases / control / scheduling)
_FREE_OPS = {
    "tuple", "get-tuple-element", "parameter", "constant", "bitcast",
    "after-all", "partition-id", "replica-id", "iota", "while", "conditional",
    "copy-start", "copy-done", "all-gather-done", "all-reduce-done",
    "collective-permute-done", "reduce-scatter-done", "all-to-all-done",
    "opt-barrier", "custom-call",
}

# ops whose operand list includes a large ALIASED buffer that is NOT streamed:
# traffic = k * (bytes of the relevant slice), not operand sizes.
#   dynamic-slice: read slice + write out            -> 2 x out
#   dynamic-update-slice: read+write the update span -> 2 x update (operand 1)
#   gather: read selected rows + write out           -> 2 x out
_SLICED_OPS = {"dynamic-slice", "gather"}


def _extract_operands(rest: str, kind: str) -> List[str]:
    """Operand names of `<shape> kind(<operand list>), attrs...`.

    The operand list is the balanced-paren span right after the op kind.
    HLO text prints operands as bare names (``dot(%a, %b)``) or with inline
    shapes (``dot(f32[128,64]{1,0} %a, ...)``), depending on the XLA
    version — so scan to the matching close paren and pull every %name
    inside, which handles both (attrs like ``calls=%comp`` sit after the
    close paren and are excluded).
    """
    start = rest.find(kind + "(")
    if start < 0:
        return []
    i = start + len(kind)
    depth = 0
    for j in range(i, len(rest)):
        if rest[j] == "(":
            depth += 1
        elif rest[j] == ")":
            depth -= 1
            if depth == 0:
                return _NAME_RE.findall(rest[i:j + 1])
    return _NAME_RE.findall(rest[i:])


def _shape_bytes(shape_str: str) -> float:
    total = 0.0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1.0
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class Op:
    name: str
    kind: str
    out_bytes: float
    out_dims: List[int]
    operands: List[str]
    line: str


@dataclasses.dataclass
class Computation:
    name: str
    ops: List[Op]
    shapes: Dict[str, Tuple[float, List[int]]]  # op name -> (bytes, dims)
    is_fusion_body: bool = False


def parse_module(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in hlo.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if not s:
            continue
        hdr = _COMP_HDR.match(line) if (line.endswith("{") and "->" in line) else None
        if hdr:
            cur = Computation(hdr.group(2), [], {})
            comps[cur.name] = cur
            continue
        if s == "}":
            cur = None
            continue
        if cur is None:
            continue
        m = _OP_RE.match(s)
        if not m:
            continue
        name, rest = m.groups()
        first_shape = _SHAPE_RE.search(rest)
        out_dims: List[int] = []
        if first_shape:
            out_dims = [int(d) for d in first_shape.group(2).split(",") if d.strip()]
        # shape of the value: up to the op kind token
        kind_m = re.search(r"\}\s*([a-z][a-z0-9\-]*)\(", rest) or \
            re.search(r"\]\s*([a-z][a-z0-9\-]*)\(", rest) or \
            re.search(r"\)\s*([a-z][a-z0-9\-]*)\(", rest)
        kind = kind_m.group(1) if kind_m else rest.split("(")[0].split()[-1]
        shape_str = rest.split(kind + "(")[0] if (kind + "(") in rest else rest
        out_bytes = _shape_bytes(shape_str)
        operands = _extract_operands(rest, kind)
        op = Op(name, kind, out_bytes, out_dims, operands, s)
        cur.ops.append(op)
        cur.shapes[name] = (out_bytes, out_dims)
    # mark fusion bodies
    for comp in comps.values():
        for op in comp.ops:
            if op.kind == "fusion":
                cm = _CALLS_RE.search(op.line)
                if cm and cm.group(1) in comps:
                    comps[cm.group(1)].is_fusion_body = True
    return comps


def _trip_count(cond: Computation) -> int:
    best = 1
    for op in cond.ops:
        for c in _CONST_RE.findall(op.line):
            best = max(best, int(c))
    return best


def multipliers(comps: Dict[str, Computation], entry: str) -> Dict[str, float]:
    """Propagate loop trip counts down the call graph."""
    mult: Dict[str, float] = {}

    def visit(name: str, m: float):
        if name not in comps:
            return
        mult[name] = max(mult.get(name, 0.0), m)
        for op in comps[name].ops:
            if op.kind == "while":
                b, c = _BODY_RE.search(op.line), _COND_RE.search(op.line)
                trip = _trip_count(comps[c.group(1)]) if (c and c.group(1) in comps) else 1
                if b:
                    visit(b.group(1), m * trip)
                if c:
                    visit(c.group(1), m * trip)
            elif op.kind in ("fusion", "call", "conditional", "custom-call",
                             "reduce", "sort", "map", "scatter", "select-and-scatter"):
                for cm in _CALLS_RE.finditer(op.line):
                    visit(cm.group(1), m)
    visit(entry, 1.0)
    return mult


def _find_entry(comps: Dict[str, Computation], hlo: str) -> str:
    m = re.search(r"ENTRY\s+%?([\w.\-]+)", hlo)
    if m and m.group(1) in comps:
        return m.group(1)
    return next(iter(comps))


def _dot_flops(op: Op, comp: Computation) -> float:
    out_elems = 1.0
    for d in op.out_dims:
        out_elems *= d
    contract = 1.0
    cm = _CONTRACT_RE.search(op.line)
    if cm and op.operands:
        lhs = comp.shapes.get(op.operands[0])
        if lhs:
            dims = lhs[1]
            for idx in cm.group(1).split(","):
                if idx.strip() and int(idx) < len(dims):
                    contract *= dims[int(idx)]
    return 2.0 * out_elems * contract


def _wire_mult(kind: str, k: int, out_bytes: float) -> float:
    if k <= 1:
        return 0.0
    if kind.startswith("all-reduce"):
        return 2.0 * out_bytes * (k - 1) / k
    if kind.startswith("all-gather"):
        return out_bytes * (k - 1) / k
    if kind.startswith("reduce-scatter"):
        return out_bytes * (k - 1)
    if kind.startswith("all-to-all"):
        return out_bytes * (k - 1) / k
    if kind.startswith("collective-permute"):
        return out_bytes
    return 0.0


_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                     "collective-permute")


@dataclasses.dataclass
class LoopAwareCost:
    flops: float
    hbm_bytes: float
    wire_bytes: float
    n_collectives: float
    by_collective: Dict[str, float]


def analyze(hlo: str) -> LoopAwareCost:
    comps = parse_module(hlo)
    entry = _find_entry(comps, hlo)
    mult = multipliers(comps, entry)

    flops = 0.0
    hbm = 0.0
    wire = 0.0
    n_coll = 0.0
    by_coll: Dict[str, float] = {}
    for comp in comps.values():
        m = mult.get(comp.name, 0.0)
        if m == 0.0:
            continue
        for op in comp.ops:
            if op.kind in ("dot", "convolution"):
                flops += m * _dot_flops(op, comp)
            base = op.kind.replace("-start", "")
            if base in _COLLECTIVE_KINDS and not op.kind.endswith("-done"):
                k = 1
                g = _GROUPS_RE.search(op.line)
                if g:
                    k = max(len([t for t in g.group(1).replace(" ", "").split(",") if t]), 1)
                w = _wire_mult(base, k, op.out_bytes)
                wire += m * w
                n_coll += m
                by_coll[base] = by_coll.get(base, 0.0) + m * w
            if comp.is_fusion_body or op.kind in _FREE_OPS:
                continue
            sliced_fusion = op.kind == "fusion" and (
                "dynamic-slice" in op.name or "gather" in op.name
                or "dynamic_slice" in op.name)
            if op.kind in _SLICED_OPS or sliced_fusion:
                # aliased big operand is NOT streamed: traffic ~ 2 x slice
                hbm += m * 2.0 * op.out_bytes
                continue
            if op.kind == "dynamic-update-slice" or (
                    op.kind == "fusion" and "dynamic-update-slice" in op.name):
                upd = comp.shapes.get(op.operands[1], (op.out_bytes, []))[0] \
                    if len(op.operands) > 1 else op.out_bytes
                hbm += m * 2.0 * min(upd, op.out_bytes)
                continue
            operand_bytes = sum(
                comp.shapes.get(o, (0.0, []))[0] for o in op.operands)
            hbm += m * (op.out_bytes + operand_bytes)
    return LoopAwareCost(flops, hbm, wire, n_coll, by_coll)
