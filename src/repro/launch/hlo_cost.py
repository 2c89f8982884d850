"""Trip-count-aware HLO cost analysis.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body ONCE regardless of
trip count (verified empirically), which would corrupt every roofline term
for scan-over-layers models.  This module parses the post-SPMD HLO text,
walks computations recursively, and multiplies while-loop bodies by their
trip counts:

  flops      — dot (2*result*K), convolution (2*out*kernel*in/group), plus
               1/elem for transcendental elementwise ops
  bytes      — operand + result bytes at fusion granularity (XLA-style)
  collective — operand bytes of all-gather / all-reduce / reduce-scatter /
               all-to-all / collective-permute, × enclosing trips

Validated against cost_analysis on loop-free programs and against
trip×body on scans (tests/test_hlo_cost.py).
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5, "pred": 1, "token": 0,
    "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
# elementwise ops that plausibly cost ~1 flop per output element
_EW_FLOP_OPS = {
    "add", "subtract", "multiply", "divide", "maximum", "minimum", "power",
    "exponential", "tanh", "log", "rsqrt", "sqrt", "negate", "compare",
    "select", "and", "or", "convert", "exponential-minus-one",
}


def xla_cost_analysis(compiled) -> Dict[str, float]:
    """``compiled.cost_analysis()``, empty when XLA reports nothing."""
    return compiled.cost_analysis() or {}


def _shape_list(text: str) -> List[Tuple[str, Tuple[int, ...]]]:
    out = []
    for dt, dims in _SHAPE_RE.findall(text):
        if dt not in _DTYPE_BYTES:
            continue
        shape = tuple(int(x) for x in dims.split(",")) if dims else ()
        out.append((dt, shape))
    return out


def _nbytes(shapes) -> float:
    total = 0.0
    for dt, shape in shapes:
        n = 1
        for d in shape:
            n *= d
        total += n * _DTYPE_BYTES[dt]
    return total


def _nelems(shapes) -> float:
    total = 0
    for _, shape in shapes:
        n = 1
        for d in shape:
            n *= d
        total += n
    return total


@dataclass
class Instr:
    name: str
    op: str
    result_text: str
    rhs: str
    operands: List[str]

    def result_shapes(self):
        return _shape_list(self.result_text)


@dataclass
class Computation:
    name: str
    instrs: List[Instr] = field(default_factory=list)
    by_name: Dict[str, Instr] = field(default_factory=dict)


_COMP_HEAD = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.+\{\s*$")
_TRIP_RE = re.compile(r"known_trip_count[\"':{\s]+n[\"':\s]+(\d+)")
_INSTR = re.compile(
    r"^(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*((?:\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?))\s+([a-z][a-z0-9\-]*)\((.*)$")


def parse_hlo(text: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    entry_name = None
    for raw in text.splitlines():
        line = raw.strip()
        if not line:
            continue
        # Instruction lines always contain " = " (spaces); computation
        # headers never do (but may contain "=" inside /*index=k*/ comments).
        if " = " not in line:
            mh = _COMP_HEAD.match(line)
            if mh:
                cur = Computation(mh.group(1))
                comps[cur.name] = cur
                if line.startswith("ENTRY"):
                    entry_name = cur.name
                continue
        if line.startswith("}"):
            cur = None
            continue
        if cur is None:
            continue
        mi = _INSTR.match(line)
        if not mi:
            continue
        name, result_text, op, rest = mi.groups()
        # operand names: inside the first balanced paren group
        depth, end = 1, None
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    end = i
                    break
        args = rest[:end] if end is not None else rest
        attrs = rest[end + 1:] if end is not None else ""
        # Operand entries are comma-separated and may be typed
        # ("f32[256,256]{1,0} %Arg_0.1" on newer jaxlibs) or bare ("%a");
        # strip bracket/brace groups, then the name is the entry's last token.
        operands = []
        if args.strip():
            clean = re.sub(r"\[[^\]]*\]|\{[^}]*\}", "", args)
            for entry in clean.split(","):
                toks = entry.split()
                if toks:
                    operands.append(toks[-1].lstrip("%"))
        operands = [o for o in operands if o and not o[0].isdigit()]
        instr = Instr(name=name, op=op, result_text=result_text,
                      rhs=args + "|" + attrs, operands=operands)
        cur.instrs.append(instr)
        cur.by_name[name] = instr
    if entry_name:
        comps["__entry__"] = comps[entry_name]
    return comps


def _attr(rhs: str, key: str) -> Optional[str]:
    m = re.search(key + r"=%?([\w.\-]+)", rhs)
    return m.group(1) if m else None


def _attr_braces(rhs: str, key: str) -> List[int]:
    m = re.search(key + r"=\{([0-9, ]*)\}", rhs)
    if not m:
        return []
    body = m.group(1).strip()
    return [int(x) for x in body.split(",")] if body else []


def trip_count(cond: Computation) -> int:
    """Loop bound: the max integer constant in the condition computation."""
    best = 1
    for ins in cond.instrs:
        if ins.op == "constant":
            m = re.search(r"constant\((-?\d+)\)", "constant(" + ins.rhs)
            if not m:
                m = re.search(r"(-?\d+)", ins.rhs)
            if m:
                best = max(best, int(m.group(1)))
    return best


@dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll_bytes: float = 0.0
    coll_breakdown: Dict[str, float] = field(default_factory=dict)

    def __iadd__(self, other: "Cost"):
        self.flops += other.flops
        self.bytes += other.bytes
        self.coll_bytes += other.coll_bytes
        for k, v in other.coll_breakdown.items():
            self.coll_breakdown[k] = self.coll_breakdown.get(k, 0.0) + v
        return self

    def scaled(self, m: float) -> "Cost":
        return Cost(self.flops * m, self.bytes * m, self.coll_bytes * m,
                    {k: v * m for k, v in self.coll_breakdown.items()})


def _operand_shapes(comp: Computation, ins: Instr):
    shapes = []
    for o in ins.operands:
        src = comp.by_name.get(o)
        if src is not None:
            shapes.extend(src.result_shapes())
    return shapes


_SLICE_OPS = ("dynamic-slice", "gather", "dynamic-update-slice")


def _fusion_root_is_dus(callee: Computation) -> bool:
    """True when the fusion computes an in-place slice update (possibly via
    a bitcast/copy root): its result tensor is the full aliased buffer, but
    the actual traffic is the updated region only."""
    roots = [i for i in callee.instrs if i.name and i is callee.instrs[-1]]
    # walk back through bitcast/copy chains from the last instruction
    cur = callee.instrs[-1] if callee.instrs else None
    seen = 0
    while cur is not None and seen < 4:
        if cur.op == "dynamic-update-slice":
            return True
        if cur.op in ("bitcast", "copy", "convert") and cur.operands:
            cur = callee.by_name.get(cur.operands[0])
            seen += 1
            continue
        return False
    return False


def _fusion_operand_bytes(callee: Computation) -> float:
    """Memory traffic of a fusion's inputs, counting parameters that are only
    sliced inside (stacked scan weights / KV buffers) at slice size — the
    HloCostAnalysis convention — instead of full buffer size."""
    total = 0.0
    for p in callee.instrs:
        if p.op != "parameter":
            continue
        uses = [u for u in callee.instrs if p.name in u.operands]
        if uses and all(u.op in _SLICE_OPS for u in uses):
            for u in uses:
                if u.op == "dynamic-update-slice":
                    # read+write of the updated region only
                    upd = callee.by_name.get(u.operands[1]) if len(u.operands) > 1 else None
                    if upd is not None and p.name == u.operands[0]:
                        total += 2 * _nbytes(upd.result_shapes())
                    else:
                        total += _nbytes(p.result_shapes()) if upd is None else _nbytes(upd.result_shapes())
                else:
                    total += 2 * _nbytes(u.result_shapes())
        else:
            total += _nbytes(p.result_shapes())
    return total


def _dot_flops(comp: Computation, ins: Instr) -> float:
    res = ins.result_shapes()
    if not res:
        return 0.0
    out_elems = _nelems(res)
    lhs = comp.by_name.get(ins.operands[0]) if ins.operands else None
    if lhs is None:
        return 2.0 * out_elems  # unknown K
    lshapes = lhs.result_shapes()
    if not lshapes:
        return 2.0 * out_elems
    ldims = lshapes[0][1]
    cdims = _attr_braces(ins.rhs, "lhs_contracting_dims")
    k = 1
    for c in cdims:
        if c < len(ldims):
            k *= ldims[c]
    return 2.0 * out_elems * max(k, 1)


def _conv_flops(comp: Computation, ins: Instr) -> float:
    res = ins.result_shapes()
    out_elems = _nelems(res)
    if len(ins.operands) < 2:
        return 2.0 * out_elems
    ker = comp.by_name.get(ins.operands[1])
    kshapes = ker.result_shapes() if ker else []
    kelems = _nelems(kshapes) if kshapes else 1
    # flops ≈ 2 * out_elems * (kernel_elems / out_features); feature_group
    # handling is safely approximated for depthwise (kernel IO=1).
    m = re.search(r"feature_group_count=(\d+)", ins.rhs)
    groups = int(m.group(1)) if m else 1
    if groups > 1:
        # depthwise-style: each output element sees kernel_elems/groups taps
        # (layout-independent — XLA may transpose the kernel operand)
        return 2.0 * out_elems * kelems / groups
    if kshapes:
        kdims = kshapes[0][1]
        out_feat = max(kdims[0], 1) if kdims else 1
        per_out = kelems / max(out_feat, 1)
        return 2.0 * out_elems * per_out
    return 2.0 * out_elems


def computation_cost(comps: Dict[str, Computation], name: str,
                     memo: Dict[str, Cost], fusion: bool = False) -> Cost:
    if name in memo:
        return memo[name]
    comp = comps[name]
    total = Cost()
    for ins in comp.instrs:
        op = ins.op
        if op == "while":
            body = _attr(ins.rhs, "body")
            cond = _attr(ins.rhs, "condition")
            mt = _TRIP_RE.search(ins.rhs)
            if mt:
                trips = int(mt.group(1))
            else:
                trips = trip_count(comps[cond]) if cond in comps else 1
            if body in comps:
                total += computation_cost(comps, body, memo).scaled(max(trips, 1))
        elif op == "fusion":
            callee = _attr(ins.rhs, "calls")
            if callee in comps:
                sub = computation_cost(comps, callee, memo, fusion=True)
                total.flops += sub.flops
                total.coll_bytes += sub.coll_bytes
                for k, v in sub.coll_breakdown.items():
                    total.coll_breakdown[k] = total.coll_breakdown.get(k, 0) + v
                # bytes at fusion granularity (slice-aware for stacked bufs)
                total.bytes += _fusion_operand_bytes(comps[callee])
                # in-place DUS fusions: result aliases the input buffer —
                # update-region traffic is already counted on the param side
                if not _fusion_root_is_dus(comps[callee]):
                    total.bytes += _nbytes(ins.result_shapes())
            else:
                total.bytes += _nbytes(_operand_shapes(comp, ins))
                total.bytes += _nbytes(ins.result_shapes())
        elif op in ("call", "conditional"):
            callee = _attr(ins.rhs, "to_apply") or _attr(ins.rhs, "branch_computations")
            if callee in comps:
                total += computation_cost(comps, callee, memo)
        elif op == "dot":
            total.flops += _dot_flops(comp, ins)
            total.bytes += _nbytes(_operand_shapes(comp, ins))
            total.bytes += _nbytes(ins.result_shapes())
        elif op == "convolution":
            total.flops += _conv_flops(comp, ins)
            total.bytes += _nbytes(_operand_shapes(comp, ins))
            total.bytes += _nbytes(ins.result_shapes())
        elif any(op == k or op.startswith(k + "-start") or op.startswith(k + ".")
                 for k in _COLLECTIVES):
            kind = next(k for k in _COLLECTIVES
                        if op == k or op.startswith(k + "-start") or op.startswith(k + "."))
            b = _nbytes(_operand_shapes(comp, ins)) or _nbytes(ins.result_shapes())
            total.coll_bytes += b
            total.coll_breakdown[kind] = total.coll_breakdown.get(kind, 0.0) + b
            total.bytes += b + _nbytes(ins.result_shapes())
        elif op in ("parameter", "constant", "get-tuple-element", "tuple",
                    "bitcast", "after-all", "partition-id", "replica-id"):
            continue
        elif op == "dynamic-slice":
            if not fusion:
                total.bytes += 2 * _nbytes(ins.result_shapes())
        elif op == "dynamic-update-slice":
            if not fusion:
                upd = comp.by_name.get(ins.operands[1]) if len(ins.operands) > 1 else None
                total.bytes += 2 * _nbytes(upd.result_shapes() if upd else ins.result_shapes())
        else:
            # standalone elementwise / reduce / copy etc.
            if not fusion:
                total.bytes += _nbytes(_operand_shapes(comp, ins))
                total.bytes += _nbytes(ins.result_shapes())
            if op in _EW_FLOP_OPS or op in ("reduce", "scatter", "gather"):
                total.flops += _nelems(ins.result_shapes())
    memo[name] = total
    return total


def analyze_hlo_text(text: str) -> Cost:
    comps = parse_hlo(text)
    if "__entry__" not in comps:
        raise ValueError("no ENTRY computation found in HLO text")
    memo: Dict[str, Cost] = {}
    entry = comps["__entry__"].name
    return computation_cost(comps, entry, memo)
