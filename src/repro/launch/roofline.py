"""Roofline-term extraction from compiled dry-run artifacts.

Hardware model (TPU v5e-class, per chip):
  peak bf16 compute : 197 TFLOP/s
  HBM bandwidth     : 819 GB/s
  ICI per link      : ~50 GB/s

Terms (seconds, per device — the compiled module is the per-device SPMD
program, so cost_analysis numbers are already per-chip):
  compute    = HLO_FLOPs / PEAK_FLOPS
  memory     = HLO_bytes / HBM_BW
  collective = collective_bytes / ICI_BW
collective_bytes is parsed from the post-SPMD HLO text (sum of operand
sizes of all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute ops) — it is NOT in cost_analysis.
"""
from __future__ import annotations

import json
import re
from dataclasses import asdict, dataclass, field
from typing import Dict, Optional, Tuple

import numpy as np

PEAK_FLOPS = 197e12
HBM_BW = 819e9
ICI_BW = 50e9

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 0.5, "u4": 0.5, "pred": 0.125,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]+m[0-9]+)?)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    if dtype not in _DTYPE_BYTES:
        return 0.0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


_INSTR_RE = re.compile(r"^(%[\w.\-]+)\s*=\s*(.+)$")
_OPND_RE = re.compile(r"\(([^)]*)\)")
_NAME_RE = re.compile(r"%[\w.\-]+")


def collective_bytes(hlo_text: str) -> Dict[str, float]:
    """Per-device bytes entering each collective kind (operand sizes).

    Post-SPMD HLO references operands by name (``all-reduce(%dot.1)``), so we
    first build a symbol table of every instruction's result bytes, then sum
    operand sizes for each collective (falling back to the collective's own
    result shape when an operand is unknown)."""
    sizes: Dict[str, float] = {}
    coll_lines = []
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("ROOT "):
            stripped = stripped[len("ROOT "):]
        m = _INSTR_RE.match(stripped)
        if not m:
            continue
        name, rhs = m.group(1), m.group(2)
        # Result shapes: all dtype[dims] tokens before the op name's paren.
        head = rhs.split("(", 1)[0]
        rbytes = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head))
        sizes[name] = rbytes
        opm = re.match(r"^\s*(?:\([^)]*\)|\S+)\s+([a-z\-]+[a-z0-9.\-]*)\(", rhs)
        if not opm:
            continue
        op = opm.group(1)
        kind = next((k for k in _COLLECTIVES
                     if op == k or op.startswith(k + ".") or
                     op.startswith(k + "-start")), None)
        if kind is not None:
            operands = _OPND_RE.search(rhs[opm.end() - 1:])
            names = _NAME_RE.findall(operands.group(1)) if operands else []
            coll_lines.append((kind, names, rbytes))

    out: Dict[str, float] = {k: 0.0 for k in _COLLECTIVES}
    seen_starts = set()
    for kind, names, rbytes in coll_lines:
        opnd = sum(sizes.get(n, 0.0) for n in names)
        out[kind] += opnd if opnd > 0 else rbytes
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float           # per device
    hlo_bytes: float           # per device
    coll_bytes: float          # per device
    coll_breakdown: Dict[str, float]
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    model_flops: float         # global useful FLOPs (6*N*D)
    useful_ratio: float        # model_flops / (hlo_flops * chips)
    mem_per_device: Optional[float] = None  # bytes (args+outputs+temps)
    fits_hbm: Optional[bool] = None
    note: str = ""

    def terms(self) -> Dict[str, float]:
        return {"compute": self.t_compute, "memory": self.t_memory,
                "collective": self.t_collective}

    def to_json(self) -> str:
        return json.dumps(asdict(self))


def _cost_get(cost: Dict, key: str) -> float:
    if key in cost:
        return float(cost[key])
    total = 0.0
    for k, v in cost.items():
        if k.startswith(key):
            total += float(v)
    return total


def analyze(
    compiled,
    lowered_text: str,
    *,
    arch: str,
    shape: str,
    mesh_name: str,
    chips: int,
    model_flops: float,
    hbm_per_chip: float = 16e9,  # v5e
) -> RooflineReport:
    # Trip-count-aware HLO walk (XLA's cost_analysis counts while bodies
    # once — see launch/hlo_cost.py).  Falls back to cost_analysis if the
    # text walk fails.
    try:
        from repro.launch.hlo_cost import analyze_hlo_text
        walked = analyze_hlo_text(lowered_text)
        flops = walked.flops
        bytes_accessed = walked.bytes
        coll = dict(walked.coll_breakdown)
        for k in _COLLECTIVES:
            coll.setdefault(k, 0.0)
        coll["total"] = walked.coll_bytes
    except Exception:
        cost = compiled.cost_analysis()
        flops = _cost_get(cost, "flops")
        bytes_accessed = _cost_get(cost, "bytes accessed")
        coll = collective_bytes(lowered_text)

    mem = None
    fits = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = float(ma.argument_size_in_bytes + ma.output_size_in_bytes
                        + ma.temp_size_in_bytes)
            fits = mem <= hbm_per_chip
    except Exception:
        pass

    t_c = flops / PEAK_FLOPS
    t_m = bytes_accessed / HBM_BW
    t_x = coll["total"] / ICI_BW
    dominant = max((("compute", t_c), ("memory", t_m), ("collective", t_x)),
                   key=lambda kv: kv[1])[0]
    useful = model_flops / max(flops * chips, 1.0)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=flops, hlo_bytes=bytes_accessed, coll_bytes=coll["total"],
        coll_breakdown={k: v for k, v in coll.items() if k != "total"},
        t_compute=t_c, t_memory=t_m, t_collective=t_x, dominant=dominant,
        model_flops=model_flops, useful_ratio=useful,
        mem_per_device=mem, fits_hbm=fits,
    )


def model_flops_for(cfg, shape_kind: str, seq: int, batch: int) -> float:
    """MODEL_FLOPS = 6*N*D (dense) / 6*N_active*D (MoE); decode: D = batch
    new tokens; train adds the backward 2x (6ND already includes fwd+bwd:
    2ND fwd + 4ND bwd)."""
    n_active = cfg.active_param_count()
    if shape_kind == "train":
        d_tokens = seq * batch
        return 6.0 * n_active * d_tokens
    if shape_kind == "prefill":
        d_tokens = seq * batch
        return 2.0 * n_active * d_tokens
    # decode: one token per sequence
    return 2.0 * n_active * batch
