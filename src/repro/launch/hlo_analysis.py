"""Post-SPMD HLO analysis: collective-traffic extraction and roofline
terms.

``compiled.as_text()`` is the per-device program after GSPMD
partitioning, so operand shapes are per-device; summing operand bytes of
every collective op gives per-chip collective bytes (the ICI roofline
numerator).  cost_analysis() provides FLOPs and HBM bytes.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.launch.mesh import peaks

# The dry-run's production mesh is v5e (launch/mesh.py).
_V5E = peaks("TPU v5 lite")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %x = bf16[16,512]{1,0} all-gather(%y), replica_groups=...
_OP_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^a-z]*\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_TUPLE_RE = re.compile(
    r"=\s*\(([^)]*)\)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind output bytes (per device) summed over the
    program.  ``-start`` variants (async) are counted once; ``-done``
    ops carry no shape payload of their own."""
    out: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        if "-done(" in line:
            continue
        m = _OP_RE.search(line)
        if m:
            dtype, dims, kind = m.groups()
            out[kind] += _shape_bytes(dtype, dims)
            continue
        m = _TUPLE_RE.search(line)
        if m:
            shapes, kind = m.groups()
            for sm in _SHAPE_RE.finditer(shapes):
                out[kind] += _shape_bytes(*sm.groups())
    return out


@dataclasses.dataclass
class Roofline:
    flops: float               # per-device HLO flops
    hbm_bytes: float           # per-device bytes accessed
    coll_bytes: float          # per-device collective bytes
    coll_breakdown: Dict[str, int]
    peak_flops: float = _V5E["bf16_flops"]
    hbm_bw: float = _V5E["hbm_bw"]
    # ~4 usable links per chip on a 2-D torus
    ici_bw: float = _V5E["ici_link_bw"] * 4

    @property
    def compute_s(self) -> float:
        return self.flops / self.peak_flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes / self.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / self.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    def to_dict(self) -> Dict:
        return {
            "flops": self.flops, "hbm_bytes": self.hbm_bytes,
            "coll_bytes": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "dominant": self.dominant,
        }


def extrapolate(c1: Roofline, c2: Roofline, groups: int) -> Roofline:
    """Unroll-delta extrapolation.

    XLA's cost_analysis (and the HLO text) count a while-loop body ONCE
    regardless of trip count.  Lowering with scan unroll=1 gives
    C1 = outside + body; unroll=2 gives C2 = outside + 2*body.  The true
    program cost is outside + groups*body = C1 + (groups-1)*(C2-C1).
    """
    def ex(a: float, b: float) -> float:
        layer = max(0.0, b - a)
        return a + (groups - 1) * layer

    breakdown = {k: int(ex(c1.coll_breakdown.get(k, 0),
                           c2.coll_breakdown.get(k, 0)))
                 for k in set(c1.coll_breakdown) | set(c2.coll_breakdown)}
    return Roofline(
        flops=ex(c1.flops, c2.flops),
        hbm_bytes=ex(c1.hbm_bytes, c2.hbm_bytes),
        coll_bytes=ex(c1.coll_bytes, c2.coll_bytes),
        coll_breakdown=breakdown)


def analyze(compiled, lowered=None) -> Roofline:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    cost = cost or {}
    flops = float(cost.get("flops", 0.0))
    bytes_ = float(cost.get("bytes accessed", 0.0))
    text = compiled.as_text()
    coll = collective_bytes(text)
    return Roofline(flops=flops, hbm_bytes=bytes_,
                    coll_bytes=float(sum(coll.values())),
                    coll_breakdown=coll)
