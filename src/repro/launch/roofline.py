"""Roofline-term extraction from compiled dry-run artifacts.

Terms (seconds, per device == per chip; cost_analysis of an SPMD executable
reports the per-device program):

  compute    = HLO_FLOPs / PEAK_FLOPS
  memory     = HLO_bytes / HBM_BW
  collective = wire_bytes / LINK_BW

wire_bytes is parsed from the post-SPMD HLO: for each collective op we take
its result (and group size) and apply ring-transfer accounting:

  all-gather        result * (g-1)/g
  all-reduce        2 * result * (g-1)/g
  reduce-scatter    result * (g-1)          (operand = result * g)
  all-to-all        result * (g-1)/g
  collective-permute  result

Hardware model: TPU v5e — 197 TFLOP/s bf16, 819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

import numpy as np

PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
LINK_BW = 50e9  # bytes/s / ICI link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "s4": 1, "u4": 1,
}

_COLL_RE = re.compile(
    r"=\s*(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\("
)
_SHAPE_RE = re.compile(r"(\w+?)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def _tensor_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


@dataclasses.dataclass
class CollectiveStats:
    op: str
    count: int
    result_bytes: int
    wire_bytes: float


def parse_collectives(hlo_text: str) -> List[CollectiveStats]:
    """Aggregate collective ops in (post-SPMD) HLO text."""
    agg: Dict[str, CollectiveStats] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        tuple_types, single_type, op = m.groups()
        rb = _tensor_bytes(tuple_types if tuple_types is not None else single_type)
        if "-done(" in line:  # async pair: count only the -start
            continue
        g = _group_size(line)
        if op == "all-gather":
            wire = rb * (g - 1) / max(g, 1)
        elif op == "all-reduce":
            wire = 2 * rb * (g - 1) / max(g, 1)
        elif op == "reduce-scatter":
            wire = rb * (g - 1)
        elif op == "all-to-all":
            wire = rb * (g - 1) / max(g, 1)
        else:  # collective-permute
            wire = rb
        key = op
        if key not in agg:
            agg[key] = CollectiveStats(op, 0, 0, 0.0)
        agg[key].count += 1
        agg[key].result_bytes += rb
        agg[key].wire_bytes += wire
    return list(agg.values())


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    m = _GROUPS_RE.search(line)
    if m:
        return len([x for x in m.group(1).split(",") if x.strip() != ""])
    return 2


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    wire_bytes_per_device: float,
) -> Dict[str, float]:
    compute = flops_per_device / PEAK_FLOPS
    memory = bytes_per_device / HBM_BW
    collective = wire_bytes_per_device / LINK_BW
    terms = {"compute_s": compute, "memory_s": memory, "collective_s": collective}
    terms["dominant"] = max(
        ("compute_s", "memory_s", "collective_s"), key=lambda k: terms[k]
    )
    terms["bound_s"] = max(compute, memory, collective)
    terms["roofline_fraction"] = compute / max(terms["bound_s"], 1e-30)
    return terms


def analyze_compiled(compiled, lowered_text: Optional[str] = None) -> Dict[str, object]:
    """Full per-cell record from a compiled executable.

    Primary terms come from ``hlo_analyzer`` (while-trip-count-exact,
    gather/scatter touched-rows byte model); XLA's own ``cost_analysis`` is
    kept under ``xla_raw`` as a diagnostic (it counts loop bodies once and
    charges gathers the full operand — see the analyzer docstring).
    """
    from repro.launch import hlo_analyzer

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    text = lowered_text if lowered_text is not None else compiled.as_text()
    c = hlo_analyzer.analyze_hlo(text)
    flops, bytes_, wire = float(c.flops), float(c.bytes), float(c.wire_bytes)
    rec = {
        "flops_per_device": flops,
        "bytes_per_device": bytes_,
        "wire_bytes_per_device": wire,
        "collectives": {k: {"wire_bytes": v} for k, v in sorted(c.coll.items())},
        "xla_raw": {
            "flops": float(cost.get("flops", 0.0)),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0)),
        },
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.output_size_in_bytes
            + mem.temp_size_in_bytes
            - mem.alias_size_in_bytes,
            "host_argument_bytes": mem.host_argument_size_in_bytes,
            "host_temp_bytes": mem.host_temp_size_in_bytes,
        },
        **roofline_terms(flops, bytes_, wire),
    }
    return rec
