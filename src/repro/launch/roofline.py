"""Roofline extraction from compiled dry-run artifacts.

Three terms per (arch, shape, mesh), all in seconds (DESIGN.md §5 /
EXPERIMENTS.md §Roofline):

    compute    = HLO_FLOPs_per_device / peak_FLOPs_per_chip
    memory     = HLO_bytes_per_device / HBM_bandwidth
    collective = wire_bytes_per_device / ICI_link_bandwidth

``cost_analysis`` of the partitioned module is already per-device.
Collective bytes are NOT in cost_analysis: we parse the optimized HLO
(``compiled.as_text()``) and charge each collective its ring-algorithm wire
traffic (group size from ``replica_groups``):

    all-gather        : out_bytes * (n-1)/n
    reduce-scatter    : out_bytes * (n-1)          (out is the shard)
    all-reduce        : 2 * bytes * (n-1)/n        (RS + AG)
    all-to-all        : bytes * (n-1)/n
    collective-permute: bytes

Hardware model (TPU v5e class, per assignment): 197 TFLOP/s bf16 per chip,
819 GB/s HBM, ~50 GB/s/link ICI.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

__all__ = ["HW", "collective_bytes", "roofline_terms", "summarize_cell"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float = 197e12      # bf16 / chip
    hbm_bw: float = 819e9           # B/s
    ici_bw: float = 50e9            # B/s per link (assignment constant)


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_RE = re.compile(
    r"(?P<dtype>\w+)\[(?P<shape>[\d,]*)\][^=]*=\s*(?P<op>all-reduce|all-gather|"
    r"reduce-scatter|all-to-all|collective-permute)(?:-start)?\("
)
_TUPLE_COLL_RE = re.compile(
    r"=\s*\((?P<tuple>[^)]*)\)\s*(?P<op>all-reduce|all-gather|reduce-scatter|"
    r"all-to-all|collective-permute)(?:-start)?\("
)
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=\[\d+\]")


def _shape_bytes(dtype: str, shape: str) -> float:
    el = _DTYPE_BYTES.get(dtype, 4)
    n = 1
    if shape:
        for d in shape.split(","):
            n *= int(d)
    return float(el * n)


def _group_size(line: str, world: int) -> int:
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    m = _GROUPS_IOTA_RE.search(line)
    if m:
        return int(m.group(2))
    return world


def _wire_factor(op: str, n: int) -> float:
    if n <= 1:
        return 0.0
    if op == "all-reduce":
        return 2.0 * (n - 1) / n
    if op == "all-gather":
        return (n - 1) / n
    if op == "reduce-scatter":
        return float(n - 1)
    if op == "all-to-all":
        return (n - 1) / n
    return 1.0  # collective-permute


def collective_bytes(hlo_text: str, world: int) -> dict[str, Any]:
    """Per-device wire bytes by collective op, from optimized HLO text."""
    per_op: dict[str, dict[str, float]] = {}
    for line in hlo_text.splitlines():
        if "replica_groups" not in line:
            continue
        m = _COLL_RE.search(line)
        entries = []
        if m:
            entries.append((m.group("op"), _shape_bytes(m.group("dtype"), m.group("shape"))))
        else:
            mt = _TUPLE_COLL_RE.search(line)
            if mt:
                tup = mt.group("tuple")
                total = 0.0
                for dt, shp in re.findall(r"(\w+)\[([\d,]*)\]", tup):
                    total += _shape_bytes(dt, shp)
                # tuple of (operand..., result...): charge result half
                entries.append((mt.group("op"), total / 2.0))
        for op, bytes_ in entries:
            n = _group_size(line, world)
            wire = bytes_ * _wire_factor(op, n)
            d = per_op.setdefault(op, {"count": 0, "bytes": 0.0, "wire_bytes": 0.0})
            d["count"] += 1
            d["bytes"] += bytes_
            d["wire_bytes"] += wire
    total_wire = sum(d["wire_bytes"] for d in per_op.values())
    return {"per_op": per_op, "total_wire_bytes": total_wire}


def roofline_terms(
    flops: float, bytes_accessed: float, wire_bytes: float, hw: HW = HW()
) -> dict[str, float]:
    terms = {
        "compute_s": flops / hw.peak_flops,
        "memory_s": bytes_accessed / hw.hbm_bw,
        "collective_s": wire_bytes / hw.ici_bw,
    }
    terms["dominant"] = max(terms, key=lambda k: terms[k] if k.endswith("_s") else -1)
    terms["bound_s"] = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    return terms


def summarize_cell(
    compiled, cfg, shape, world: int, hw: HW = HW()
) -> dict[str, Any]:
    """Full §Roofline record for one compiled cell.

    FLOPs/bytes/collectives come from the while-aware HLO analyzer
    (:mod:`repro.launch.hlo_analysis`): XLA's ``cost_analysis`` counts each
    scan body once, which under-reports a scanned-layers transformer by the
    trip count — both raw views are recorded.
    """
    from repro.launch.hlo_analysis import analyze_hlo

    cost = compiled.cost_analysis()
    mem = compiled.memory_analysis()
    hlo = analyze_hlo(compiled.as_text(), world)
    flops = float(hlo.flops)
    bytes_accessed = float(hlo.bytes_proxy)
    colls = {
        "per_op": hlo.collectives,
        "total_wire_bytes": hlo.wire_bytes,
        "n_whiles": hlo.n_whiles,
        "unknown_trip_whiles": hlo.unknown_trip_whiles,
    }
    terms = roofline_terms(flops, bytes_accessed, colls["total_wire_bytes"], hw)

    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:  # decode: one token per sequence per step
        tokens = shape.global_batch
        model_flops = 2.0 * n_active * tokens

    hlo_flops_global = flops * world
    useful = model_flops / hlo_flops_global if hlo_flops_global else 0.0
    mfu_bound = model_flops / (world * hw.peak_flops * terms["bound_s"]) if terms["bound_s"] else 0.0
    return {
        "arch": cfg.name,
        "shape": shape.name,
        "world": world,
        "flops_per_device": flops,
        "bytes_per_device": bytes_accessed,
        "collectives": colls,
        "terms": terms,
        "model_flops": model_flops,
        "useful_flop_ratio": useful,
        "roofline_mfu": mfu_bound,
        "xla_cost_analysis_raw": {
            "flops_body_once": float(cost.get("flops", 0.0)),
            "bytes_body_once": float(cost.get("bytes accessed", 0.0)),
        },
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
    }
