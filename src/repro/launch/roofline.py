"""Roofline-term extraction from compiled dry-run artifacts.

  compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
  memory term     = HLO_bytes / (chips * HBM_bw)
  collective term = collective_bytes / (chips * link_bw)

cost_analysis() on the partitioned module reports PER-DEVICE flops/bytes,
so the per-chip terms divide by peak only. collective_bytes is parsed from
the post-SPMD HLO text: we sum the OUTPUT buffer sizes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute instruction
(a per-device byte count, since the partitioned HLO is the per-device
program).

Peak numbers come from ``launch.mesh.peaks(device_kind)``.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional, Tuple

from .mesh import peaks

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string (handles tuples by summing parts)."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output-buffer bytes per collective kind from post-SPMD HLO."""
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.match(r"(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|[\w\[\],{}\s]+?)\s+"
                     r"(all-gather|all-reduce|reduce-scatter|all-to-all|"
                     r"collective-permute)", stripped)
        if not m:
            continue
        shape_str, kind = m.group(1), m.group(2)
        out[kind] += _shape_bytes(shape_str)
        out["count"] += 1
    return out


@dataclasses.dataclass
class RooflineTerms:
    flops: float                 # per-device HLO flops
    bytes_accessed: float        # per-device HBM bytes
    coll_bytes: float            # per-device collective bytes
    coll_breakdown: Dict[str, int]
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: Optional[float] = None    # 6*N*D (global, useful flops)
    useful_ratio: Optional[float] = None   # model_flops / global HLO flops

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def analyze(compiled, hlo_text: str, n_chips: int, device_kind: str,
            model_flops: Optional[float] = None,
            links_per_chip: float = 1.0) -> RooflineTerms:
    """Loop-corrected roofline terms against ``device_kind``'s peaks.

    Uses hlo_analysis.aggregate (walks the call graph with while-loop trip
    multiplicities) because raw cost_analysis counts lax.scan bodies ONCE,
    undercounting layered models by ~n_layers (EXPERIMENTS.md §Roofline).
    """
    from .hlo_analysis import aggregate
    tot = aggregate(hlo_text)
    flops = float(tot["flops"])
    byts = float(tot["traffic_bytes"])
    cbytes = float(tot["coll_bytes_total"])
    coll = {k: int(v) for k, v in tot["coll_bytes"].items()}
    coll["count"] = int(tot["coll_count"])
    peak = peaks(device_kind)
    compute_s = flops / peak["flops_bf16"]
    memory_s = byts / peak["hbm_bw"]
    collective_s = cbytes / (peak["ici_link_bw"] * links_per_chip)
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)
    useful = None
    if model_flops:
        useful = model_flops / max(flops * n_chips, 1.0)
    return RooflineTerms(flops=flops, bytes_accessed=byts, coll_bytes=cbytes,
                         coll_breakdown=coll, compute_s=compute_s,
                         memory_s=memory_s, collective_s=collective_s,
                         bottleneck=bottleneck, model_flops=model_flops,
                         useful_ratio=useful)


def lm_model_flops(n_params_active: int, n_tokens: int,
                   kind: str = "train") -> float:
    """6*N*D for training; 2*N*D for inference forward."""
    mult = 6.0 if kind == "train" else 2.0
    return mult * n_params_active * n_tokens


def memory_report(compiled) -> Dict:
    try:
        m = compiled.memory_analysis()
        if m is None:
            return {}
        keys = ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "generated_code_size_in_bytes",
                "alias_size_in_bytes")
        return {k: int(getattr(m, k)) for k in keys if hasattr(m, k)}
    except Exception as e:  # CPU backend may not implement it
        return {"error": str(e)}
