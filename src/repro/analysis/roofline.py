"""Roofline analysis from compiled dry-run artifacts (no hardware needed).

Three terms per (arch × shape × mesh), TPU v5e constants:

    compute    = HLO_FLOPs_global    / (chips × 197e12 FLOP/s)
    memory     = HLO_bytes_global    / (chips × 819e9  B/s)
    collective = collective_bytes    / (chips × 50e9   B/s per link)

``cost_analysis()`` on an SPMD executable reports the PER-DEVICE partitioned
module; we scale by chip count for the global numbers (verified in
tests/test_roofline.py against an analytic matmul).  collective_bytes comes
from parsing the post-partitioning HLO: summing operand bytes of all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

import numpy as np

# TPU v5e (per chip)
PEAK_FLOPS = 197e12        # bf16
HBM_BW = 819e9             # bytes/s
ICI_BW = 50e9              # bytes/s per link

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "tuple": 0, "token": 0,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RX = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, int]:
    """Sum operand bytes per collective kind from post-SPMD HLO text.

    Heuristic per op kind: all-reduce/collective-permute/all-to-all move the
    operand (== result) size; all-gather's operand is the smallest shape on
    the line; reduce-scatter's operand is the largest."""
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.strip()
        # the result may be a tuple with spaces (TPU async ops): the op name
        # is the first collective token after '=' that opens a call
        m = re.search(r"=.*?\s(" + "|".join(_COLLECTIVES) + r")(-start|-done)?\(", ls)
        if not m:
            continue
        kind = m.group(1)
        if m.group(2) == "-done":
            continue  # avoid double counting async pairs
        shapes = _SHAPE_RX.findall(ls)
        if not shapes:
            continue
        sizes = [_shape_bytes(dt, dims) for dt, dims in shapes]
        result = sizes[0]
        operands = sizes[1:] or sizes[:1]
        if kind == "all-gather":
            moved = min(operands + [result])
        elif kind == "reduce-scatter":
            moved = max(operands + [result])
        else:
            moved = result
        out[kind] += moved
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_global: float
    bytes_global: float
    collective_bytes_per_chip: float
    collectives_detail: Dict[str, int]
    model_flops: float
    peak_memory_bytes_per_chip: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.flops_global / (self.chips * PEAK_FLOPS)

    @property
    def t_memory(self) -> float:
        return self.bytes_global / (self.chips * HBM_BW)

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_chip / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy waste detector."""
        return self.model_flops / max(self.flops_global, 1.0)

    @property
    def roofline_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """compute-term / bound: 1.0 == perfectly compute-bound (ideal)."""
        return self.t_compute / max(self.roofline_time, 1e-30)

    def to_dict(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "flops_global": self.flops_global,
            "bytes_global": self.bytes_global,
            "collective_bytes_per_chip": self.collective_bytes_per_chip,
            "collectives_detail": self.collectives_detail,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "peak_memory_bytes_per_chip": self.peak_memory_bytes_per_chip,
        }


def model_flops_estimate(cfg, shape) -> float:
    """6·N·D (dense) or 6·N_active·D for training; 2·N·D per generated/
    prefilled token for inference (decode: one token per sequence)."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one new token per sequence (+ attention over the cache, which
    # 2·N·D does not count — that's fine, this is the "useful" floor)
    return 2.0 * n * shape.global_batch


def build_report(arch: str, shape_cfg, mesh_desc: str, chips: int,
                 cost: Dict, hlo_text: str, cfg,
                 memory_stats: Optional[Dict] = None,
                 colls: Optional[Dict[str, float]] = None) -> RooflineReport:
    flops_dev = float(cost.get("flops", 0.0))
    bytes_dev = float(cost.get("bytes accessed", 0.0))
    if colls is None:
        colls = collective_bytes_from_hlo(hlo_text)
    coll_per_chip = float(sum(colls.values()))
    return RooflineReport(
        arch=arch, shape=shape_cfg.name, mesh=mesh_desc, chips=chips,
        flops_global=flops_dev * chips,
        bytes_global=bytes_dev * chips,
        collective_bytes_per_chip=coll_per_chip,
        collectives_detail=colls,
        model_flops=model_flops_estimate(cfg, shape_cfg),
        peak_memory_bytes_per_chip=(memory_stats or {}).get("peak_bytes"),
    )
