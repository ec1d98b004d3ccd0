"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x mesh), TPU v5e constants:

    compute    = flops_per_device / PEAK_FLOPS
    memory     = hbm_bytes_per_device / HBM_BW
    collective = wire_bytes_per_device / ICI_BW

``cost_analysis()`` of an SPMD executable reports the PER-DEVICE program
(flops, bytes accessed); collective bytes are not in cost_analysis, so we
parse the post-optimization HLO: for every all-reduce / all-gather /
reduce-scatter / all-to-all / collective-permute we take the result-shape
bytes and apply the ring-model wire multiplier
(all-reduce 2(G-1)/G, gather/scatter (G-1)/G, permute 1) with the group
size G parsed from replica_groups.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional

__all__ = [
    "HW",
    "TPU_V5E",
    "PEAKS_BY_DEVICE_KIND",
    "hw_for_device_kind",
    "CollectiveOp",
    "parse_collectives",
    "collective_wire_bytes",
    "RooflineReport",
    "analyze",
]


@dataclasses.dataclass(frozen=True)
class HW:
    name: str
    peak_flops: float  # per chip, bf16
    hbm_bw: float  # bytes/s per chip
    ici_bw: float  # bytes/s per link


TPU_V5E = HW(
    name="tpu-v5e", peak_flops=197e12, hbm_bw=819e9, ici_bw=50e9
)

# published per-chip peaks keyed by ``jax.Device.device_kind`` (TPU v5e:
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s
# HBM).  A device kind not listed here has no peaks: nothing may price
# its measurements against another chip's roof.
PEAKS_BY_DEVICE_KIND = {
    "TPU v5 lite": TPU_V5E,  # what a v5e reports as its device_kind
}


def hw_for_device_kind(kind: str) -> Optional[HW]:
    """The peak table entry of a device kind, or None when unlisted."""
    return PEAKS_BY_DEVICE_KIND.get(kind)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16, "token": 0,
}

_COLL_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(",
)
_GROUP_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUP_LIST_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    dtype: str
    shape: tuple
    group_size: int
    result_bytes: int

    @property
    def wire_bytes(self) -> float:
        g = max(self.group_size, 1)
        if g == 1:
            return 0.0
        if self.kind == "all-reduce":
            return 2.0 * (g - 1) / g * self.result_bytes
        if self.kind in ("all-gather", "reduce-scatter", "all-to-all"):
            return (g - 1) / g * self.result_bytes
        return float(self.result_bytes)  # collective-permute


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def parse_collectives(hlo_text: str) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        if "-done(" in line:  # async pair: count only the -start
            continue
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        gs = 1
        gm = _GROUP_RE.search(line)
        if gm:
            gs = int(gm.group(2))
        else:
            gl = _GROUP_LIST_RE.search(line)
            if gl:
                gs = len([x for x in gl.group(1).split(",") if x.strip()])
        shape = tuple(int(d) for d in dims.split(",")) if dims else ()
        ops.append(
            CollectiveOp(
                kind=kind,
                dtype=dtype,
                shape=shape,
                group_size=gs,
                result_bytes=_shape_bytes(dtype, dims),
            )
        )
    return ops


def collective_wire_bytes(hlo_text: str) -> float:
    return sum(op.wire_bytes for op in parse_collectives(hlo_text))


@dataclasses.dataclass
class RooflineReport:
    arch: str
    cell: str
    mesh: str
    n_chips: int
    flops_per_device: float
    hbm_bytes_per_device: float
    wire_bytes_per_device: float
    model_flops: float  # 6*N*D (or 6*N_active*D) global
    hw: HW = TPU_V5E
    collective_counts: Optional[Dict[str, int]] = None
    memory_stats: Optional[dict] = None

    @property
    def t_compute(self) -> float:
        return self.flops_per_device / self.hw.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes_per_device / self.hw.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.wire_bytes_per_device / self.hw.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_lb(self) -> float:
        """Lower-bound step time: max of the three terms (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / (per-device HLO flops x chips)."""
        total = self.flops_per_device * self.n_chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu(self) -> float:
        """Model FLOPs utilization at the roofline lower-bound step time."""
        denom = self.step_time_lb * self.n_chips * self.hw.peak_flops
        return self.model_flops / denom if denom else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch,
            "cell": self.cell,
            "mesh": self.mesh,
            "n_chips": self.n_chips,
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "wire_bytes_per_device": self.wire_bytes_per_device,
            "model_flops": self.model_flops,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "mfu_bound": self.mfu,
            "collective_counts": self.collective_counts,
            "memory_stats": self.memory_stats,
        }


def analyze(
    arch: str,
    cell: str,
    mesh_name: str,
    n_chips: int,
    compiled,
    model_flops: float,
    hw: HW = TPU_V5E,
) -> RooflineReport:
    """Build a report from a compiled executable.

    Uses the loop-aware HLO walker (hlocount.py): XLA's own
    ``cost_analysis()`` counts while-loop bodies once, which undercounts a
    scan-over-layers model by ~n_layers x microbatches.
    """
    from repro import hlocount

    txt = compiled.as_text()
    cost = hlocount.analyze_hlo(txt)
    flops = float(cost.flops)
    hbm_bytes = float(cost.bytes)
    wire = float(cost.wire_bytes)
    counts = {k: int(v) for k, v in cost.coll_counts.items()}
    mem = compiled.memory_analysis()
    mem_stats = None
    if mem is not None:
        mem_stats = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
            "output_bytes": getattr(mem, "output_size_in_bytes", 0),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
            "alias_bytes": getattr(mem, "alias_size_in_bytes", 0),
        }
    return RooflineReport(
        arch=arch,
        cell=cell,
        mesh=mesh_name,
        n_chips=n_chips,
        flops_per_device=flops,
        hbm_bytes_per_device=hbm_bytes,
        wire_bytes_per_device=wire,
        model_flops=model_flops,
        hw=hw,
        collective_counts=counts,
        memory_stats=mem_stats,
    )
