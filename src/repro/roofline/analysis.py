"""Roofline terms from compiled XLA artifacts.

* ``compute_s``    = HLO_FLOPs / peak_FLOP/s                 (per chip)
* ``memory_s``     = HLO_bytes / HBM_bw                      (per chip)
* ``collective_s`` = Σ_kind ring_factor·bytes / (link_bw × links)

HLO_FLOPs / HLO_bytes: XLA's ``compiled.cost_analysis()`` counts while/scan
bodies exactly ONCE (verified in tests/test_roofline.py), which misses >95%
of the work in scan-over-layers models.  We therefore parse the optimized
(post-SPMD) HLO text with a while-aware analyzer (``hlo_parse.py``) that
scales dot FLOPs, HBM traffic, and collective bytes by recovered loop trip
counts.  Both the raw cost_analysis numbers and the trip-corrected numbers
are reported; the roofline terms use the corrected ones.

Collective bytes are NOT in cost_analysis at all — they come from the parser
(summed result sizes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute, × trips), converted to per-device ICI
traffic with per-kind ring factors and the instruction's replica-group size.
"""

from __future__ import annotations

import json
import math
import re
from dataclasses import dataclass, field
from typing import Dict, Optional

from .hlo_parse import HloAnalysis, analyze_hlo
from .hw import HWSpec, HW_V5E

__all__ = ["collective_bytes", "roofline_terms", "RooflineReport",
           "analyze_compiled"]


def collective_bytes(hlo_text: str, total_devices: int = 1) -> Dict[str, float]:
    """Per-device bytes moved by each collective kind (trip-corrected)."""
    return dict(analyze_hlo(hlo_text, total_devices).collectives)


def _ring_factor(kind: str, group: int) -> float:
    """Per-device ICI traffic of one collective as a fraction of the
    instruction's RESULT size, ring algorithm over `group` devices."""
    if group <= 1:
        return 0.0
    if kind == "all-gather":
        return (group - 1) / group          # result = gathered tensor
    if kind == "reduce-scatter":
        return (group - 1)                  # result = scattered shard
    if kind == "all-reduce":
        return 2 * (group - 1) / group      # RS + AG over the full tensor
    if kind == "all-to-all":
        return (group - 1) / group
    if kind == "collective-permute":
        return 1.0
    return 1.0


@dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float                 # per-device, trip-corrected
    hlo_bytes: float                 # per-device HBM traffic, trip-corrected
    raw_flops: float                 # cost_analysis (scan bodies once)
    raw_bytes: float
    collective: Dict[str, float]     # per-device result bytes by kind
    collective_counts: Dict[str, float]
    group_sizes: Dict[str, int]
    compute_s: float
    memory_s: float
    collective_s: float
    model_flops: float               # 6·N·D (or 6·N_active·D) GLOBAL
    useful_ratio: float              # model_flops / (hlo_flops · chips)
    bytes_per_device: Optional[float] = None
    num_whiles: int = 0
    hw: str = "tpu-v5e"

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline-model step time: max of the three terms (perfect
        overlap) — the optimistic bound."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Useful model FLOP/s at the modeled step time vs. cluster peak."""
        if self.step_time_s <= 0:
            return 0.0
        achieved = self.model_flops / self.step_time_s
        return achieved / (self.chips * HW_V5E.peak_flops_bf16)

    def summary(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "compute_s": float(f"{self.compute_s:.5g}"),
            "memory_s": float(f"{self.memory_s:.5g}"),
            "collective_s": float(f"{self.collective_s:.5g}"),
            "dominant": self.dominant,
            "useful_ratio": round(min(self.useful_ratio, 99.0), 4),
            "roofline_fraction": round(self.roofline_fraction, 4),
            "bytes_per_device": self.bytes_per_device,
        }


def roofline_terms(hlo_flops: float, hlo_bytes: float,
                   collectives: Dict[str, float],
                   group_sizes: Dict[str, int],
                   hw: HWSpec = HW_V5E):
    compute_s = hlo_flops / hw.peak_flops_bf16
    memory_s = hlo_bytes / hw.hbm_bw
    coll_bytes = 0.0
    for kind, nbytes in collectives.items():
        group = group_sizes.get(kind, 1)
        coll_bytes += nbytes * _ring_factor(kind, group)
    collective_s = coll_bytes / (hw.ici_link_bw * hw.ici_links)
    return compute_s, memory_s, collective_s


def analyze_compiled(compiled, arch: str, shape: str, mesh_desc: str,
                     chips: int, mesh_groups: Dict[str, int],
                     model_flops: float, hw: HWSpec = HW_V5E,
                     hlo_text: Optional[str] = None) -> RooflineReport:
    cost = compiled.cost_analysis()
    raw_flops = float(cost.get("flops", 0.0))
    raw_bytes = float(cost.get("bytes accessed", 0.0))
    text = hlo_text if hlo_text is not None else compiled.as_text()
    hlo = analyze_hlo(text, total_devices=chips)
    # trip-corrected numbers can only add work relative to raw
    hlo_flops = max(hlo.flops, raw_flops)
    hlo_bytes = max(hlo.traffic_bytes, 0.0)

    compute_s, memory_s, collective_s = roofline_terms(
        hlo_flops, hlo_bytes, hlo.collectives, hlo.group_sizes, hw)

    bytes_per_device = None
    try:
        mem = compiled.memory_analysis()
        args = getattr(mem, "argument_size_in_bytes", 0)
        out = getattr(mem, "output_size_in_bytes", 0)
        tmp = getattr(mem, "temp_size_in_bytes", 0)
        alias = getattr(mem, "alias_size_in_bytes", 0)
        bytes_per_device = float(args + out + tmp - alias)
    except Exception:  # pragma: no cover
        pass

    useful = model_flops / max(hlo_flops * chips, 1.0)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_desc, chips=chips,
        hlo_flops=hlo_flops, hlo_bytes=hlo_bytes,
        raw_flops=raw_flops, raw_bytes=raw_bytes,
        collective=hlo.collectives, collective_counts=hlo.collective_counts,
        group_sizes=hlo.group_sizes,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        model_flops=model_flops, useful_ratio=useful,
        bytes_per_device=bytes_per_device, num_whiles=hlo.num_whiles,
        hw=hw.name,
    )
