"""Roofline assembly: compiled-HLO terms next to the EdgeProfiler analytical
prediction (the paper's thesis — 'analytical model ≈ reality' — tested
against the XLA compiler instead of three devkits).

Hardware constants (assignment): TPU v5e — 197 TFLOP/s bf16/chip,
819 GB/s HBM, ~50 GB/s/link ICI.
"""
from __future__ import annotations

import json
from dataclasses import asdict, dataclass, field
from pathlib import Path
from typing import Dict, Optional

from repro.core import hardware as hw_mod
from repro.core.latency import RooflineTerms, roofline_terms

PEAK_FLOPS = hw_mod.TPU_V5E.peak_flops
HBM_BW = hw_mod.TPU_V5E.mem_bw
ICI_BW = hw_mod.TPU_V5E.net_bw
ICI_LINKS = 4          # v5e 2D torus: 4 links/chip


@dataclass
class CellResult:
    """One (arch x shape x mesh) dry-run cell."""
    arch: str
    shape: str
    mesh: str
    num_devices: int
    # compiled (per-device, SPMD-partitioned module)
    hlo_flops: float = 0.0
    hlo_bytes: float = 0.0
    collective_bytes: float = 0.0
    collective_detail: Dict[str, float] = field(default_factory=dict)
    memory_detail: Dict[str, float] = field(default_factory=dict)
    # analytical (per-device)
    model_flops_total: float = 0.0        # 6·N·D (assignment definition)
    analytic_flops: float = 0.0
    analytic_hbm: float = 0.0
    analytic_collective: float = 0.0
    compile_seconds: float = 0.0
    note: str = ""

    # ------------------------------------------------------------------
    def terms(self) -> RooflineTerms:
        return roofline_terms(self.hlo_flops, self.hlo_bytes,
                              self.collective_bytes, hw_mod.TPU_V5E,
                              links=ICI_LINKS)

    @property
    def model_flops_per_device(self) -> float:
        return self.model_flops_total / max(1, self.num_devices)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — how much compiled compute is useful."""
        return self.model_flops_per_device / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def analytic_bound_s(self) -> float:
        """Minimum achievable step time: useful FLOPs at peak vs minimum
        necessary HBM traffic (weights+cache+activations once) vs analytic
        collective bytes — the roofline the cell is chasing."""
        return max(self.model_flops_per_device / PEAK_FLOPS,
                   self.analytic_hbm / HBM_BW,
                   self.analytic_collective / (ICI_BW * ICI_LINKS))

    @property
    def roofline_fraction(self) -> float:
        """analytic-minimum time / compiled bound time — 1.0 means the
        compiled program moves/computes nothing beyond the physics of the
        workload. The score we hillclimb (per dominant term)."""
        t = self.terms()
        if t.bound <= 0:
            return 0.0
        return min(1.0, self.analytic_bound_s / t.bound)

    def row(self) -> Dict[str, object]:
        t = self.terms()
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "devices": self.num_devices,
            "hlo_gflops": self.hlo_flops / 1e9,
            "hlo_gb": self.hlo_bytes / 1e9,
            "coll_mb": self.collective_bytes / 1e6,
            "t_compute_ms": t.compute_s * 1e3,
            "t_memory_ms": t.memory_s * 1e3,
            "t_collective_ms": t.collective_s * 1e3,
            "dominant": t.dominant,
            "useful_ratio": round(self.useful_ratio, 3),
            "roofline_frac": round(self.roofline_fraction, 3),
            "note": self.note,
        }

    def save(self, directory: str | Path) -> Path:
        p = Path(directory)
        p.mkdir(parents=True, exist_ok=True)
        f = p / f"{self.arch}__{self.shape}__{self.mesh}.json"
        f.write_text(json.dumps(asdict(self), indent=1))
        return f

    @staticmethod
    def load(path: str | Path) -> "CellResult":
        return CellResult(**json.loads(Path(path).read_text()))

