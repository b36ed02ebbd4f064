"""Three-term roofline model for TPU v5e (target hardware, per task spec).

  compute    = HLO_FLOPs_per_device / peak_FLOPs
  memory     = HLO_bytes_per_device / HBM_bw
  collective = collective_wire_bytes_per_device / ICI_bw

``cost_analysis()`` of an SPMD module reports *per-partition* flops/bytes
(verified empirically at session start: 512² × 256 sharded matmul reported
total/8 on an 8-device mesh). Collective bytes come from runtime.hlo.
"""
from __future__ import annotations

import dataclasses
from typing import Dict

PEAK_FLOPS = 197e12  # bf16 per chip
HBM_BW = 819e9  # bytes/s per chip
ICI_BW = 50e9  # bytes/s per link (~per-chip injection, task-spec constant)


@dataclasses.dataclass
class RooflineTerms:
    compute_s: float
    memory_s: float
    collective_s: float
    flops_per_device: float
    bytes_per_device: float
    collective_bytes_per_device: float

    @property
    def dominant(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        """Step-time lower bound if terms overlap perfectly."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def serial_s(self) -> float:
        """Step-time upper bound if nothing overlaps."""
        return self.compute_s + self.memory_s + self.collective_s

    def roofline_fraction(self) -> float:
        """Fraction of the dominant-resource bound actually achievable:
        bound / serial ∈ (1/3, 1]. 1.0 = the other two terms are free."""
        if self.serial_s == 0:
            return 0.0
        return self.bound_s / self.serial_s

    def to_dict(self) -> Dict:
        return {
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "bound_s": self.bound_s,
            "serial_s": self.serial_s,
            "roofline_fraction": self.roofline_fraction(),
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes_per_device,
        }


def roofline(flops_per_device: float, bytes_per_device: float, collective_bytes: float) -> RooflineTerms:
    return RooflineTerms(
        compute_s=flops_per_device / PEAK_FLOPS,
        memory_s=bytes_per_device / HBM_BW,
        collective_s=collective_bytes / ICI_BW,
        flops_per_device=flops_per_device,
        bytes_per_device=bytes_per_device,
        collective_bytes_per_device=collective_bytes,
    )


def model_flops(cfg, shape, n_devices: int) -> Dict[str, float]:
    """Useful-work model FLOPs: 6·N·D train, 2·N·D per decode step (N =
    active params). Returned per device, for the MODEL/HLO ratio."""
    n_active = cfg.n_active_params()
    if shape.kind == "train":
        total = 6.0 * n_active * shape.tokens
    elif shape.kind == "prefill":
        total = 2.0 * n_active * shape.tokens
    else:  # decode: one token per sequence
        total = 2.0 * n_active * shape.global_batch
    return {"model_flops_total": total, "model_flops_per_device": total / n_devices}
