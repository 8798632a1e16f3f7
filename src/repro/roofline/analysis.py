"""Roofline terms from compiled dry-run artifacts.

Hardware model: the per-chip peaks in PEAKS, keyed by the chip's
`device_kind` (as `jax.devices()[0].device_kind` reports it). A kind that
is not in the table is an error, never a default.

  compute term    = HLO_FLOPs / peak
  memory term     = HLO_bytes / HBM_bw
  collective term = collective_bytes / link_bw

All three inputs come from repro.roofline.hlo_parse (loop-trip-aware
analysis of compiled.as_text(); see that module).
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float                 # bf16 FLOP/s per chip
    hbm_bw: float                # bytes/s per chip
    ici_bw: float                # bytes/s per link


# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
# HBM, 1,600 Gbit/s chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> ChipPeaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

@dataclasses.dataclass
class Roofline:
    flops: float                  # per device
    bytes_hbm: float              # per device
    bytes_collective: float       # per device
    model_flops: float            # 6*N*D (active params), whole step
    chips: int
    device_kind: str              # key into PEAKS

    @property
    def peaks(self) -> ChipPeaks:
        return peaks_for(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_hbm / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.bytes_collective / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs * chips): remat/redundancy waste."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the roofline the step achieves if it runs exactly at
        the binding resource: useful model FLOPs per second at bound_time
        over the chips' peak."""
        if self.bound_time == 0:
            return 0.0
        achieved = self.model_flops / self.bound_time / self.chips
        return achieved / self.peaks.flops

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops,
            "hbm_bytes_per_device": self.bytes_hbm,
            "collective_bytes_per_device": self.bytes_collective,
            "model_flops": self.model_flops,
            "chips": self.chips,
            "device_kind": self.device_kind,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "dominant": self.dominant,
            "useful_flops_fraction": self.useful_flops_fraction,
            "roofline_fraction": self.roofline_fraction,
        }
