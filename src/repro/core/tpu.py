"""Target-hardware model: TPU chip peaks and roofline terms.

Peaks are keyed by the ``device_kind`` JAX reports for the chip
(``jax.devices()[0].device_kind``); a kind missing from :data:`PEAKS` is
an error, never a default.  TPU v5e reports ``"TPU v5 lite"``.  Its
numbers are from Google Cloud's "TPU v5e" documentation page:

    peak bf16 compute : 197 TFLOP/s per chip
    peak int8 compute : 394 TOP/s per chip
    HBM               : 16 GB at 819 GB/s per chip
    interchip (ICI)   : 1,600 Gbit/s per chip = 50 GB/s on each of 4 links
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class TpuSpec:
    name: str
    peak_flops_bf16: float                 # FLOP/s per chip
    peak_ops_int8: float                   # OP/s per chip
    hbm_bw: float                          # bytes/s per chip
    ici_bw_per_link: float                 # bytes/s per link
    hbm_bytes: float                       # HBM capacity per chip
    vmem_bytes: float                      # physical VMEM per core
    #: what Mosaic lets one kernel use unless its CompilerParams raise it
    vmem_scoped_limit_bytes: float
    mxu_dim: int                           # systolic array tile


#: device_kind -> peaks (Google Cloud "TPU v5e" page)
PEAKS: Dict[str, TpuSpec] = {
    "TPU v5 lite": TpuSpec(
        name="tpu-v5e", peak_flops_bf16=197e12, peak_ops_int8=394e12,
        hbm_bw=819e9, ici_bw_per_link=1600e9 / 8 / 4, hbm_bytes=16e9,
        vmem_bytes=128 * 2 ** 20, vmem_scoped_limit_bytes=16 * 2 ** 20,
        mxu_dim=128),
}

V5E = PEAKS["TPU v5 lite"]


def spec_for(device_kind: str) -> TpuSpec:
    """The peaks of a chip by its JAX ``device_kind``; raises for a chip
    the table does not hold."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks recorded for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class RooflineTerms:
    """The three-term roofline for one (arch x shape x mesh) cell."""

    cell: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float          # summed over all chips
    model_flops: float               # 6*N*D (train) or 2*N_active*D (decode)
    spec: TpuSpec = dataclasses.field(default_factory=lambda: V5E)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * self.spec.peak_flops_bf16)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.chips * self.spec.hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.collective_bytes / (self.chips * self.spec.ici_bw_per_link)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step-time estimate = max of the three terms (perfect
        overlap assumption; the sum would be the no-overlap bound)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — fraction of compiled compute that is
        'useful' (catches remat and redundancy waste).  Can exceed 1 only if
        the compiler fused away work; values << 1 indicate recompute."""
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Achievable MFU under the roofline: useful FLOPs / (chips * peak *
        step_time).  This is the score we hillclimb."""
        denom = self.chips * self.spec.peak_flops_bf16 * self.step_time_s
        return self.model_flops / denom if denom else 0.0

    def as_dict(self) -> Dict:
        return {
            "cell": self.cell, "chips": self.chips,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
        }


def dense_train_model_flops(n_params: float, tokens: float) -> float:
    """6*N*D: fwd 2ND + bwd 4ND."""
    return 6.0 * n_params * tokens


def decode_model_flops(n_active_params: float, tokens: float) -> float:
    """Forward-only decode: 2*N_active per generated token."""
    return 2.0 * n_active_params * tokens
