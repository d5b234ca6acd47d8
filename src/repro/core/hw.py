"""Engine-aware hardware specifications.

The paper (Table 1) characterizes each platform by peak throughput *per
execution engine* (CUDA core vs tensor core) plus memory bandwidth.  We keep
the same shape and add the TPU v5e target, mapping:

    CUDA core  -> vector engine (TPU VPU)
    tensor core-> matrix engine (TPU MXU)

All throughputs are in FLOP/s, bandwidths in B/s.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional


@dataclasses.dataclass(frozen=True)
class Engine:
    """One execution engine (matrix or vector) at a given precision."""

    name: str
    peak_flops: float  # FLOP/s
    dtype: str


@dataclasses.dataclass(frozen=True)
class HardwareSpec:
    """A platform: engines sharing one memory hierarchy (paper Fig. 1)."""

    name: str
    mem_bw: float                      # HBM bandwidth, B/s
    engines: Dict[str, Engine]         # keyed by "vector"/"matrix"
    l2_bytes: Optional[int] = None     # last-level on-chip cache (L2 / VMEM)
    link_bw: Optional[float] = None    # per-link interconnect, B/s
    chips: int = 1

    @property
    def vector(self) -> Engine:
        return self.engines["vector"]

    @property
    def matrix(self) -> Engine:
        return self.engines["matrix"]

    @property
    def alpha(self) -> float:
        """Matrix/vector engine speed ratio (the paper's alpha > 1)."""
        return self.matrix.peak_flops / self.vector.peak_flops

    def engine(self, which: str) -> Engine:
        return self.engines[which]


# --- Paper platforms (Table 1, FP64) -------------------------------------

A100_80G = HardwareSpec(
    name="A100-80GB",
    mem_bw=1.94e12,
    l2_bytes=40 * 2**20,
    link_bw=600e9 / 12,  # NVLink3: 600 GB/s total, 12 links
    engines={
        "vector": Engine("cuda-core-fp64", 9.7e12, "fp64"),
        "matrix": Engine("tensor-core-fp64", 19.5e12, "fp64"),
    },
)

GH200 = HardwareSpec(
    name="GH200",
    mem_bw=4.00e12,
    l2_bytes=50 * 2**20,
    link_bw=900e9 / 18,
    engines={
        "vector": Engine("cuda-core-fp64", 34.0e12, "fp64"),
        "matrix": Engine("tensor-core-fp64", 67.0e12, "fp64"),
    },
)

# --- TPU target ------------------------------------------------------------
# v5e peaks from Google Cloud's "TPU v5e" documentation: 197 TFLOP/s bf16
# (MXU), 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of interconnect per chip
# (~50 GB/s per ICI link).  The VPU peak is not published; it is derived
# from the unit shape: 8 lanes x 128 sublanes x 2 FLOP (FMA) x 4 units x
# ~0.94 GHz ~= 7.7e12 f32 FLOP/s, rounded to 7.5 TF and kept as an estimate.
TPU_V5E = HardwareSpec(
    name="TPU-v5e",
    mem_bw=819e9,
    l2_bytes=128 * 2**20,  # VMEM (acts as the software-managed cache level)
    link_bw=50e9,
    engines={
        "vector": Engine("vpu-f32", 7.5e12, "f32"),
        "matrix": Engine("mxu-bf16", 197e12, "bf16"),
    },
)

PLATFORMS: Dict[str, HardwareSpec] = {
    "a100": A100_80G,
    "gh200": GH200,
    "v5e": TPU_V5E,
}


#: Specs by the ``device_kind`` JAX reports for a device
#: (``jax.devices()[0].device_kind``).
DEVICE_KINDS: Dict[str, HardwareSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def spec_for_device_kind(kind: str) -> HardwareSpec:
    """The spec of a device JAX reports; a kind not in the table is an
    error, never a default."""
    try:
        return DEVICE_KINDS[kind]
    except KeyError:
        raise KeyError(f"no hardware spec for device_kind {kind!r}; "
                       f"known kinds: {sorted(DEVICE_KINDS)}") from None


def get_platform(name: str) -> HardwareSpec:
    key = name.lower().replace("-", "").replace("_", "")
    for k, v in PLATFORMS.items():
        if k.replace("-", "") == key:
            return v
    raise KeyError(f"unknown platform {name!r}; have {sorted(PLATFORMS)}")
