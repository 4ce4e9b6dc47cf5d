"""Hardware models for the adapted roofline / vectorization-bound analysis.

The paper (ARM SVE Unleashed) parameterizes its analysis by three hardware
quantities: the vector length VLEN, the peak compute throughput, and the peak
memory bandwidth.  We keep that parameterization but provide two concrete
machine models:

* ``GRACE`` — the paper's platform (Neoverse V2, 128-bit SVE), used by the
  paper-validation benchmarks so the analytic reproduction matches the paper's
  own numbers.
* ``TPU_V5E`` — the target platform for the framework.  The TPU has two
  data-parallel engines: the VPU (8x128 lanes of 32-bit) and the MXU (128x128
  systolic array, bf16-native).  "Vector length" on TPU is per-issue lane
  count x element bits; element-size packing (fp32 -> bf16 -> int8) plays the
  role the paper assigns to ELEN.
"""

from __future__ import annotations

import dataclasses
from typing import Mapping


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    """Per-chip hardware model used by the roofline and VB metrics."""

    name: str
    # Peak dense compute throughput per chip, FLOP/s, keyed by element type.
    peak_flops: Mapping[str, float]
    # Peak HBM/DRAM bandwidth per chip, bytes/s.
    hbm_bw: float
    # Inter-chip interconnect bandwidth per link, bytes/s (0 for single-socket).
    ici_bw_per_link: float
    # Number of ICI links per chip that can be driven concurrently.
    ici_links: int
    # Native vector width in bits for the vector (non-matrix) engine.
    vlen_bits: int
    # Memory transaction granule in bytes (cache line / HBM burst).
    transaction_bytes: int
    # MXU dims (0 if no matrix engine).
    mxu_dim: int = 0

    def peak(self, dtype: str = "bf16") -> float:
        if dtype not in self.peak_flops:
            raise KeyError(
                f"{self.name}: no peak for dtype {dtype!r}; "
                f"have {sorted(self.peak_flops)}"
            )
        return self.peak_flops[dtype]

    def ici_bw(self) -> float:
        return self.ici_bw_per_link * max(self.ici_links, 1)


#: Element sizes in bits for the dominant data formats (paper's ELEN).
ELEN_BITS: Mapping[str, int] = {
    "fp64": 64,
    "f64": 64,
    "float64": 64,
    "fp32": 32,
    "f32": 32,
    "float32": 32,
    "tf32": 32,
    "bf16": 16,
    "fp16": 16,
    "f16": 16,
    "float16": 16,
    "bfloat16": 16,
    "int8": 8,
    "s8": 8,
    "fp8": 8,
    "int4": 4,
}


def elen_bits(dtype: str) -> int:
    key = str(dtype).lower()
    if key not in ELEN_BITS:
        raise KeyError(f"unknown element type {dtype!r}")
    return ELEN_BITS[key]


# --- The paper's platform: Nvidia Grace (Neoverse V2), 128-bit SVE -----------
# Peak FP64/chip-core: 4 FPU pipes x 2 FLOP (FMA) x 2 lanes (128b/64b) x 3.447GHz.
# We model a single core (the paper's single-thread analysis) and the full
# 72-core socket; STREAM-measured bandwidth per the paper: 30 GB/s @1T,
# 250 GB/s @72T.
_GRACE_CORE_FP64_SCALAR = 4 * 2 * 3.447e9  # 4 pipes, FMA, scalar (1 elem)

GRACE_CORE = ChipSpec(
    name="grace-core",
    peak_flops={
        # scalar baseline (vectorization disabled) — 1 element per issue
        "scalar_fp64": _GRACE_CORE_FP64_SCALAR,
        "scalar_fp32": _GRACE_CORE_FP64_SCALAR,
        # vectorized peaks = scalar x VB
        "fp64": _GRACE_CORE_FP64_SCALAR * 2,
        "fp32": _GRACE_CORE_FP64_SCALAR * 4,
        "fp16": _GRACE_CORE_FP64_SCALAR * 8,
        # Neoverse V2 SVE carries the BF16 extension (BFDOT/BFMMLA); same
        # 16-bit lane packing as fp16 — needed by the ELEN-packing tuning axis
        "bf16": _GRACE_CORE_FP64_SCALAR * 8,
    },
    hbm_bw=30e9,  # single-thread STREAM triad (paper Sec. 3)
    ici_bw_per_link=0.0,
    ici_links=0,
    vlen_bits=128,
    transaction_bytes=64,  # LLC line (paper Sec. 5: 64-byte line)
)

GRACE_SOCKET = dataclasses.replace(
    GRACE_CORE,
    name="grace-socket-72c",
    peak_flops={k: v * 72 for k, v in GRACE_CORE.peak_flops.items()},
    hbm_bw=250e9,  # 72-thread STREAM triad (paper Sec. 3)
)


# --- Target platform: TPU v5e ------------------------------------------------
# Constants fixed by the assignment: 197 TFLOP/s bf16/chip, 819 GB/s HBM,
# ~50 GB/s/link ICI.  fp32 matmul runs the MXU in passes -> 1/2 bf16; int8 2x.
# The VPU is (8 sublanes x 128 lanes) of 32-bit elements per issue.
TPU_V5E = ChipSpec(
    name="tpu-v5e",
    peak_flops={
        "bf16": 197e12,
        "fp32": 98.5e12,
        "int8": 394e12,
        # scalar-equivalent baseline: one element per issue slot at VPU clock.
        # 197e12 / (2 flop/MAC) / (128*128 MACs) ~= 6.0e9 issue slots/s; the
        # scalar model charges 2 FLOP per slot.
        "scalar": 197e12 / (128 * 128),
    },
    hbm_bw=819e9,
    ici_bw_per_link=50e9,
    ici_links=4,
    vlen_bits=8 * 128 * 32,  # one VPU vreg issue: 8x128 lanes x 32-bit
    transaction_bytes=512,
    mxu_dim=128,
)

TPU_V5P = ChipSpec(
    name="tpu-v5p",
    peak_flops={
        "bf16": 459e12,
        "fp32": 229.5e12,
        "int8": 918e12,
        "scalar": 459e12 / (128 * 128),
    },
    hbm_bw=2765e9,
    ici_bw_per_link=100e9,
    ici_links=6,
    vlen_bits=8 * 128 * 32,
    transaction_bytes=512,
    mxu_dim=128,
)

DEFAULT_CHIP = TPU_V5E

CHIPS: Mapping[str, ChipSpec] = {
    "grace-core": GRACE_CORE,
    "grace-socket": GRACE_SOCKET,
    "tpu-v5e": TPU_V5E,
    "tpu-v5p": TPU_V5P,
}


def get_chip(name: str) -> ChipSpec:
    if name not in CHIPS:
        raise KeyError(f"unknown chip {name!r}; have {sorted(CHIPS)}")
    return CHIPS[name]


# JAX's ``device_kind`` -> the chip whose published peaks apply.  A device
# missing here has no peaks, and a caller that needs them must fail.
DEVICE_KINDS: Mapping[str, ChipSpec] = {
    "TPU v5 lite": TPU_V5E,
}


def chip_for_device_kind(kind: str) -> ChipSpec:
    """Peaks for a device as ``jax.devices()[i].device_kind`` names it."""
    if kind not in DEVICE_KINDS:
        raise KeyError(
            f"no peaks for device kind {kind!r}; have {sorted(DEVICE_KINDS)}")
    return DEVICE_KINDS[kind]
