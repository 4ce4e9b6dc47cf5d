"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (system architecture):
197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s per chip.  A device kind that
is not listed has no peaks, and the benchmark refuses to run on it.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peaks:
    bf16_flops: float  # FLOP/s
    hbm_bytes_s: float  # bytes/s
    hbm_bytes: int  # bytes of device memory


PEAKS = {
    "TPU v5 lite": Peaks(bf16_flops=197e12, hbm_bytes_s=819e9,
                         hbm_bytes=16 * 10**9),
}


def for_device_kind(kind: str) -> Peaks:
    if kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"have {sorted(PEAKS)}")
    return PEAKS[kind]
