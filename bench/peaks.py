"""Published peaks of each chip the benchmark runs on, keyed by the
`device_kind` JAX reports.  A chip that is not here is an error, never a
default.

TPU v5e: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s per chip.
"""
from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"flops_per_s": 197e12, "int8_ops_per_s": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}
SOURCE = "Google Cloud documentation, TPU v5e"


def peaks_for(device_kind: str) -> Dict[str, float]:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; add them to bench/peaks.py")
    return PEAKS[device_kind]


def least_seconds(flops: float, nbytes: float, peaks: Dict[str, float]
                  ) -> float:
    """The least time the chip could take for the work: the larger of
    its operations at peak rate and its bytes at peak bandwidth."""
    return max(flops / peaks["flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
