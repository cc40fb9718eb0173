"""Published peaks of one chip, keyed by JAX's `device_kind`. A device
that is not in the table is an error, not a default.

Source: Google Cloud documentation, "TPU v5e" system architecture
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 394 TOP/s int8,
16 GB HBM2e at 819 GB/s per chip. (The program keeps a table of its own
in `dml_tpu.benchmarks.CHIP_PEAKS`; this copy is the yardstick.)
"""

from __future__ import annotations

from typing import Dict

CHIP_PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks_of(device_kind: str) -> Dict[str, float]:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add it to "
            f"benchmark/harness/peaks.py with its source") from None


def least_seconds(flops: float, bytes_: float, device_kind: str) -> float:
    """The roofline bound: the larger of compute time and memory time."""
    p = peaks_of(device_kind)
    return max(flops / p["bf16_flops"], bytes_ / p["hbm_bytes_per_s"])
