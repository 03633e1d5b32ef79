"""Published peaks of the chips the benchmark knows, keyed by the
``device_kind`` jax reports. A device that is not here is an error, never a
default."""

from __future__ import annotations

from typing import Dict

PEAKS: Dict[str, Dict] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s per chip.
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks_for(device_kind: str) -> Dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmarks/harness/peaks.py with its "
                       f"source")
    return PEAKS[device_kind]
