"""Published peaks of the chips the benchmark runs on, keyed by JAX's
``device_kind``.  A kind that is not here is an error, not a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 and 16 GB of
    # HBM at 819 GB/s per chip.
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add them to chipbench/peaks.py with their source")
    return PEAKS[device_kind]
