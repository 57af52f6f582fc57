"""Published peaks of the chips the benchmark may run on, keyed by
``jax.devices()[0].device_kind``.  A kind that is not here is an error,
never a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" system architecture page:
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16 * 1024**3,
        "source": "cloud.google.com/tpu/docs/v5e (system architecture)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}: add it to "
            "benchmarks/srbench/peaks.py with its source"
        ) from None
