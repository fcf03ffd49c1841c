"""Peak rates of the chips the benchmark may run on, keyed by the
``device_kind`` that JAX reports.

A device that is not in the table is an error, never a default: a roofline
or utilization share is only as true as the peak it divides by.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
    # int8), 16 GB of HBM at 819 GB/s, per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud, TPU v5e"},
}


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; ``KeyError`` when absent."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak table entry for device_kind "
                       f"{device_kind!r} (known: {sorted(PEAKS)})") from None
