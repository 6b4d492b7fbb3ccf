"""Published peaks of one chip, keyed by the ``device_kind`` JAX reports.

A device that is not in the table is an error, not a default: a share of
the wrong peak is a wrong number.

"TPU v5 lite" (one TPU v5e chip): 197 TFLOP/s dense bf16, 16 GB of HBM2e at
819 GB/s.  Source: Google Cloud documentation, "TPU v5e" system
architecture page.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark/peaks.py has no published peak for device_kind "
            f"{device_kind!r} (known: {sorted(PEAKS)}); add it with its source"
        ) from None
