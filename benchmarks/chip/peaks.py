"""Published per-chip peaks, keyed by the ``device_kind`` JAX reports.

Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 (393 TOP/s
int8), 16 GB HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect.
A kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_s": 819e9,
                    "hbm_bytes": 16e9, "ici_bytes_s": 200e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None
