"""Published peaks of the chips the benchmark runs on, by ``device_kind``.

TPU v5e ("TPU v5 lite"): Google Cloud documentation, "TPU v5e" — 197
TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip.  A kind that is not here is an error.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}"
                       f" (known: {sorted(PEAKS)})")
    return PEAKS[device_kind]
