"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source: Google Cloud documentation, "TPU v5e" (per chip: 197 TFLOP/s bf16,
819 GB/s HBM bandwidth, 16 GB HBM).  ``unit_price`` is the price of one
machine of this chip in the unit ``plan_cost`` is counted in (one v5e
chip-hour is 1).  A kind that is not here is an error, never a default.
"""
from __future__ import annotations

PEAKS: dict[str, dict[str, float]] = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "unit_price": 1.0},
}


def peaks_for(device_kind: str) -> dict[str, float]:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; known: {sorted(PEAKS)}"
        ) from None
