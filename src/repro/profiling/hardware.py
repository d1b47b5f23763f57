"""TPU hardware catalog: the heterogeneous pool Harpagon schedules over.

Price ratios follow on-demand cloud pricing; the P100/V100 heterogeneity of
the paper maps onto TPU generations (DESIGN.md Sec. 3/7).  Each entry also
carries the ``device_kind`` string JAX reports for that chip, so a run on an
attached device looks its peaks up with `spec_for` (an unknown kind is an
error, never a default).  v5e peaks: Google Cloud documentation, "TPU v5e".
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class TPUSpec:
    name: str
    device_kind: str  # jax.Device.device_kind of this chip
    peak_flops_bf16: float  # FLOP/s per chip
    hbm_bw: float  # bytes/s per chip
    hbm_bytes: float
    ici_bw: float  # bytes/s per link
    unit_price: float  # relative $ / chip-hour


TPU_V5E = TPUSpec("tpu-v5e", "TPU v5 lite", 197e12, 819e9, 16e9, 50e9, 1.0)
TPU_V4 = TPUSpec("tpu-v4", "TPU v4", 275e12, 1228e9, 32e9, 50e9, 1.35)
TPU_V5P = TPUSpec("tpu-v5p", "TPU v5", 459e12, 2765e9, 96e9, 100e9, 1.75)

CATALOG: dict[str, TPUSpec] = {t.name: t for t in (TPU_V5E, TPU_V4, TPU_V5P)}
_BY_KIND: dict[str, TPUSpec] = {t.device_kind: t for t in CATALOG.values()}


def spec_for(device) -> TPUSpec:
    """The catalog entry of an attached device (or its ``device_kind``
    string); raises `KeyError` for a kind the catalog does not hold."""
    kind = device if isinstance(device, str) else device.device_kind
    try:
        return _BY_KIND[kind]
    except KeyError:
        raise KeyError(
            f"no peaks for device kind {kind!r}; known: {sorted(_BY_KIND)}"
        ) from None
