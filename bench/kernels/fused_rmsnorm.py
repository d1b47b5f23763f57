"""fused_rmsnorm (``repro.kernels.rmsnorm``): one row-blocked RMSNorm."""
from __future__ import annotations

TRACE_NAME = "fused_rmsnorm"


def cost(*, rows: int, width: int, dtype_bytes: int = 2) -> tuple[float, float]:
    # square, sum, scale by the rsqrt, scale by the weight: 4 per element
    ops = 4.0 * rows * width
    return ops, float((2 * rows * width + width) * dtype_bytes)


def calls(arch: dict, batch: int, seq: int) -> list[dict]:
    one = dict(rows=batch * seq, width=arch["d_model"])
    # two per layer (before attention and before the MLP) and the final norm
    return [one] * (2 * arch["n_layers"] + 1)
