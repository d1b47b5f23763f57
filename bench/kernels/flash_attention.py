"""flash_attention (``repro.kernels.flash_attention``): causal GQA prefill."""
from __future__ import annotations

from bench.flops import causal_pairs

# the kernel's name in the device trace (the jitted wrapper's name scope)
TRACE_NAME = "flash_attention"


def cost(*, batch: int, seq: int, heads: int, kv_heads: int, head_dim: int,
         dtype_bytes: int = 2) -> tuple[float, float]:
    ops = 2.0 * 2.0 * batch * heads * head_dim * causal_pairs(seq)
    elems = batch * seq * head_dim * (2 * heads + 2 * kv_heads)  # q, o; k, v
    return ops, float(elems * dtype_bytes)


def calls(arch: dict, batch: int, seq: int) -> list[dict]:
    h = arch["n_heads"]
    hd = arch.get("head_dim") or arch["d_model"] // h
    one = dict(batch=batch, seq=seq, heads=h, kv_heads=arch["n_kv_heads"], head_dim=hd)
    return [one] * arch["n_layers"]
