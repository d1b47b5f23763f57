"""Forward operations of a dense decoder at (batch, seq), counted from its sizes.

The count is the algorithm's: every matrix multiplication of the projections,
the gated MLP and the unembedding (2 operations per multiply-add), and the
two attention contractions over the causal pairs only (``seq * (seq + 1) / 2``
per head).  Norms, RoPE, softmax and the embedding gather are not counted.
XLA's ``cost_analysis`` counts a scanned layer body once, so it is not used.
"""
from __future__ import annotations


def causal_pairs(seq: int) -> int:
    return seq * (seq + 1) // 2


def layer_matmul_params(arch: dict) -> int:
    d, h, hkv = arch["d_model"], arch["n_heads"], arch["n_kv_heads"]
    hd = arch.get("head_dim") or d // h
    return d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * arch["d_ff"]


def forward_flops(arch: dict, batch: int, seq: int) -> float:
    """Operations of one forward over a ``(batch, seq)`` block of tokens."""
    d, h = arch["d_model"], arch["n_heads"]
    hd = arch.get("head_dim") or d // h
    tokens = batch * seq
    per_layer = 2.0 * layer_matmul_params(arch) * tokens
    per_layer += 2.0 * 2.0 * batch * h * hd * causal_pairs(seq)  # QK^T and PV
    unembed = 2.0 * d * arch["vocab_size"] * tokens
    return arch["n_layers"] * per_layer + unembed
