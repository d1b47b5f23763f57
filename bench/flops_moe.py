"""Forward operations of a DeepSeek-V3-block LM (latent attention, routed and
shared experts) at (batch, seq), counted from its sizes.

The count is the algorithm's, as ``bench.flops`` counts a dense decoder's:
2 operations per multiply-add of every weight matmul a token goes through
(the latent attention's projections, the first layers' dense MLP, the
router, its ``top_k`` routed experts and the shared experts, and the
unembedding), and the two attention contractions over the causal pairs only,
with query/key heads ``head_dim + rope_head_dim`` wide and value heads
``v_head_dim``.  Experts a token does not choose are not counted; norms,
RoPE, softmax, sorting and the embedding gather are not counted.  Every layer
after the ``n_dense_layers`` dense ones is an expert layer.
"""
from __future__ import annotations

from bench.flops import causal_pairs


def mla_params(arch: dict) -> int:
    d, h = arch["d_model"], arch["n_heads"]
    dn, dr, dv = arch["head_dim"], arch["rope_head_dim"], arch["v_head_dim"]
    dc, dq = arch["kv_lora_rank"], arch.get("q_lora_rank") or 0
    q = d * dq + dq * h * (dn + dr) if dq else d * h * (dn + dr)
    return q + d * (dc + dr) + dc * h * dn + dc * h * dv + h * dv * d


def moe_params_per_token(arch: dict) -> int:
    d, fe = arch["d_model"], arch["d_ff_expert"]
    return d * arch["n_experts"] + 3 * d * fe * (arch["top_k"] + arch["n_shared_experts"])


def forward_flops(arch: dict, batch: int, seq: int) -> float:
    """Operations of one forward over a ``(batch, seq)`` block of tokens."""
    h = arch["n_heads"]
    dn, dr, dv = arch["head_dim"], arch["rope_head_dim"], arch["v_head_dim"]
    tokens = batch * seq
    n_dense = arch["n_dense_layers"]
    n_moe = arch["n_layers"] - n_dense
    attn = 2.0 * mla_params(arch) * tokens + 2.0 * batch * h * (dn + dr + dv) * causal_pairs(seq)
    dense = 2.0 * 3 * arch["d_model"] * arch["d_ff"] * tokens
    moe = 2.0 * moe_params_per_token(arch) * tokens
    unembed = 2.0 * arch["d_model"] * arch["vocab_size"] * tokens
    return arch["n_layers"] * attn + n_dense * dense + n_moe * moe + unembed
