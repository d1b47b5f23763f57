"""moonlight-16b-a3b [moe] — deepseek-v3 block: MLA (no q-LoRA), 1 dense + 26
MoE layers of 64 sigmoid-routed experts top-6 with 2 shared
[hf:moonshotai/Moonlight-16B-A3B]."""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="moonlight-16b-a3b",
    arch_type="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,  # nominal; MLA caches the 576-wide latent instead
    d_ff=11264,  # the first (dense) layer; routed experts use d_ff_expert
    vocab_size=163840,
    source="hf:moonshotai/Moonlight-16B-A3B",
    attn_kind="mla",
    head_dim=128,  # qk nope dim
    v_head_dim=128,
    q_lora_rank=0,  # null: q is projected from the hidden state directly
    kv_lora_rank=512,
    rope_head_dim=64,
    n_experts=64,
    n_shared_experts=2,
    top_k=6,
    d_ff_expert=1408,
    n_dense_layers=1,
    moe_every=1,
    router_score="sigmoid_noaux",  # topk_method noaux_tc, n_group 1
    routed_scale=2.446,
    rope_theta=50_000.0,
    max_seq_len=8_192,
)

SMOKE = CONFIG.replace(
    n_layers=3,
    d_model=128,
    n_heads=4,
    n_kv_heads=4,
    head_dim=32,
    v_head_dim=32,
    kv_lora_rank=64,
    rope_head_dim=16,
    d_ff=256,
    d_ff_expert=64,
    n_experts=16,
    vocab_size=512,
    param_dtype="float32",
    compute_dtype="float32",
)
