"""DeepSeek-V2-Lite 16B [arXiv:2405.04434] — MLA + MoE.

MLA: kv_lora_rank=512, per-head (nope=128, rope=64, v=128), 16 heads.
MoE: 64 routed experts top-6 + 2 shared experts, expert d_ff=1408.

NOTE (DESIGN.md §5): the assignment line lists both "64e top-6" and
"2 shared+160 routed"; 160 routed is full V2 — we follow the explicit
64e/top-6 numbers. Real V2-Lite keeps layer 0 dense; we use a homogeneous
MoE stack so the layer stack scans (compile-time), a documented simplification
that leaves param count within ~1%.
"""
from repro.configs.base import ModelConfig, MoEConfig, MLAConfig, MLA_, MOE_FF

CONFIG = ModelConfig(
    name="deepseek-v2-lite-16b",
    family="moe",
    n_layers=27,
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    d_ff=1408,
    vocab_size=102400,
    vocab_multiple=2048,
    layer_pattern=((MLA_, MOE_FF),),
    mla=MLAConfig(kv_lora_rank=512, q_lora_rank=0,
                  qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, num_shared_experts=2,
                  expert_d_ff=1408, shared_d_ff=2816),
    rope_theta=10000.0,
    act="silu",
    fsdp=True,
    remat_policy="dots",
    notes="MLA compresses the KV cache to kv_lora_rank+rope dims per token; "
          "attention is still quadratic in sequence length.",
)

REDUCED = ModelConfig(
    name="deepseek-v2-lite-16b-reduced",
    family="moe",
    n_layers=2,
    d_model=64,
    n_heads=4,
    n_kv_heads=4,
    d_ff=48,
    vocab_size=257,
    layer_pattern=((MLA_, MOE_FF),),
    mla=MLAConfig(kv_lora_rank=32, q_lora_rank=0,
                  qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16),
    moe=MoEConfig(num_experts=4, top_k=2, num_shared_experts=1,
                  expert_d_ff=48, shared_d_ff=48),
)
