"""deepseek-v3 [moe] — multi-head latent attention, 3 dense then 58 MoE.

61L d_model=7168 128H; MLA q_lora 1536, kv_lora 512, qk 128+64 (RoPE),
v 128; dense d_ff=18432; 256 routed experts top-8 (8 groups, top-4
groups, sigmoid scores) + 1 shared, d_ff=2048; vocab=129280, untied.
The one multi-token-prediction layer is not part of the model here.
[hf:deepseek-ai/DeepSeek-V3 config.json]
"""

from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek-v3",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    head_dim=128,                   # qk_nope_head_dim; MLA reads its own
    d_ff=18432,                     # the leading dense layers' FFN
    vocab_size=129280,
    n_experts=256,
    experts_per_token=8,
    moe_d_ff=2048,
    n_shared_experts=1,
    n_expert_groups=8,
    topk_expert_groups=4,
    n_dense_layers=3,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    ffn_kind="swiglu",
    norm_kind="rmsnorm",
    tie_embeddings=False,
    supports_long_context=False,
    source="hf:deepseek-ai/DeepSeek-V3 config.json",
)
