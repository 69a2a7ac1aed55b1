"""deepseek-v3-671b [moe]: 61L d_model=7168 128H d_ff=2048 vocab=129280.

MLA attention with YaRN (factor 40 over 4096 positions), MoE with 1 shared
+ 256 routed experts top-8 chosen by sigmoid scores with a choice-only bias
inside the 4 best of 8 groups (``noaux_tc``), weights normalised and scaled
by 2.5, MTP [arXiv:2412.19437; hf]. First 3 layers are dense
(d_ff=18432); the remaining 58 are MoE with per-expert hidden 2048. The
output head is its own matrix.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v3-671b",
    family="moe",
    n_layers=61,
    d_model=7168,
    n_heads=128,
    n_kv_heads=128,
    d_ff=18432,            # dense layers' hidden (first_k_dense)
    moe_d_ff=2048,
    vocab_size=129280,
    n_experts=256,
    n_shared_experts=1,
    top_k=8,
    scoring_func="sigmoid",
    n_group=8,
    topk_group=4,
    routed_scaling_factor=2.5,
    first_k_dense=3,
    expert_sharding="expert",  # 256 experts / 16-way model axis = 16 per device
    attention="mla",
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_factor=40.0,
    rope_original_max_position=4096,
    rope_beta_fast=32.0,
    rope_beta_slow=1.0,
    rope_mscale=1.0,
    rope_mscale_all_dim=1.0,
    mtp_depth=1,
    tie_embeddings=False,
)
