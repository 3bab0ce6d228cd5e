"""Assigned architecture config (see registry for the full pool)."""
from repro.configs.base import ModelConfig

# [arXiv:2405.04434; deepseek-ai/DeepSeek-V2 config.json] MLA kv_lora=512,
# 2 shared + 160 routed top-6 (softmax gate, group-limited greedy over 8
# groups keeping 3, weights unnormalised x16), first layer dense, YaRN rope.
CONFIG = ModelConfig(
    name="deepseek-v2-236b", family="moe",
    num_layers=60, d_model=5120, num_heads=128, num_kv_heads=128,
    d_ff=12288, vocab_size=102400,
    attn_type="mla", q_lora_rank=1536, kv_lora_rank=512,
    qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    num_experts=160, num_experts_per_tok=6, num_shared_experts=2,
    moe_d_ff=1536, first_k_dense=1, rope_theta=10_000.0, norm_eps=1e-6,
    rope_scaling={"type": "yarn", "factor": 40,
                  "original_max_position_embeddings": 4096,
                  "beta_fast": 32, "beta_slow": 1,
                  "mscale": 0.707, "mscale_all_dim": 0.707},
    n_group=8, topk_group=3, norm_topk_prob=False, routed_scaling_factor=16.0,
    moe_group_size=8192, optimizer="adafactor",
)

DEEPSEEK_V2_236B = CONFIG
