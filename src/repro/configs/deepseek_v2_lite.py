"""DeepSeek-V2-Lite (15.7B total, 2.4B active): MLA with no query LoRA and
YaRN rope; one dense layer, then 26 MoE layers of 64 routed experts (softmax
router, greedy top-6, unnormalised) beside 2 shared experts.

[hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434] 27L
d_model=2048 16H; MLA kv_lora_rank 512, q_lora_rank null, qk_nope 128,
qk_rope 64, v 128; dense d_ff 10944; expert d_ff 1408; vocab 102400;
rope_theta 1e4 with YaRN (factor 40 over 4096, beta_fast 32, beta_slow 1,
mscale = mscale_all_dim = 0.707); RMSNorm eps 1e-6; untied head; released
in bfloat16.
"""
from repro.configs.base import (LayerSpec, MLAConfig, ModelConfig, Segment,
                                YaRNConfig)

DENSE = LayerSpec(mixer="attn", ffn="mlp")
MOE = LayerSpec(mixer="attn", ffn="moe")

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    family="moe",
    source="https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite/blob/main/"
           "config.json; arXiv:2405.04434",
    d_model=2048,
    n_heads=16,
    n_kv_heads=16,
    head_dim=128,
    d_ff=10944,              # the dense first layer (first_k_dense_replace 1)
    vocab_size=102_400,
    segments=(
        Segment((DENSE,), repeat=1),
        Segment((MOE,), repeat=26),
    ),
    norm="rmsnorm",
    act="silu",
    pos_emb="rope",
    rope_theta=10_000.0,
    rope_scaling=YaRNConfig(factor=40.0, original_max_position=4096,
                            beta_fast=32.0, beta_slow=1.0, mscale=0.707,
                            mscale_all_dim=0.707),
    n_experts=64,
    n_shared_experts=2,
    moe_top_k=6,
    moe_d_ff=1408,
    moe_renorm_topk=False,   # norm_topk_prob false, routed_scaling_factor 1
    mla=MLAConfig(q_lora_rank=0, kv_lora_rank=512, qk_rope_head_dim=64,
                  qk_nope_head_dim=128, v_head_dim=128),
    param_dtype="bfloat16",
)
