"""DeepSeek-V2-Lite — MLA attention with YaRN rope + fine-grained MoE.

Published config (https://huggingface.co/deepseek-ai/DeepSeek-V2-Lite,
config.json; arXiv:2405.04434): 27L d_model=2048 16H, MLA without a
query latent (q_lora_rank null), kv_lora_rank 512, qk_nope_head_dim 128,
qk_rope_head_dim 64, v_head_dim 128; first layer dense with
intermediate_size 10944, the other 26 MoE: 64 routed experts of
moe_intermediate_size 1408, 6 per token (softmax scoring, greedy top-k,
norm_topk_prob false, routed_scaling_factor 1) plus 2 shared experts;
YaRN rope (factor 40 over original_max_position_embeddings 4096,
beta_fast 32, beta_slow 1, mscale = mscale_all_dim = 0.707), rope_theta
1e4; vocab 102400, untied; rms_norm_eps 1e-6. The aux-loss coefficient
(aux_loss_alpha 0.001, seq_aux) is the release's.
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-lite",
    arch_type="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    head_dim=128,               # qk_nope_head_dim = v_head_dim
    d_ff=1408,                  # routed expert width
    dense_d_ff=10944,           # the leading dense layer's width
    vocab_size=102400,
    attention="mla",
    kv_lora_rank=512,
    q_lora_rank=0,
    rope_head_dim=64,
    rope_theta=1e4,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_beta_fast=32.0,
    yarn_beta_slow=1.0,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    num_experts=64,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_layer_period=1,
    first_k_dense=1,
    router_scoring="softmax",
    norm_topk_prob=False,
    routed_scaling_factor=1.0,
    router_aux_coef=0.001,
    norm_eps=1e-6,
    mlp_act="swiglu",
    source="hf:deepseek-ai/DeepSeek-V2-Lite config.json; arXiv:2405.04434",
)
