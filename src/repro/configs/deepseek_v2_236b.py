"""DeepSeek-V2 236B — MLA attention + fine-grained MoE.

Assigned spec: 60L d_model=5120 128H (kv=128) d_ff=1536 vocab=102400,
MoE 160 experts top-6, MLA kv_lora=512, 2 shared experts.
[arXiv:2405.04434] — the first layer is dense at the release's width of
12288; the routed experts are 1536 wide and the 2 shared experts
together 2 x 1536. Gating is the release's softmax over all 160
experts, top-6 probabilities unnormalized, times routed_scaling_factor
16, dropless (the expert-parallel path, `moe_impl="ep"`, keeps its
capacity dispatch).

Not implemented of V2's published routing: device-limited routing
(topk_method group_limited_greedy: n_group 8 device groups, top-6
experts drawn from the topk_group 3 best groups) — the program takes the
plain greedy top-6 over all 160; the device-level and communication
balance losses beside the expert-level aux loss; token dropping at a
device-level capacity factor in training. Rope is the release's YaRN
(factor 40 over 4096 positions, mscale = mscale_all_dim = 0.707).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="deepseek-v2-236b",
    arch_type="moe",
    num_layers=60,
    d_model=5120,
    num_heads=128,
    num_kv_heads=128,
    head_dim=128,
    d_ff=1536,                  # routed expert width
    dense_d_ff=12288,           # the leading dense layer (release width)
    vocab_size=102400,
    attention="mla",
    kv_lora_rank=512,
    q_lora_rank=1536,
    rope_head_dim=64,
    yarn_factor=40.0,
    yarn_original_max_pos=4096,
    yarn_mscale=0.707,
    yarn_mscale_all_dim=0.707,
    num_experts=160,
    num_experts_per_tok=6,
    num_shared_experts=2,
    moe_layer_period=1,
    first_k_dense=1,
    router_scoring="softmax",
    norm_topk_prob=False,
    routed_scaling_factor=16.0,
    router_aux_coef=0.003,
    mlp_act="swiglu",
    source="arXiv:2405.04434",
)
