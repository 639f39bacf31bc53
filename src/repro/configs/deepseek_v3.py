"""deepseek-v3 — multi-head latent attention with a q-LoRA, YaRN RoPE, and
a sigmoid-routed, group-limited MoE with one shared expert after three
dense layers. [hf:deepseek-ai/DeepSeek-V3 config.json; arXiv:2412.19437]

Published: hidden 7168, 61 layers (the first 3 dense, d_ff 18432), 128
heads, q_lora_rank 1536, kv_lora_rank 512, qk_nope/v head 128, qk_rope
head 64; 256 routed experts of width 2048, 8 per token, 1 shared expert;
sigmoid scores with a per-expert correction bias for selection, 8 groups
of 32 with the best 4 kept, gates renormalised and scaled by 2.5; YaRN
(factor 40 over 4096 positions, beta 32/1, mscale 1/1) on theta 10000;
vocabulary 129280, untied; RMSNorm eps 1e-6. The multi-token-prediction
layer is not part of this serving model.
"""
from repro.configs.base import ArchConfig, Family, MoEConfig, RopeScaling

CONFIG = ArchConfig(
    name="deepseek-v3",
    family=Family.MOE,
    num_layers=61, d_model=7168, num_heads=128, num_kv_heads=128,
    d_ff=18432, vocab_size=129280,
    head_dim=128,
    q_lora_rank=1536, kv_lora_rank=512, qk_rope_head_dim=64,
    rope_theta=10000.0,
    rope_scaling=RopeScaling(factor=40.0,
                             original_max_position_embeddings=4096,
                             beta_fast=32.0, beta_slow=1.0, mscale=1.0,
                             mscale_all_dim=1.0),
    first_k_dense=3,
    moe=MoEConfig(num_experts=256, top_k=8, d_ff=2048, shared_experts=1,
                  scoring="sigmoid", n_group=8, topk_group=4,
                  routed_scale=2.5),
    skip_shapes=("long_500k",),
    notes="MLA latent rows of 576 floats; dropless sigmoid-routed MoE",
)
