"""Shared by the benchmark's DeepSeek-V3 CPU tests: the cell's
configuration at a size the CPU holds, with every mechanism on (query
LoRA, YaRN, sigmoid group-limited routing with a correction bias, a
shared expert, a leading dense layer, and a held share of the experts)."""
import tinycell  # noqa: F401  (import paths)

SMALL_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 32, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4,
    "held_experts": {"first": 4, "count": 4, "router_outputs": 16},
    "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "routed_scaling_factor": 2.5, "vocab_size": 512,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 32, "type": "yarn"}}
SMALL_OVERRIDES = {
    "num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
    "head_dim": 16, "d_ff": 128, "vocab_size": 512, "q_lora_rank": 32,
    "kv_lora_rank": 32, "qk_rope_head_dim": 8, "first_k_dense": 1,
    "rope_scaling": {"original_max_position_embeddings": 32},
    "moe": {"num_experts": 16, "top_k": 4, "d_ff": 32, "n_group": 4,
            "topk_group": 2, "held": [4, 4]}}


def tiny_patch(limit: float) -> dict:
    return {"config": {"cfg_overrides": SMALL_OVERRIDES, "model": SMALL_MODEL,
                       "serve": {"batch_slots": 2, "s_max": 256,
                                 "prefill_chunk_tokens": 32}},
            "mix": {"prompt_len": {"median": 40, "sigma": 0.5, "min": 8,
                                   "max": 120},
                    "gen_len": {"median": 24, "sigma": 0.5, "min": 8,
                                "max": 48},
                    "fill_cap_s": 10, "n_requests": 400, "backlog": 2},
            "workload": {"check": {"min_tokens": 32, "max_requests": 4,
                                   "max_logit_gap": limit}}}
