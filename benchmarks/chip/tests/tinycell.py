"""Shared by the benchmark's CPU tests: import paths, and a patch that runs
a cell at a size the CPU holds (two layers of 256 wide, two slots, short
prompts, a window of a few seconds)."""
import pathlib
import sys

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

SMALL_MODEL = {"hidden_size": 256, "intermediate_size": 512,
               "num_attention_heads": 2, "num_key_value_heads": 1,
               "head_dim": 128, "num_hidden_layers": 2, "vocab_size": 1024,
               "rope_theta": 1e6, "rms_norm_eps": 1e-6}
SMALL_OVERRIDES = {"num_layers": 2, "d_model": 256, "num_heads": 2,
                   "num_kv_heads": 1, "head_dim": 128, "d_ff": 512,
                   "vocab_size": 1024, "rope_theta": 1e6}


def tiny_patch(limit: float) -> dict:
    return {"config": {"cfg_overrides": SMALL_OVERRIDES, "model": SMALL_MODEL,
                       "serve": {"batch_slots": 2, "s_max": 256,
                                 "prefill_chunk_tokens": 32}},
            "mix": {"prompt_len": {"median": 40, "sigma": 0.5, "min": 8,
                                   "max": 120},
                    "gen_len": {"median": 8, "sigma": 0.5, "min": 4,
                                "max": 24},
                    "pre_roll_s": 0.5, "drain_cap_s": 10, "rate_rps": 3,
                    "fill_cap_s": 10, "n_requests": 64, "backlog": 2},
            "workload": {"check": {"min_tokens": 32, "max_requests": 4,
                                   "max_logit_gap": limit}}}
