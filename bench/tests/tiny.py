"""A cell at CPU size for the benchmark's own tests: the qwen2.5-3b code
path with tiny widths, a short chat mix and loose latency limits."""
from __future__ import annotations

import copy

from bench.harness import Cell

CONFIG = {
    "registry": "qwen2.5-3b", "reference": "dense_decoder",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "vocab_size": 512,
    "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "attention_bias": True,
    "engine": {"max_batch": 4, "max_seq": 256, "page_size": 16},
}
MIX = {"kind": "sessions", "shape_seed": 3, "shuffle_block": 2,
       "lead_s": 10.0, "system_prompt_len": 16,
       "mean_rounds": 2.0, "mean_think_s": 1.0, "median_turn_len": 16,
       "turn_len_sigma": 0.8, "max_prompt_frac": 0.5, "mean_output_len": 8.0,
       "max_output_len": 16}
PARAMS = {"rate_per_s": 1.0, "ttft_limit_s": 30.0, "itl_limit_s": 30.0,
          "engine": {"prefill_chunk_tokens": 64, "incremental_prefill": True},
          "warm_up": {"prompt_len": 100, "traffic_s": 1.0},
          "check": {"tokens": 32, "max_requests": 4, "min_tokens": 8,
                    "max_gap_limit": 0.05}}
BENCH = {"end_to_end": [], "per_layer": []}
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
          "peaks": {"bf16_flops": 1e12, "hbm_bytes_s": 1e11}}


def cell() -> Cell:
    return Cell("tiny.chat", {"chips": 1}, copy.deepcopy(CONFIG),
                copy.deepcopy(MIX), copy.deepcopy(PARAMS))
