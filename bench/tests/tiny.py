"""A tiny cell for the CPU tests: the qwen2-0.5b configuration cut to
reduced_config-like sizes (2 layers, d 64, vocab 512) under a churn mix of
8-request waves whose outputs are longer than their prompts (so a
fault in the decode path shows), in a temporary root laid out like a checkout."""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)

CONFIG: Dict = {
    "name": "tiny", "source": "test", "reduced": [], "model": "dense_decoder",
    "family": "dense", "citation": "test", "n_layers": 2, "d_model": 64,
    "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 128,
    "vocab_size": 512, "norm_eps": 1e-6, "tie_embeddings": True,
    "act": "silu", "glu": True, "dtype": "bfloat16",
    "attn": {"layer_pattern": ["global"], "sliding_window": 4096,
             "qkv_bias": True, "rope_theta": 1000000.0},
    "lora": {"rank": 4, "alpha": 8.0,
             "target_modules": ["q", "k", "v", "o", "up", "gate", "down"],
             "max_resident": 4, "n_adapters": 16},
    "correct": {"logit_gap_max": 0.25, "logit_gap_mean": 0.01},
}

MIX: Dict = {
    "wave_requests": 8, "n_adapters": None, "alpha": 1.0,
    "input_range": [4, 12], "output_range": [16, 32],
    "engine": {"n_slots": 4, "max_ctx": 48, "prompt_buckets": [16],
               "policy": "edgelora", "top_k": 3, "kv_backend": "dense",
               "lora_backend": "einsum", "prefill_batching": True,
               "async_swap": True},
}


def make_root(tmp: str, config: Optional[Dict] = None,
              mix: Optional[Dict] = None) -> str:
    """A temporary checkout root with BENCHMARK.json naming one cell,
    ``tiny.churn``; the harness code itself is imported from the repo."""
    os.makedirs(os.path.join(tmp, "bench", "configs"), exist_ok=True)
    os.makedirs(os.path.join(tmp, "bench", "traffic"), exist_ok=True)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        spec = json.load(f)
    spec["configs"] = [{"name": "tiny", "source": "test",
                        "file": "bench/configs/tiny.json", "reduced": [],
                        "why": "test"}]
    spec["workloads"] = [{"name": "tiny.churn", "config": "tiny",
                          "traffic": "churn", "chips": 1, "why": "test"}]
    for m in spec["per_layer"]:
        m.pop("workloads", None)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(tmp, "bench", "configs", "tiny.json"), "w") as f:
        json.dump(config or CONFIG, f)
    with open(os.path.join(tmp, "bench", "traffic", "churn.json"), "w") as f:
        json.dump(mix or MIX, f)
    return tmp
