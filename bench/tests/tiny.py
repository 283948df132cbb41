"""A tiny configuration and mixes for driving the harness on the CPU."""
from __future__ import annotations

import copy
import json
import os

from bench import harness

ROOT = harness.ROOT

MODEL = {
    "name": "tiny", "arch_type": "dense", "n_layers": 2, "d_model": 64, "n_heads": 4,
    "n_kv_heads": 2, "d_head": 16, "d_ff": 128, "vocab_size": 512, "qkv_bias": True,
    "qk_norm": True, "tie_embeddings": True, "rope_theta": 1e6, "norm_eps": 1e-6,
    "param_dtype": "bfloat16", "compute_dtype": "bfloat16",
}
SYNAPSE = {"n_landmarks": 16, "window": 8, "n_inject": 4, "alpha": 0.5,
           "score_ema": 0.99, "coverage_cap": 4.0}
ENGINE = {"n_main": 2, "max_side": 8, "main_capacity": 512, "side_max_steps": 16,
          "side_prompt_cap": 64, "sync_every": 8, "temperature": 0.8, "side_greedy": True,
          "theta": -1.0, "max_queue": 64, "synapse": SYNAPSE}
LIMITS = {"token_gap": 0.05, "spawn_gap": 0.002, "thought_mismatch": 0}
COUNCIL = {"kind": "council", "sessions": 2, "prompt_bytes": 256, "tags_per_prompt": 4,
           "payload_bytes": [8, 16], "max_new_tokens": 24, "greedy_share": 0.5,
           "stagger_steps": 2, "preroll_steps": 6, "trace_s": 1.0, "engine": ENGINE,
           "check": {"rivers": 2, "sides": 3}}


def cell(mix=COUNCIL, limits=None) -> harness.Cell:
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    return harness.Cell(
        workload={"name": "tiny", "chips": 1, "config": "tiny", "traffic": "tiny"},
        config={"family": "qwen_dense", "model_config": copy.deepcopy(MODEL)},
        mix=copy.deepcopy(mix),
        limits=limits or dict(LIMITS),
        end_to_end=bench["end_to_end"],
        per_layer=[],
    )


def run(seed: int, seconds: float = 2.0, mix=COUNCIL, limits=None) -> harness.Run:
    r = harness.Run(cell(mix, limits), seed, seconds, False, require_tpu=False)
    r.result = r.execute()
    return r
