"""A tiny benchmark root for the ``smallthinker`` builder (a NoPE full layer
and sliding layers at 7 query heads a KV head, a router that reads the
block's input ahead of attention, ReLU-gated experts): the real harness,
builder, readers and reference under a manifest whose one configuration is
a two-layer decoder of width 64 (7/1 heads of 16; a full layer, then a
sliding one with a window of 32 on a 128-token sequence; 8 experts of which
4 are held, 2 a token), so that a whole run takes seconds. Beside the other
``bench_tiny*.py``, which it does not touch."""

import copy
import json
import os

from benchmarks.lib import manifest as mf

# The catalog row's ``config``, verbatim
# (/opt/skills/guides/model-configs/architectures.jsonl, row
# SmallThinker-21BA3B-Instruct).
CATALOG = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52,
    "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False,
    "vocab_size": 151936}

CONFIG = {
    "source": "tests only", "model_name": "smallthinker_tiny", "layers": 2,
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 7,
    "num_key_value_heads": 1, "head_dim": 16, "vocab_size": 96,
    "max_position_embeddings": 128, "moe_ffn_hidden_size": 32,
    "moe_num_primary_experts": 8, "moe_num_active_primary_experts": 2,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_local_experts": 4, "first_local_expert": 2,
    "sliding_window_layout": [0, 1], "rope_layout": [0, 1],
    "sliding_window_size": 32, "rms_norm_eps": 1e-06,
    "rope_theta": 1500000, "rope_scaling": None,
    "tie_word_embeddings": False, "initializer_range": 0.02,
    "builder": "smallthinker",
    "optimizer": {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-08, "weight_decay": 0.1, "clip_norm": 1.0,
                  "warmup_steps": 2000},
    "per_chip_batch": 1, "reference": {"micro_rows": 1, "q_block": 64},
}
JOB = {"kind": "closed_loop_training", "seq_len": 128, "tokens": "uniform",
       "pool_batches": 4}
CELL = "tiny-smallthinker.train-1chip"
# Set as PERF.md sets the chip's, from readings at THIS size on the CPU,
# seeds 1..12 sound and 1..8 the float8 control: loss_gap sound <= 1.6e-4,
# control 3.0e-4 to 1.7e-3 (the limit three times the sound largest; the
# control passes it on some seeds, as it may); grad_norm_gap sound <= 0.0081
# (next 0.0067), control >= 0.021, SiLU where ReLU belongs >= 0.29, the
# router fed the post-attention stream >= 0.12 (four seeds each);
# delta_norm_gap sound <= 0.0062, control >= 0.243, an unchanged state 1.0:
# the geometric middle.
LIMITS = {"steps": 2, "loss_gap": 0.0005, "grad_norm_gap": 0.015,
          "delta_norm_gap": 0.04}


def make_root(tmp_path, config=None) -> str:
    """Write the tiny manifest and its data files under ``tmp_path``."""
    manifest = copy.deepcopy(mf.load())
    manifest["configs"] = [{"name": "tiny-smallthinker",
                            "source": "tests only",
                            "file": "benchmarks/configs/tiny-smallthinker.json",
                            "reduced": [], "why": "tests"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-smallthinker",
                              "traffic": "train-1chip", "chips": 1,
                              "why": "tests"}]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    root = str(tmp_path)
    for sub in ("configs", "jobs", "limits"):
        os.makedirs(os.path.join(root, "benchmarks", sub), exist_ok=True)

    def write(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    write("BENCHMARK.json", manifest)
    write("benchmarks/configs/tiny-smallthinker.json", config or CONFIG)
    write("benchmarks/jobs/train-1chip.json", JOB)
    write(f"benchmarks/limits/{CELL}.json", LIMITS)
    return root
