"""A tiny benchmark root for the ``sdar_moe`` builder: the real harness,
builder, readers and reference under a manifest whose one configuration is
a two-layer decoder of width 64 (4/2 heads of 16, 8 experts of which 4 are
held, 2 a row) trained under the block-diffusion objective on 64-token
sequences in blocks of 4 (128 rows a step), so that a whole run takes
seconds. Beside ``bench_tiny.py``, which it does not touch."""

import copy
import json
import os

from benchmarks.lib import manifest as mf

CONFIG = {
    "source": "tests only", "model_type": "sdar_moe", "layers": 2,
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
    "max_position_embeddings": 128, "num_experts": 8,
    "num_local_experts": 4, "first_local_expert": 2,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "intermediate_size": 96, "sliding_window": None,
    "norm_topk_prob": True, "rms_norm_eps": 1e-06, "rope_theta": 1000000,
    "block_length": 4, "initializer_range": 0.02, "builder": "sdar_moe",
    "optimizer": {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-08, "weight_decay": 0.1, "clip_norm": 1.0},
    "per_chip_batch": 1, "reference": {"micro_rows": 1, "q_block": 64},
}
JOB = {"kind": "closed_loop_training", "seq_len": 64, "tokens": "uniform",
       "pool_batches": 4}
CELL = "tiny-sdar.train-1chip"
# Set as PERF.md sets the chip's, from readings at THIS size on the CPU,
# seeds 1..12 sound and 1..6 the float8 control: loss_gap sound <= 6.5e-4,
# control 1.1e-3 to 9.3e-3 (the limit three times the sound largest; the
# control passes it on some seeds, as it may); grad_norm_gap sound <= 0.027
# (next 0.011), control >= 0.22; delta_norm_gap sound <= 0.027, control >=
# 0.70, an unchanged state 1.0. The control is far off here: 1 / t reaches
# hundreds on a block whose level is small, and float8 logits under such a
# weight move the head's and the last layer's gradients by their own size.
LIMITS = {"steps": 2, "loss_gap": 0.002, "grad_norm_gap": 0.08,
          "delta_norm_gap": 0.1}


def make_root(tmp_path, config=None) -> str:
    """Write the tiny manifest and its data files under ``tmp_path``."""
    manifest = copy.deepcopy(mf.load())
    manifest["configs"] = [{"name": "tiny-sdar", "source": "tests only",
                            "file": "benchmarks/configs/tiny-sdar.json",
                            "reduced": [], "why": "tests"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-sdar",
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
    write("benchmarks/configs/tiny-sdar.json", config or CONFIG)
    write("benchmarks/jobs/train-1chip.json", JOB)
    write(f"benchmarks/limits/{CELL}.json", LIMITS)
    return root
