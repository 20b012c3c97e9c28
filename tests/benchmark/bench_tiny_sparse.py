"""A tiny benchmark root for the sparse-attention / routed-experts builder:
the real harness, builder, readers and reference under a manifest whose one
configuration is a two-layer decoder of width 64 (4/2 heads of 16, 8
experts of which 4 are held, 2 a token, an indexer of 2 x 8 keeping 16 keys
of a 128-token sequence), so that a whole run takes seconds. Beside
``bench_tiny.py``, which it does not touch."""

import copy
import json
import os

from benchmarks.lib import manifest as mf

CONFIG = {
    "source": "tests only", "model_type": "KeyeVL2", "layers": 2,
    "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
    "max_position_embeddings": 128, "num_experts": 8,
    "num_local_experts": 4, "first_local_expert": 2,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "norm_topk_prob": True, "rms_norm_eps": 1e-06, "rope_theta": 10000000,
    "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                  "indexer_num_kv_heads": 1, "topk": 16},
    "initializer_range": 0.02, "builder": "sparse_moe_decoder",
    "optimizer": {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-08, "weight_decay": 0.1, "clip_norm": 1.0},
    "per_chip_batch": 1, "reference": {"micro_rows": 1, "q_block": 64},
}
JOB = {"kind": "closed_loop_training", "seq_len": 128, "tokens": "uniform",
       "pool_batches": 4}
CELL = "tiny-sparse.train-1chip"
# Set as PERF.md sets the chip's, from readings at THIS size on the CPU,
# seeds 1..12 sound, 1..4 each fault and the control: loss_gap sound <=
# 4.1e-3 (limit three times that; no fault moves it); grad_norm_gap sound
# <= 0.048 (next 0.041), the float8 control >= 0.056, a held expert's
# output zeroed >= 0.112, a selection of topk/2 >= 0.127; delta_norm_gap
# sound <= 0.026, the control >= 0.033, the zeroed expert >= 0.135, an
# unchanged state 1.0. With every matrix at the family's 0.02 the float8
# control stands only a sixth above the sound largest at this size (128
# tokens: the backward's cotangents are large enough for float8 to hold),
# so the limits sit close under it; the chip's cell has its own readings.
LIMITS = {"steps": 2, "loss_gap": 0.012, "grad_norm_gap": 0.052,
          "delta_norm_gap": 0.03}


def make_root(tmp_path, config=None) -> str:
    """Write the tiny manifest and its data files under ``tmp_path``."""
    manifest = copy.deepcopy(mf.load())
    manifest["configs"] = [{"name": "tiny-sparse", "source": "tests only",
                            "file": "benchmarks/configs/tiny-sparse.json",
                            "reduced": [], "why": "tests"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-sparse",
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
    write("benchmarks/configs/tiny-sparse.json", config or CONFIG)
    write("benchmarks/jobs/train-1chip.json", JOB)
    write(f"benchmarks/limits/{CELL}.json", LIMITS)
    return root
