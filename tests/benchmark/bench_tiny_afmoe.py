"""A tiny benchmark root for the ``afmoe`` builder (window and full
grouped-KV attention, a dense layer, sigmoid-routed experts beside a shared
one, the balancing bias as state): the real harness, builder, readers and
reference under a manifest whose one configuration is a three-layer decoder
of width 64 (4/2 heads of 16; a dense sliding layer, a routed sliding layer
and a routed full one; a window of 32 on a 128-token sequence; 8 experts of
which 4 are held, 2 a token), so that a whole run takes seconds. Beside
``bench_tiny.py`` and ``bench_tiny_sparse.py``, which it does not touch."""

import copy
import json
import os

from benchmarks.lib import manifest as mf

CONFIG = {
    "source": "tests only", "model_type": "afmoe", "layers": 3,
    "num_hidden_layers": 3, "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 96,
    "max_position_embeddings": 128, "intermediate_size": 96,
    "layer_types": ["sliding_attention", "sliding_attention",
                    "full_attention"],
    "sliding_window": 32, "num_dense_layers": 1, "num_experts": 8,
    "num_local_experts": 4, "first_local_expert": 2,
    "num_experts_per_tok": 2, "moe_intermediate_size": 32,
    "num_shared_experts": 1, "score_func": "sigmoid", "route_norm": True,
    "route_scale": 2.826, "load_balance_coeff": 0.001, "n_group": 1,
    "topk_group": 1, "mup_enabled": True, "rms_norm_eps": 1e-05,
    "rope_theta": 10000, "initializer_range": 0.02, "builder": "afmoe",
    "optimizer": {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-08, "weight_decay": 0.1, "clip_norm": 1.0},
    "per_chip_batch": 1, "reference": {"micro_rows": 1, "q_block": 64},
}
JOB = {"kind": "closed_loop_training", "seq_len": 128, "tokens": "uniform",
       "pool_batches": 4}
CELL = "tiny-afmoe.train-1chip"
# Set as PERF.md sets the chip's, from readings at THIS size on the CPU
# (seeds 1..12 sound, 1..10 the control, 1..4 each fault): loss_gap sound <=
# 2.7e-3 (limit three times that; no fault moves it much); grad_norm_gap
# sound <= 0.0373, the float8 control >= 0.050 on nine seeds of ten (0.038
# on seed 4, which delta_norm_gap catches at 0.033), a window on the full
# layer >= 0.172, rotary position on it >= 0.068, no output gate >= 0.365;
# delta_norm_gap sound <= 0.0244, no output gate 0.997 (the gate's weights
# never move), a frozen bias >= 0.193 (its leaves read 0 where the
# reference's moved). At 128 tokens the control stands a third above the
# sound largest, so the limits sit close under it; the chip's cell has its
# own readings.
LIMITS = {"steps": 2, "loss_gap": 0.008, "grad_norm_gap": 0.045,
          "delta_norm_gap": 0.03}


def make_root(tmp_path, config=None) -> str:
    """Write the tiny manifest and its data files under ``tmp_path``."""
    manifest = copy.deepcopy(mf.load())
    manifest["configs"] = [{"name": "tiny-afmoe", "source": "tests only",
                            "file": "benchmarks/configs/tiny-afmoe.json",
                            "reduced": [], "why": "tests"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-afmoe",
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
    write("benchmarks/configs/tiny-afmoe.json", config or CONFIG)
    write("benchmarks/jobs/train-1chip.json", JOB)
    write(f"benchmarks/limits/{CELL}.json", LIMITS)
    return root
