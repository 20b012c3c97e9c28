"""A tiny benchmark root for the ``nemotron_h`` builder (one sublayer a
layer by ``hybrid_override_pattern``: Mamba-2 mixers through the chunked
scan, an attention layer with no position, sigmoid-routed two-matrix
``relu2`` experts in a latent beside a shared expert, the routers' biases as
state): the real harness, builder, readers and reference under a manifest
whose one configuration is five layers ``MEM*E`` of width 64 (4 Mamba heads
of 8 over 2 groups, 16 states, chunks of 16 on a 64-token sequence; 4 / 2
attention heads of 16; 16 experts of which 4 are held, 3 a token, in a
latent of 32), so that a whole run takes seconds. Beside the other
``bench_tiny*.py``, which it does not touch."""

import copy
import json
import os

from benchmarks.lib import manifest as mf

CONFIG = {
    "source": "tests only", "model_type": "nemotron_h",
    "hybrid_override_pattern": "MEM*EME", "num_hidden_layers": 7,
    "layers": [0, 1, 2, 3, 4], "hidden_size": 64,
    "layer_norm_epsilon": 1e-05, "mamba_num_heads": 4, "mamba_head_dim": 8,
    "n_groups": 2, "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 16,
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 0.0001,
    "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "n_routed_experts": 16, "num_local_experts": 4, "first_local_expert": 4,
    "num_experts_per_tok": 3, "moe_intermediate_size": 48,
    "moe_latent_size": 32, "moe_shared_expert_intermediate_size": 96,
    "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 5,
    "load_balance_coeff": 0.001, "vocab_size": 96,
    "max_position_embeddings": 64, "tie_word_embeddings": False,
    "initializer_range": 0.1, "builder": "nemotron_h",
    "optimizer": {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-08, "weight_decay": 0.1, "clip_norm": 1.0,
                  "warmup_steps": 2000},
    "per_chip_batch": 1, "reference": {"micro_rows": 1, "q_block": 32},
}
JOB = {"kind": "closed_loop_training", "seq_len": 64, "tokens": "uniform",
       "pool_batches": 4}
CELL = "tiny-nemotron-h.train-1chip"
# Set as PERF.md sets the chip's, from readings at THIS size on the CPU
# (initializer_range 0.1: at width 64 normal(0.02) weights through a squared
# ReLU and two projections leave the routed experts a thousandth of the
# shared one, and no compared number sees them), seeds 1..8 and two large
# ones sound, 1..6 the float8 control: loss_gap sound <= 5.4e-3, control
# 3.0e-3 to 2.6e-2 (the control passes it on some seeds, as it may);
# grad_norm_gap sound <= 0.045 (a Mamba layer's Dskip or convolution bias,
# 4 or 96 entries summed over every token in bfloat16; next 0.032), control
# 0.052 to 0.132, gates left unscaled (routed_scaling_factor 1) >= 0.79,
# gates left un-normalised >= 0.88; delta_norm_gap sound <= 0.060 (a
# router's bias where one token-choice falls the other side of the mean),
# an unchanged state 1.0.
LIMITS = {"steps": 2, "loss_gap": 0.012, "grad_norm_gap": 0.06,
          "delta_norm_gap": 0.25}


def make_root(tmp_path, config=None) -> str:
    """Write the tiny manifest and its data files under ``tmp_path``."""
    manifest = copy.deepcopy(mf.load())
    manifest["configs"] = [{"name": "tiny-nemotron-h",
                            "source": "tests only",
                            "file": "benchmarks/configs/tiny-nemotron-h.json",
                            "reduced": [], "why": "tests"}]
    manifest["workloads"] = [{"name": CELL, "config": "tiny-nemotron-h",
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
    write("benchmarks/configs/tiny-nemotron-h.json", config or CONFIG)
    write("benchmarks/jobs/train-1chip.json", JOB)
    write(f"benchmarks/limits/{CELL}.json", LIMITS)
    return root
