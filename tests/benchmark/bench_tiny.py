"""A tiny benchmark root for the CPU tests: the real harness, builder,
readers and reference under a manifest whose one configuration is a
two-layer GPT-2 of width 64, so that a whole run takes seconds."""

import copy
import json
import os

from benchmarks.lib import manifest as mf

CONFIG = {
    "source": "tests only", "model_type": "gpt2",
    "activation_function": "gelu_new", "n_layer": 2, "n_embd": 64,
    "n_head": 4, "n_inner": None, "n_positions": 128, "n_ctx": 128,
    "vocab_size": 256, "initializer_range": 0.02,
    "layer_norm_epsilon": 1e-06, "attn_pdrop": 0.0, "embd_pdrop": 0.0,
    "resid_pdrop": 0.0, "builder": "gpt_decoder",
    "optimizer": {"name": "adamw", "lr": 0.0003, "b1": 0.9, "b2": 0.95,
                  "eps": 1e-08, "weight_decay": 0.1, "clip_norm": 1.0},
    "per_chip_batch": 2, "reference": {"micro_rows": 2},
}
SIZES = {"layers": 2, "d_model": 64, "heads": 4, "d_ff": 256, "vocab": 256,
         "positions": 128, "ln_eps": 1e-06, "init_std": 0.02}
JOB = {"kind": "closed_loop_training", "seq_len": 128, "tokens": "uniform",
       "pool_batches": 4}
# Set as PERF.md sets the chip's, from readings at THIS size on the CPU mesh,
# seeds 1..12 sound and 1..4 float8 control, one and four devices:
# loss_gap sound <= 1.3e-4 (limit three times that); grad_norm_gap sound <=
# 3.7e-3, control >= 2.1e-2 (limit between); delta_norm_gap sound <= 0.20,
# all in the key third of a qkv/bias (limit three times that; an unchanged
# state reads 1.0).
LIMITS = {"steps": 3, "loss_gap": 4e-4, "grad_norm_gap": 9e-3,
          "delta_norm_gap": 0.6}
CELLS = {"tiny.train-1chip": 1, "tiny.train-4chip": 4}


def make_root(tmp_path) -> str:
    """Write the tiny manifest and its data files under ``tmp_path``."""
    real = mf.load()
    manifest = copy.deepcopy(real)
    manifest["configs"] = [{"name": "tiny", "source": "tests only",
                            "file": "benchmarks/configs/tiny.json",
                            "reduced": [], "why": "tests"}]
    manifest["workloads"] = [
        {"name": name, "config": "tiny", "traffic": name.split(".")[1],
         "chips": chips, "why": "tests"} for name, chips in CELLS.items()]
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        m.pop("workloads", None)
    root = str(tmp_path)
    for sub in ("configs", "jobs", "limits"):
        os.makedirs(os.path.join(root, "benchmarks", sub), exist_ok=True)

    def write(rel, obj):
        with open(os.path.join(root, rel), "w") as f:
            json.dump(obj, f)

    write("BENCHMARK.json", manifest)
    write("benchmarks/configs/tiny.json", CONFIG)
    for name in CELLS:
        write(f"benchmarks/jobs/{name.split('.')[1]}.json", JOB)
        write(f"benchmarks/limits/{name}.json", LIMITS)
    return root
