"""The ``sdar_moe`` builder (Qwen3-MoE's block under the block-diffusion
objective: a noised and a clean copy of every sequence in one step) under
the real harness at a tiny size on the CPU
(tests/benchmark/bench_tiny_sdar.py): a sound run is correct, and the faults
the comparison exists to catch are not; the cell's files, FLOP and kernel
cost functions against hand-worked numbers; the new readers on hand-made
ops.
"""

import math
import types

import jax
import pytest

import horovod_tpu as hvd
from benchmarks.lib import (compare, flops_sdar, harness, kernels,
                            kernels_block_diffusion as kbd, kernels_window,
                            manifest as mf, peaks, reference_sdar, scopes)
from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

import bench_tiny_sdar as tiny

MANIFEST = mf.load()
CELL = "sdar-30b-a3b.train-8k-1chip"
SHARES = ("block_diff_attn_fwd_roofline", "block_diff_attn_bwd_roofline")
NEW_METRICS = ("attention.block_diff_ms", "diffusion.noise_ms") + SHARES
JOINED = ("step.forward_ms", "step.backward_ms", "step.optimizer_ms",
          "step.unscoped_pct", "head_loss.ms", "attention.ms",
          "attention.layout_ms", "moe_ffn.ms", "step.interval_p90_ms")


@pytest.fixture()
def session_mesh_restored():
    """The builder owns hvd.init/shutdown; hand the session its mesh back."""
    yield
    hvd.shutdown()
    hvd.init()


def _run(tmp_path, seed, lines):
    root = tiny.make_root(tmp_path)
    return harness.run_cell(tiny.CELL, seed=seed, seconds=0.3, trace=False,
                            root=root, devices=jax.devices()[:1],
                            log=lines.append)


def _row(lines, name):
    return next(ln for ln in lines if ln.startswith(f"[check] {name} "))


@pytest.mark.parametrize("seed", [1, 2147486001])
def test_sound_run_is_correct(session_mesh_restored, tmp_path, seed):
    lines = []
    result = _run(tmp_path, seed, lines)
    assert result["correct"] is True, "\n".join(lines)
    assert result["failed"] == 0 and result["attempted"] > 4
    assert {"tokens_per_s_per_chip", "setup_s"} <= set(result["metrics"])
    for name in compare.NUMBERS + ("non_finite_losses",
                                   "compilations_in_window"):
        assert " limit " in _row(lines, name) and "ok" in _row(lines, name)
    for kernel in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert "ok" in _row(lines, f"{kernel}_bd_in_program")
        assert " 0 limit ==0 ok" in _row(lines, f"{kernel}_in_program")


def _plain_causal(monkeypatch):
    """Attention over the 2 L rows under the plain causal mask: as fast or
    faster, and another objective."""
    import horovod_tpu.models.sparse_moe_decoder as model

    monkeypatch.setattr(
        model, "block_diffusion_attention",
        lambda q, k, v, block_length: model.causal_attention(q, k, v))


def _unweighted(monkeypatch):
    """The loss over the masked positions without the 1 / t weight."""
    real = hvd.block_diffusion_loss
    monkeypatch.setattr(
        hvd, "block_diffusion_loss",
        lambda h, head, tokens, masked, t: real(
            h, head, tokens, masked, jax.numpy.ones_like(t)))


def _same_noise_every_step(monkeypatch):
    """A step that never folds its count into the key: the second step's
    noise is the first's."""
    real = hvd.block_diffusion_noise
    monkeypatch.setattr(
        hvd, "block_diffusion_noise",
        lambda tokens, key, **kw: real(tokens, jax.random.key(0), **kw))


@pytest.mark.parametrize("fault, fails, seed", [
    (_plain_causal, ("grad_norm_gap",), 3),
    (_unweighted, ("loss_gap", "grad_norm_gap"), 4),
    (_same_noise_every_step, ("loss_gap",), 5)])
def test_a_fault_is_not_correct(session_mesh_restored, tmp_path,
                                monkeypatch, fault, fails, seed):
    fault(monkeypatch)
    lines = []
    result = _run(tmp_path, seed, lines)
    assert result["correct"] is False, "\n".join(lines)
    for name in fails:
        assert "FAIL" in _row(lines, name), "\n".join(lines)
    if fault is _plain_causal:   # the plain kernels' names give it away too
        assert "FAIL" in _row(lines, "hvd_flash_fwd_in_program")


@pytest.mark.parametrize("seed", [1, 3, 5])
def test_lower_precision_control_is_not_correct(session_mesh_restored, seed):
    """The control: the reference with float8 matmul operands (router
    included) put in the program's place. It has to fail a number of the
    cell, not each."""
    session = mf.load_module("builders", "sdar_moe").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    sound = session.reference(seed, tiny.LIMITS["steps"])
    low = session.reference(seed, tiny.LIMITS["steps"], precision="float8")
    verdict = {name: ok for name, _, _, ok, _ in
               compare.judge(low, sound, tiny.LIMITS)}
    assert verdict["grad_norm_gap"] is False
    assert verdict["delta_norm_gap"] is False


def test_the_second_steps_gap_is_split_and_its_faults_are_read(
        session_mesh_restored, tmp_path):
    """scripts/sdar_loss_gap.py (PERF.md section 6, PR 41): the second
    checked step's gap is its forward part plus its parameters part, the
    parameters part is what the positions carry, and the faults the loss is
    held against read far above a sound gap."""
    import importlib.util
    import json
    import os

    spec = importlib.util.spec_from_file_location(
        "sdar_loss_gap", os.path.join(mf.ROOT, "scripts", "sdar_loss_gap.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    root = tiny.make_root(tmp_path)
    assert script.main(["--workload", tiny.CELL, "--seeds", "2", "--root",
                        root, "--any-device", "--dir", root]) == 0
    with open(os.path.join(root, "loss_gap_2.json")) as f:
        row = json.load(f)
    signed = row["loss_program"][1] - row["loss_reference"][1]
    assert row["gap"][1] == pytest.approx(abs(signed))
    assert (row["step1_forward_part"] + row["step1_parameters_part"]
            == pytest.approx(signed, abs=2e-6))
    assert row["step1_part_abs_sum"] >= abs(row["step1_parameters_part"])
    assert row["first_order_sum"] == pytest.approx(
        row["step1_parameters_part"], rel=0.5, abs=2e-5)
    assert row["grad_norm_step0"] > 0 and row["grad_norm_step1"] > 0
    assert 0 <= row["leaf_groups"]["moe/router"]["moved_the_other_way"] < 0.5
    for reads in row["step1_loss_gap_under_a_fault"].values():
        assert reads > 50 * tiny.LIMITS["loss_gap"] > row["gap"][1]


def test_the_feed_draws_from_the_slice_less_the_mask_id(
        session_mesh_restored):
    session = mf.load_module("builders", "sdar_moe").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    session.place_inputs(7)
    assert session.sizes["mask_id"] == tiny.CONFIG["vocab_size"] - 1
    assert len(session.pool) == tiny.JOB["pool_batches"]
    for x0 in session.pool:
        assert x0.shape == (1, tiny.JOB["seq_len"])      # the last id is cut
        assert int(x0.max()) < session.sizes["mask_id"]
    assert session.tokens_per_step == tiny.JOB["seq_len"]   # DATA tokens
    assert session.kernel_shapes["block_diffusion_attention"] == dict(
        batch=1, seq=64, heads=4, kv_heads=2, head_dim=16, block=4,
        act_bytes=2)


def test_manifest_is_valid_and_the_cells_files_are_found():
    assert mf.validate(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert cell in MANIFEST["workloads"]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "sdar-30b-a3b", "train-8k-1chip")
    assert len(cell["why"]) <= 200
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    config = mf.config_of(MANIFEST, cell["config"])
    job = mf.job_of(cell["traffic"])
    assert (job["kind"], job["seq_len"], job["tokens"],
            job["pool_batches"]) == ("closed_loop_training", 8192,
                                     "uniform", 8)              # ISSUE 41
    assert (config["builder"], config["per_chip_batch"]) == ("sdar_moe", 1)
    # every number of the catalog row's config, the cut ones apart
    published = {
        "decoder_sparse_step": 1, "head_dim": 128, "hidden_size": 2048,
        "intermediate_size": 6144, "max_position_embeddings": 32768,
        "max_window_layers": 48, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 1000000}
    assert {k: config[k] for k in published} == published
    assert (config["hidden_act"], config["model_type"],
            config["attention_bias"], config["norm_topk_prob"],
            config["tie_word_embeddings"], config["use_sliding_window"],
            config["mlp_only_layers"], config["rope_scaling"],
            config["sliding_window"]) == (
        "silu", "sdar_moe", False, True, False, False, [], None, None)
    assert config["vocab_size"] == 18992 == 151936 // 8
    assert (config["layers"], config["num_local_experts"],
            config["first_local_expert"], config["block_length"]) == (
        6, 16, 0, 4)
    assert config["reduced"] == ["layers", "num_local_experts", "vocab_size"]
    assert set(config["reduced"]) <= set(config["departures"])
    for key, said in (("layers", "48"), ("num_local_experts", "128"),
                      ("vocab_size", "151,936")):
        assert said in config["departures"][key]
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "sdar-30b-a3b")
    assert (entry["reduced"], entry["source"]) == (config["reduced"],
                                                   config["source"])
    for key in ("assumed", "deployment", "memory", "catalog", "source"):
        assert config[key]
    for key in ("block_length", "noise", "eps", "rows", "mask", "loss",
                "mask_id", "block", "qk_norm", "rope", "router",
                "initializer_range", "precision", "remat"):
        assert config["assumed"][key]
    assert "64 chips" in config["deployment"]
    keye = mf.config_of(MANIFEST, "keye-vl2-30b-a3b")
    assert config["optimizer"] == keye["optimizer"]     # like for like
    limits = mf.limits_of(CELL)
    assert set(compare.NUMBERS) <= set(limits) and "set_from" in limits


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_for_the_new_cell(name):
    reader = mf.load_module("layers", name)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (entry["name"], entry["unit"], entry["layer"], entry["moves"]) \
        == (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES)
    assert entry["workloads"] == [CELL]
    assert entry["source"] == ("device_trace" if name.endswith("_roofline")
                               else "program_span")
    if hasattr(reader, "SCOPE"):
        assert reader.SCOPE in DEVICE_SCOPES


def test_the_new_cell_joins_the_nine_lists_and_no_pinned_one():
    """The lists a test of the accepted benchmark pins letter for letter
    (``test_bench_owners.py``, ``test_bench_afmoe.py``) are left as they
    are: PERF.md section 7 says which."""
    mine = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(JOINED) | set(NEW_METRICS) | {
        "setup.init_s", "setup.compile_s", "step.dispatch_ms",
        "step.device_busy_ms", "device.idle_pct",
        "device.peak_hbm_gb"} == mine
    for name in JOINED:
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
    e2e = {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"tokens_per_s_per_chip", "mfu_pct", "setup_s"}


def _sizes():
    return reference_sdar.sizes_from_config(
        mf.config_of(MANIFEST, "sdar-30b-a3b"))


def test_parameter_count_is_the_issues():
    """18,874,368 of q, k, v, o + 262,144 of router + 75,497,472 of 16
    experts + 4,352 of norms a layer; 77,791,232 of embedding and head:
    645,623,296, 10.33 GB at 16 bytes a parameter (ISSUE 41)."""
    s = _sizes()
    flat = jax.tree_util.tree_leaves(
        reference_sdar.param_shapes(s),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], tuple))
    total = sum(math.prod(shape) for shape, _ in flat)
    layer = 18_874_368 + 262_144 + 75_497_472 + 4_352
    assert layer == 94_638_336
    assert total == 6 * layer + 77_791_232 + 2_048 == 645_623_296
    assert flops_sdar.parameter_count(s) == total
    assert round(total * 16 / 1e9, 2) == 10.33
    # the keye cell's count less its indexer
    assert total == 659_189_248 - 6 * 2_260_992


def test_train_flops_are_35_8_tflop_a_step():
    s, L = _sizes(), 8192
    assert flops_sdar.layer_matmul_weights(s) == 23_855_104
    assert flops_sdar.visible_pairs(L, 4) == L * L + 4 * L
    per_token = flops_sdar.train_flops_per_token(s, L)
    assert per_token == 6 * (2 * 6 * 23_855_104 + 12 * 32 * 128 * (L + 4)) \
        + 6 * 18_992 * 2048
    assert round(per_token * L / 1e12, 1) == 35.8
    attention = 6 * 12 * 32 * 128 * (L + 4) * L
    assert 0.54 < attention / (per_token * L) < 0.56     # "55%"


def test_block_diffusion_costs_by_hand():
    shape = dict(batch=1, seq=8192, heads=32, kv_heads=4, head_dim=128,
                 block=4, act_bytes=2)
    pairs = 8192 * 8192 + 8192 * 4
    assert kbd.visible_pairs(8192, 4) == pairs
    q, kv, rows = (16384 * 32 * 128 * 2, 16384 * 4 * 128 * 2,
                   32 * 16384 * 4)
    flops, nbytes = kbd.attn_fwd_cost(**shape)
    assert flops == 32 * pairs * 4 * 128
    assert nbytes == 2 * q + 2 * kv + rows
    assert flops == pytest.approx(1.10e12, rel=5e-3)       # ISSUE 41
    flops, nbytes = kbd.attn_bwd_cost(**shape)
    assert flops == 32 * pairs * 8 * 128
    assert nbytes == 3 * q + 4 * kv + 2 * rows
    # the pairs of two causal calls over L keys, and the blocks' own
    causal = kernels_window.visible_pairs(8192)
    assert pairs == 2 * causal + 8192 * 3
    # the FLOPs file and the kernel cost file count the same pairs
    per_token = (kbd.attn_fwd_cost(**shape)[0]
                 + kbd.attn_bwd_cost(**shape)[0]) / 8192
    assert per_token == 12 * 32 * 128 * (8192 + 4)
    peak = peaks.for_device_kind("TPU v5 lite")
    least, bound = kernels.roofline(*kbd.attn_fwd_cost(**shape), peak)
    assert bound == "flops" and least == pytest.approx(5.58e-3, rel=5e-3)


def test_structure_check_counts_whole_names():
    from benchmarks.builders import sdar_moe

    text = ("%hvd_flash_fwd_bd.1 = custom-call(), name=hvd_flash_fwd_bd\n"
            "%hvd_flash_fwd_win.2 %hvd_flash_bwd_dq_bd.3 hvd_flash_fwd.4")
    assert sdar_moe.whole_name_count(text, "hvd_flash_fwd_bd") == 2
    assert sdar_moe.whole_name_count(text, "hvd_flash_fwd") == 1
    assert sdar_moe.whole_name_count(text, "hvd_flash_bwd_dq") == 0


# -- the new readers on hand-made ops -----------------------------------------

GRAD = "jit(spmd)/shard_map/hvd.grad/"
BACK = GRAD + "transpose(hvd.grad)/"
ATTN = "jvp(SparseMoEDecoder)/h0/attn/hvd.flash_attention/"
BD = ATTN + "hvd.flash_block_diffusion/"
NOISE = "jit(spmd)/shard_map/hvd.block_diffusion_noise/"
SHAPES = {"block_diffusion_attention": dict(
    batch=1, seq=8192, heads=32, kv_heads=4, head_dim=128, block=4,
    act_bytes=2)}


def _kernel(name, n, start, dur, path):
    return (f"%{name}.{n} = (bf16[1,16384,4096]) custom-call()", start, dur,
            path + name + "/pallas_call")


# One step of 60 ms: the noise (0.2 ms), the forward kernel (12), a layout
# op around it (0.5), then the dq and dk/dv kernels (11, 14) and the delta
# rows (0.3).
OPS = [
    ("%fusion.1 = s32[1,16384] fusion()", 0.0000, 0.0002,
     NOISE + "threefry2x32"),
    _kernel("hvd_flash_fwd_bd", 1, 0.0010, 0.012, GRAD + BD),
    ("%copy.1 = bf16[1,16384,4096] copy()", 0.0140, 0.0005,
     GRAD + BD + "reshape"),
    ("%fusion.2 = f32[32,1,16384] fusion()", 0.0150, 0.0003,
     BACK + BD + "dot_general"),
    _kernel("hvd_flash_bwd_dq_bd", 1, 0.0160, 0.011, BACK + BD),
    _kernel("hvd_flash_bwd_dkv_bd", 1, 0.0280, 0.014, BACK + BD),
]


def _traced_run(ops, shapes=SHAPES):
    run = types.SimpleNamespace(
        trace=object(), peak=peaks.for_device_kind("TPU v5 lite"),
        kernel_shapes=shapes, notes=[])
    run.note = run.notes.append
    run.scoped_ops = scopes.ScopedOps(sorted(ops, key=lambda o: o[1]),
                                      [(0.0, 0.060)])
    return run


def test_new_readers_on_the_hand_made_step():
    run = _traced_run(OPS)
    got = {name: mf.load_module("layers", name).read(run)
           for name in NEW_METRICS}
    shape = SHAPES["block_diffusion_attention"]
    fwd = kernels.roofline(*kbd.attn_fwd_cost(**shape), run.peak)[0]
    bwd = kernels.roofline(*kbd.attn_bwd_cost(**shape), run.peak)[0]
    assert got == pytest.approx({
        "attention.block_diff_ms": 37.8, "diffusion.noise_ms": 0.2,
        "block_diff_attn_fwd_roofline": 100 * fwd / 0.012,
        "block_diff_attn_bwd_roofline": 100 * bwd / 0.025})
    assert all(0 < got[n] < 100 for n in SHARES)
    assert any("visible pairs a head" in line for line in run.notes)
    # the shared readers take the new kernels for kernels
    assert mf.load_module("layers", "attention.ms").read(run) == \
        pytest.approx(37.8)
    assert mf.load_module("layers", "attention.layout_ms").read(run) == \
        pytest.approx(0.8)


def test_new_readers_read_nothing_from_a_program_without_them():
    """The parent's program under this PR's benchmark files: plain flash
    kernels, no such scopes, no such ``kernel_shapes`` entry; nothing is
    reported and nothing raises."""
    old = [(op[0].replace("_bd", ""), op[1], op[2],
            op[3].replace("hvd.flash_block_diffusion/", "").replace(
                "_bd", "").replace("hvd.block_diffusion_noise/", ""))
           for op in OPS]
    run = _traced_run(old, shapes={"gqa_attention": dict(
        batch=1, seq=8192, heads=32, kv_heads=4, head_dim=128, window=None,
        act_bytes=2)})
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(run) is None, name
    # a windowed or plain kernel is not a block-diffusion kernel by prefix
    run = _traced_run(old)
    for name in SHARES:
        assert mf.load_module("layers", name).read(run) is None, name
    untraced = types.SimpleNamespace(trace=None, peak=None, kernel_shapes={},
                                     note=lambda text: None)
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(untraced) is None, name
