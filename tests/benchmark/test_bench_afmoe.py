"""The ``afmoe`` builder (window and full grouped-KV attention, sigmoid
routed experts beside a shared one, the balancing bias as state) under the
real harness at a tiny size on the CPU (tests/benchmark/bench_tiny_afmoe.py):
a sound run is correct, and the faults the comparison exists to catch are
not; the cell's files, FLOP and kernel cost functions against brute-force
counts; the new readers on hand-made ops.
"""

import math
import types

import jax
import numpy as np
import pytest

import horovod_tpu as hvd
from benchmarks.lib import (compare, flops_afmoe, harness, kernels,
                            kernels_window, manifest as mf, peaks,
                            reference_afmoe, scopes)

import bench_tiny_afmoe as tiny

MANIFEST = mf.load()
CELL = "trinity-mini.train-8k-1chip"
NEW_METRICS = ("window_attn_fwd_roofline", "window_attn_bwd_roofline",
               "gqa_attn_fwd_roofline", "gqa_attn_bwd_roofline",
               "attention.window_ms", "shared_expert.ms", "router_bias.ms")


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


@pytest.mark.parametrize("seed", [1, 8, 2147486001])
def test_sound_run_is_correct(session_mesh_restored, tmp_path, seed):
    lines = []
    result = _run(tmp_path, seed, lines)
    assert result["correct"] is True, "\n".join(lines)
    assert result["failed"] == 0 and result["attempted"] > 4
    assert {"tokens_per_s_per_chip", "setup_s"} <= set(result["metrics"])
    for name in compare.NUMBERS + ("non_finite_losses",
                                   "compilations_in_window",
                                   "router_bias_moved"):
        assert " limit " in _row(lines, name) and "ok" in _row(lines, name)
    for kernel in ("hvd_flash_fwd_win", "hvd_flash_bwd_dq_win",
                   "hvd_flash_bwd_dkv_win", "hvd_flash_fwd",
                   "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert "ok" in _row(lines, f"{kernel}_in_program")


def _faulty(monkeypatch, fault):
    import horovod_tpu.models as models
    import horovod_tpu.models.sparse_moe_decoder as model

    if fault == "window_on_the_full_layer":
        real = model._flash.flash_attention
        monkeypatch.setattr(
            model._flash, "flash_attention",
            lambda q, k, v, *, causal, window: real(
                q, k, v, causal=causal,
                window=tiny.CONFIG["sliding_window"]))
    elif fault == "rope_on_the_full_layer":
        real = model.SparseMoEConfig.from_dict.__func__
        monkeypatch.setattr(
            model.SparseMoEConfig, "from_dict", classmethod(
                lambda cls, cfg, **kw: real(
                    cls, cfg, **{**kw, "rope_layers": "all"})))
    elif fault == "no_output_gate":
        monkeypatch.setattr(model, "_output_gate", lambda o, g: o)
    elif fault == "frozen_bias":
        monkeypatch.setattr(models, "update_router_biases",
                            lambda biases, loads, **kw: biases)
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault, caught_by", [
    ("window_on_the_full_layer", "grad_norm_gap"),
    ("rope_on_the_full_layer", "grad_norm_gap"),
    ("no_output_gate", "delta_norm_gap"),
    ("frozen_bias", "router_bias_moved")])
def test_a_fault_is_not_correct(session_mesh_restored, tmp_path,
                                monkeypatch, fault, caught_by):
    """Each is as fast as the sound step or faster and hardly moves the
    loss: the full layer attending a window only, position where the
    family has none, attention's output ungated (the gate's weights then
    never move), a balancing bias that stays where it started (its leaves
    of ``delta_norm`` read 0 and the structure row says so)."""
    _faulty(monkeypatch, fault)
    lines = []
    result = _run(tmp_path, 1, lines)
    assert result["correct"] is False, "\n".join(lines)
    assert "FAIL" in _row(lines, caught_by)
    assert "ok" in _row(lines, "loss_gap")
    if fault == "frozen_bias":
        assert "FAIL" in _row(lines, "delta_norm_gap")
        assert "moe/bias" in _row(lines, "delta_norm_gap")


@pytest.mark.parametrize("seed", [1, 2, 5])
def test_lower_precision_control_is_not_correct(session_mesh_restored, seed):
    """The control: the reference with float8 matmul operands (router
    included) put in the program's place. It has to fail a number of the
    cell (the gradient), not each."""
    session = mf.load_module("builders", "afmoe").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    sound = session.reference(seed, tiny.LIMITS["steps"])
    low = session.reference(seed, tiny.LIMITS["steps"], precision="float8")
    verdict = {name: ok for name, _, _, ok, _ in
               compare.judge(low, sound, tiny.LIMITS)}
    assert verdict["grad_norm_gap"] is False
    assert verdict["loss_gap"] is True
    assert {k for k in sound["delta_norm"] if k.endswith("moe/bias")} == {
        "h1/moe/bias", "h2/moe/bias"}


def test_manifest_stays_valid_and_the_cells_files_are_found():
    assert mf.validate(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "trinity-mini", "train-8k-1chip")
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    config = mf.config_of(MANIFEST, cell["config"])
    job = mf.job_of(cell["traffic"])
    assert (job["kind"], job["seq_len"], job["tokens"],
            job["pool_batches"]) == ("closed_loop_training", 8192,
                                     "uniform", 8)              # ISSUE 30
    assert (config["builder"], config["per_chip_batch"]) == ("afmoe", 1)
    # every published number of the catalog row, the cut ones apart
    published = {
        "global_attn_every_n_layers": 4, "head_dim": 128,
        "hidden_size": 2048, "intermediate_size": 6144,
        "load_balance_coeff": 0.001, "max_position_embeddings": 131072,
        "moe_intermediate_size": 1024, "n_group": 1,
        "num_attention_heads": 32, "num_expert_groups": 1,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 32, "num_key_value_heads": 4,
        "num_limited_groups": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05, "rope_theta": 10000, "route_scale": 2.826,
        "sliding_window": 2048, "topk_group": 1}
    assert {k: config[k] for k in published} == published
    assert (config["layers"], config["num_dense_layers"],
            config["num_local_experts"], config["first_local_expert"],
            config["vocab_size"]) == (5, 1, 16, 0, 25024)
    assert config["layer_types"] == ["sliding_attention"] * 4 + [
        "full_attention"]
    assert sorted(config["reduced"]) == sorted(
        ["layers", "num_dense_layers", "layer_types", "num_local_experts",
         "vocab_size"])
    assert set(config["reduced"]) <= set(config["departures"])
    entry = next(c for c in MANIFEST["configs"]
                 if c["name"] == "trinity-mini")
    assert entry["reduced"] == config["reduced"]
    for key in ("assumed", "deployment", "memory", "catalog", "source"):
        assert config[key]
    limits = mf.limits_of(CELL)
    assert set(compare.NUMBERS) <= set(limits) and "set_from" in limits


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_for_the_new_cell_alone(name):
    reader = mf.load_module("layers", name)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (entry["name"], entry["unit"], entry["layer"], entry["moves"]) \
        == (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES)
    assert entry["workloads"] == [CELL]
    assert entry["source"] == ("device_trace" if name.endswith("_roofline")
                               else "program_span")
    assert name in {m["name"] for m in
                    mf.metrics_for(MANIFEST, "per_layer", CELL)}


def test_the_new_cell_reads_the_shared_readers_and_not_the_flash_shares():
    mine = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert {"step.forward_ms", "step.backward_ms", "step.optimizer_ms",
            "step.unscoped_pct", "head_loss.ms", "attention.ms",
            "attention.layout_ms", "moe_ffn.ms", "setup.init_s",
            "setup.compile_s", "step.dispatch_ms", "step.device_busy_ms",
            "device.idle_pct", "device.peak_hbm_gb"} <= mine
    assert not mine & {"flash_fwd_roofline", "flash_bwd_roofline",
                       "sparse_attention.ms", "collective.total_ms"}


def test_parameter_count_is_the_configurations():
    """134.5M a routed layer, 65.0M the dense one, 705.5M in all: 11.29 GB
    at 16 bytes a parameter."""
    s = reference_afmoe.sizes_from_config(
        mf.config_of(MANIFEST, "trinity-mini"))
    flat = jax.tree_util.tree_leaves(
        reference_afmoe.param_shapes(s),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], tuple))
    total = sum(math.prod(shape) for shape, _ in flat)
    attention = 2048 * (4096 + 512 + 512 + 4096) + 4096 * 2048 + 2 * 128
    norms = 4 * 2048
    expert = 3 * 2048 * 1024
    routed = attention + norms + 2048 * 128 + 16 * expert + expert
    dense = attention + norms + 3 * 2048 * 6144
    assert (attention, expert) == (27_263_232, 6_291_456)
    assert (routed, dense) == (134_488_320, 65_020_160)
    assert total == dense + 4 * routed + 2 * 25_024 * 2_048 + 2_048 \
        == 705_473_792


def test_train_flops_per_token_against_a_brute_force_count():
    s = reference_afmoe.sizes_from_config(
        mf.config_of(MANIFEST, "trinity-mini"))
    T = 8192
    # weights a token multiplies: attention 2048 * 128 * (3 * 32 + 2 * 4);
    # dense MLP 3 * 2048 * 6144; routed: router 2048 * 128, the shared
    # expert and 8 * 16 / 128 = 1 routed expert of 3 * 2048 * 1024 each
    assert flops_afmoe.attention_weights(s) == 27_262_976
    assert flops_afmoe.mlp_weights(s, 0) == 37_748_736
    assert flops_afmoe.mlp_weights(s, 1) == 262_144 + 2 * 6_291_456
    t = np.arange(T)
    band = np.minimum(t + 1, 2048).sum()
    causal = (t + 1).sum()
    assert (band, causal) == (14_681_088, 33_558_528)           # ISSUE 30
    assert flops_afmoe.mean_visible(s, 0, T) == band / T
    assert flops_afmoe.mean_visible(s, 4, T) == causal / T == (T + 1) / 2
    want = 6 * 25_024 * 2_048
    for i in range(5):
        pairs = causal if i == 4 else band
        mlp = 37_748_736 if i == 0 else 262_144 + 2 * 6_291_456
        want += 6 * (27_262_976 + mlp) + 12 * 32 * 128 * pairs / T
    assert flops_afmoe.train_flops_per_token(s, T) == want == 2_213_855_232


@pytest.mark.parametrize("seq, window", [(64, 16), (64, 1), (48, 48),
                                         (48, 100), (40, None)])
def test_visible_pairs_against_a_brute_force_count(seq, window):
    ahead = np.arange(seq)[:, None] - np.arange(seq)[None, :]
    seen = ahead >= 0
    if window is not None:
        seen &= ahead < window
    assert kernels_window.visible_pairs(seq, window) == seen.sum()


def test_kernel_costs_by_hand():
    shape = dict(batch=1, seq=8192, heads=32, kv_heads=4, head_dim=128,
                 window=2048, act_bytes=2)
    q, kv = 8192 * 32 * 128 * 2, 8192 * 4 * 128 * 2
    rows = 32 * 8192 * 4
    flops, nbytes = kernels_window.attn_fwd_cost(**shape)
    assert flops == 4 * 14_681_088 * 32 * 128
    assert nbytes == 2 * q + 2 * kv + rows       # K and V for 4 heads, not 32
    flops, nbytes = kernels_window.attn_bwd_cost(**shape)
    assert flops == 8 * 14_681_088 * 32 * 128            # four matmuls
    assert nbytes == 3 * q + 4 * kv + 2 * rows
    full = dict(shape, window=None)
    assert kernels_window.attn_fwd_cost(**full)[0] == \
        4 * 33_558_528 * 32 * 128
    # against the accepted cost of an ungrouped causal call: the same
    # operations but for the diagonal's half pairs, fewer bytes
    old = kernels.flash_fwd_cost(batch=1, seq=8192, heads=32, head_dim=128,
                                 causal=True)
    new = kernels_window.attn_fwd_cost(**full)
    assert new[0] == old[0] * (8193 / 8192) and new[1] < old[1]
    peak = peaks.for_device_kind("TPU v5 lite")
    least, bound = kernels.roofline(*kernels_window.attn_fwd_cost(**shape),
                                    peak)
    assert bound == "flops" and least == pytest.approx(1.2210e-3, rel=1e-3)


# -- the new readers on hand-made ops -----------------------------------------

GRAD = "jit(spmd)/shard_map/hvd.grad/"
BACK = GRAD + "transpose(hvd.grad)/"
WIN = "jvp(SparseMoEDecoder)/h1/attn/hvd.flash_attention/hvd.flash_window/"
FULL = "jvp(SparseMoEDecoder)/h4/attn/hvd.flash_attention/"


def _kernel(name, n, start, dur, path):
    return ((f"%{name}.{n} = (bf16[1,8192,4096]) custom-call()", start, dur,
             path + name + "/pallas_call"))


# One step of 40 ms, a gap between any two events: a windowed forward call
# (2 ms), the full call's (4), the shared expert (1), a routed layer's
# grouped matmul (3), then the backward: the full call's dq (3) and dk/dv
# (5), the windowed (2, 3), the shared expert's (2); the bias update (1).
OPS = [
    _kernel("hvd_flash_fwd_win", 1, 0.0000, 0.002, GRAD + WIN),
    _kernel("hvd_flash_fwd", 1, 0.0025, 0.004, GRAD + FULL),
    ("%fusion.7 = bf16[8192,2048] fusion()", 0.0070, 0.001,
     GRAD + "jvp(SparseMoEDecoder)/h1/moe/hvd.shared_expert/dot_general"),
    ("%ragged-dot-none.3 = bf16[73728,1024] custom-call()", 0.0085, 0.003,
     ""),
    ("%fusion.8 = f32[32,1,8192] fusion()", 0.0115, 0.0004,
     BACK + FULL + "dot_general"),                  # delta, no kernel
    _kernel("hvd_flash_bwd_dq", 1, 0.0120, 0.003, BACK + FULL),
    _kernel("hvd_flash_bwd_dkv", 1, 0.0155, 0.005, BACK + FULL),
    _kernel("hvd_flash_bwd_dq_win", 1, 0.0210, 0.002, BACK + WIN),
    _kernel("hvd_flash_bwd_dkv_win", 1, 0.0235, 0.003, BACK + WIN),
    ("%fusion.9 = bf16[8192,2048] fusion()", 0.0270, 0.002,
     BACK + "jvp(SparseMoEDecoder)/h1/moe/hvd.shared_expert/dot_general"),
    ("%fusion.11 = f32[128] fusion()", 0.0385, 0.001,
     "jit(spmd)/shard_map/hvd.router_bias_update/"
     "hvd.router_bias_update/sign"),
]
SHAPES = {"window_attention": dict(batch=1, seq=8192, heads=32, kv_heads=4,
                                   head_dim=128, window=2048, act_bytes=2),
          "gqa_attention": dict(batch=1, seq=8192, heads=32, kv_heads=4,
                                head_dim=128, window=None, act_bytes=2)}


def _traced_run(ops, shapes=SHAPES):
    run = types.SimpleNamespace(
        trace=object(), peak=peaks.for_device_kind("TPU v5 lite"),
        kernel_shapes=shapes, notes=[])
    run.note = run.notes.append
    run.scoped_ops = scopes.ScopedOps(sorted(ops, key=lambda o: o[1]),
                                      [(0.0, 0.040)])
    return run


def test_new_readers_on_the_hand_made_step():
    run = _traced_run(OPS)
    got = {name: mf.load_module("layers", name).read(run)
           for name in NEW_METRICS}
    peak = run.peak
    least = {(entry, which): kernels.roofline(*cost(**SHAPES[entry]),
                                              peak)[0]
             for entry in SHAPES for which, cost in (
                 ("fwd", kernels_window.attn_fwd_cost),
                 ("bwd", kernels_window.attn_bwd_cost))}
    assert got == pytest.approx({
        "window_attn_fwd_roofline":
            100 * least["window_attention", "fwd"] / 0.002,
        "window_attn_bwd_roofline":
            100 * least["window_attention", "bwd"] / 0.005,
        "gqa_attn_fwd_roofline": 100 * least["gqa_attention", "fwd"] / 0.004,
        "gqa_attn_bwd_roofline": 100 * least["gqa_attention", "bwd"] / 0.008,
        "attention.window_ms": 7.0, "shared_expert.ms": 3.0,
        "router_bias.ms": 1.0})
    assert all(0 < got[n] < 100 for n in NEW_METRICS
               if n.endswith("_roofline"))
    # a windowed call's kernels are not the full call's, and the reader
    # that tells kernels by substring takes all six for kernels
    layout = mf.load_module("layers", "attention.layout_ms")
    assert all(layout.is_kernel(op) for op in OPS if "hvd_flash" in op[0])
    assert mf.load_module("layers", "attention.ms").read(run) == \
        pytest.approx(19.4)
    assert layout.read(run) == pytest.approx(0.4)
    assert mf.load_module("layers", "moe_ffn.ms").read(run) is None


def test_new_readers_read_nothing_from_a_program_without_them():
    """The parent's program under this PR's benchmark files: no window, no
    such scopes, no such ``kernel_shapes`` entries; nothing is reported
    and nothing raises."""
    old = [op for op in OPS if "_win" not in op[0]
           and "shared_expert" not in op[3] and "router_bias" not in op[3]]
    run = _traced_run(old, shapes={"flash_attention": dict(
        batch=8, seq=1024, heads=16, head_dim=64, causal=True,
        act_bytes=2)})
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(run) is None, name
    untraced = types.SimpleNamespace(trace=None, peak=None, kernel_shapes={},
                                     note=lambda text: None)
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(untraced) is None, name


# -- the recorded piece -------------------------------------------------------

RECORDED = {"window_attn_fwd_roofline": 34.536,
            "window_attn_bwd_roofline": 43.640,
            "gqa_attn_fwd_roofline": 51.211, "gqa_attn_bwd_roofline": 43.939,
            "attention.window_ms": 37.781, "shared_expert.ms": 9.240,
            "router_bias.ms": 0.106, "attention.ms": 56.248,
            "attention.layout_ms": 1.569, "moe_ffn.ms": 234.834,
            "head_loss.ms": 16.544, "step.forward_ms": 94.158,
            "step.backward_ms": 219.719, "step.optimizer_ms": 0.873}


@pytest.fixture(scope="module")
def recorded():
    """One traced step of the cell on the chip
    (data/trace_trinity_scoped.json.gz says where it came from)."""
    import gzip
    import json
    import os

    from benchmarks.lib import trace as tr

    path = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                        "trace_trinity_scoped.json.gz")
    with gzip.open(path) as f:
        d = json.load(f)
    run = types.SimpleNamespace(
        trace=tr.Trace.from_json(d), chips=1, notes=[],
        peak=peaks.for_device_kind("TPU v5 lite"), kernel_shapes=SHAPES)
    run.note = run.notes.append
    run.scoped_ops = scopes.ScopedOps.from_json(d)
    return run


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_reader_on_the_recorded_step(name, recorded):
    assert mf.load_module("layers", name).read(recorded) == pytest.approx(
        RECORDED[name], abs=2e-3)


def test_recorded_kernels_are_told_apart_by_their_own_names(recorded):
    """Four sliding layers and a full one: four events of each windowed
    kernel and one of each full kernel a step, all under
    ``hvd.flash_attention``, the windowed ones under ``hvd.flash_window``
    too; no share of a roofline passes 100%."""
    kernel_seconds = mf.load_module(
        "layers", "sparse_attn_fwd_roofline").kernel_seconds
    scoped = recorded.scoped_ops
    for name in ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert len(kernel_seconds(scoped, name)) == 1
        assert len(kernel_seconds(scoped, name + "_win")) == 4
        for op in scoped.ops:
            if op[0].startswith(f"%{name}"):
                assert "hvd.flash_attention" in op[3]
                assert ("hvd.flash_window" in op[3]) == ("_win" in op[0])
                assert ("transpose(" in op[3]) == ("bwd" in name)
    # a windowed call against the full call: 43.7% of the pairs in 65% of
    # the forward's time and 44% of the backward's
    fwd_win, fwd = (kernel_seconds(scoped, n)[0] for n in
                    ("hvd_flash_fwd_win", "hvd_flash_fwd"))
    assert 0.6 < fwd_win / fwd < 0.7
    assert all(0 < RECORDED[n] < 100 for n in RECORDED
               if n.endswith("_roofline"))
    classes = scoped.classes_ms()
    assert classes["hvd.router_bias_update"] == pytest.approx(0.106, abs=2e-3)
    assert sum(classes.values()) == pytest.approx(466.28, abs=0.05)
