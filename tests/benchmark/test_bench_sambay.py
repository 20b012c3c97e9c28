"""The ``sambay`` builder (state-space layers, differential attention, a
gated memory unit and a cross-attention layer that read what earlier layers
made) under the real harness at a tiny size on the CPU
(tests/benchmark/bench_tiny_sambay.py): a sound run is correct, and the
faults the comparison exists to catch are not; the cell's files, FLOP and
kernel cost functions against brute-force counts; the new readers on
hand-made ops.
"""

import math
import types

import jax
import numpy as np
import pytest

import horovod_tpu as hvd
from benchmarks.lib import (compare, flops_sambay, harness, kernels,
                            kernels_diff, kernels_scan, kernels_window,
                            manifest as mf, owners, peaks, reference_sambay,
                            scopes)
from horovod_tpu.monitor import hlo_owners as ho
from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

import bench_tiny_sambay as tiny

MANIFEST = mf.load()
CELL = "phi-4-mini-flash.train-8k-1chip"
OWNED = ("ssm.ms", "gmu.ms", "attention.diff_ms")
SHARES = ("selective_scan_fwd_roofline", "selective_scan_bwd_roofline",
          "diff_attn_fwd_roofline", "diff_attn_bwd_roofline",
          "diff_attn_window_fwd_roofline", "diff_attn_window_bwd_roofline")
NEW_METRICS = OWNED + ("ssm_scan.ms",) + SHARES


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
                                   "compilations_in_window",
                                   "state_a_token_arrays_in_program"):
        assert " limit " in _row(lines, name) and "ok" in _row(lines, name)
    for kernel in ("hvd_selective_scan_fwd", "hvd_selective_scan_bwd",
                   "hvd_flash_fwd_win", "hvd_flash_bwd_dq_win",
                   "hvd_flash_bwd_dkv_win", "hvd_flash_fwd",
                   "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv"):
        assert "ok" in _row(lines, f"{kernel}_in_program")


def _faulty(monkeypatch, fault):
    import horovod_tpu.models.sambay as model

    if fault == "no_window_on_the_sliding_layer":
        real = model.causal_attention
        monkeypatch.setattr(
            model, "causal_attention",
            lambda q, k, v, window=None, scale=None: real(q, k, v,
                                                         scale=scale))
    elif fault == "memory_never_read":
        real = model._GMU.__call__
        monkeypatch.setattr(
            model._GMU, "__call__",
            lambda self, u, m: real(self, u, jax.numpy.ones_like(m)))
    elif fault == "scan_state_dropped_every_32_tokens":
        real = model._scan.selective_scan

        def pieces(x, dt, A, Bm, Cm, Dskip, **kw):
            B, T, Dn = x.shape

            def cut(a):
                return a.reshape(B * T // 32, 32, a.shape[-1])

            return real(cut(x), cut(dt), A, cut(Bm), cut(Cm), Dskip,
                        **kw).reshape(B, T, Dn)

        monkeypatch.setattr(model._scan, "selective_scan", pieces)
    elif fault == "scan_decay_takes_no_gradient":
        real = model._scan.selective_scan
        monkeypatch.setattr(
            model._scan, "selective_scan",
            lambda x, dt, A, *rest, **kw: real(
                x, dt, jax.lax.stop_gradient(A), *rest, **kw))
    else:
        raise ValueError(fault)


@pytest.mark.parametrize("fault, caught_by, leaf", [
    ("no_window_on_the_sliding_layer", "loss_gap", ""),
    ("memory_never_read", "grad_norm_gap", "h4/mixer/out_proj"),
    ("scan_state_dropped_every_32_tokens", "grad_norm_gap",
     "h0/mixer/x_proj"),
    ("scan_decay_takes_no_gradient", "delta_norm_gap", "mixer/A_log")])
def test_a_fault_is_not_correct(session_mesh_restored, tmp_path,
                                monkeypatch, fault, caught_by, leaf):
    """Each is as fast as the sound step or faster: the sliding layer
    attending every key; a gated memory unit that gates ones and not the
    scan's output (the state-space layer's memory then takes no gradient
    from it); a recurrence broken forward (the state not carried from one
    piece of 32 tokens to the next: the gradient of B and C, ``x_proj``,
    shows it, and the loss) and one broken backward alone (the decay's
    gradient dropped: ``A_log`` then moves by its weight decay alone)."""
    _faulty(monkeypatch, fault)
    lines = []
    result = _run(tmp_path, 1, lines)
    assert result["correct"] is False, "\n".join(lines)
    assert "FAIL" in _row(lines, caught_by) and leaf in _row(lines, caught_by)


@pytest.mark.parametrize("seed", [1, 2])
def test_lower_precision_control_is_not_correct(session_mesh_restored, seed):
    """The control: the reference with float8 matmul operands put in the
    program's place. It has to fail a number of the cell (here the loss and
    the gradient), not each."""
    session = mf.load_module("builders", "sambay").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    sound = session.reference(seed, tiny.LIMITS["steps"])
    low = session.reference(seed, tiny.LIMITS["steps"], precision="float8")
    verdict = {name: ok for name, _, _, ok, _ in
               compare.judge(low, sound, tiny.LIMITS)}
    assert verdict["loss_gap"] is False and verdict["grad_norm_gap"] is False


def test_manifest_stays_valid_and_the_cells_files_are_found():
    assert mf.validate(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, "phi-4-mini-flash", "train-8k-1chip")
    assert MANIFEST["workloads"][-1] is cell
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    config = mf.config_of(MANIFEST, cell["config"])
    job = mf.job_of(cell["traffic"])
    assert (job["kind"], job["seq_len"], job["tokens"],
            job["pool_batches"]) == ("closed_loop_training", 8192,
                                     "uniform", 8)              # ISSUE 39
    assert (config["builder"], config["per_chip_batch"]) == ("sambay", 1)
    # every number of the catalog row's config, the cut one apart
    published = {
        "embd_pdrop": 0, "hidden_size": 2560, "intermediate_size": 10240,
        "layer_norm_eps": 1e-05, "max_position_embeddings": 262144,
        "mb_per_layer": 2, "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20,
        "resid_pdrop": 0, "sliding_window": 512}
    assert {k: config[k] for k in published} == published
    assert (config["hidden_act"], config["model_type"],
            config["tie_word_embeddings"], config["mlp_bias"],
            config["lm_head_bias"]) == ("silu", "phi4flash", True, False,
                                        False)
    assert config["vocab_size"] == 25008 == 200064 // 8
    assert config["layers"] == [0, 1, 16, 17, 18, 19]
    assert config["layer_types"] == [
        "mamba", "sliding_attention", "mamba", "full_attention", "gmu",
        "cross_attention"]
    assert (config["mamba_d_state"], config["mamba_d_conv"],
            config["mamba_expand"], config["mamba_dt_rank"]) == (
        16, 4, 2, math.ceil(2560 / 16))
    assert config["reduced"] == ["layers", "layer_types", "vocab_size"]
    assert set(config["reduced"]) <= set(config["departures"])
    entry = MANIFEST["configs"][-1]
    assert (entry["name"], entry["reduced"], entry["source"]) == (
        "phi-4-mini-flash", config["reduced"], config["source"])
    for key in ("assumed", "deployment", "memory", "catalog", "source"):
        assert config[key]
    for said in ("2 of 6", "8 of 32", "9 of 32", "1 of 6"):
        assert said in config["departures"]["layers"]
    limits = mf.limits_of(CELL)
    assert set(compare.NUMBERS) <= set(limits) and "set_from" in limits


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_for_the_new_cell(name):
    reader = mf.load_module("layers", name)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (entry["name"], entry["unit"], entry["layer"], entry["moves"]) \
        == (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES)
    assert CELL in entry["workloads"]
    assert entry["source"] == ("device_trace" if name.endswith("_roofline")
                               else "program_span")
    assert name in {m["name"] for m in
                    mf.metrics_for(MANIFEST, "per_layer", CELL)}
    if hasattr(reader, "SCOPE"):
        assert reader.SCOPE in DEVICE_SCOPES


def test_the_new_cell_reads_the_shared_readers_it_may_join():
    """The lists a test of the accepted benchmark pins letter for letter
    (``test_bench_owners.py``, ``test_bench_afmoe.py``) are left as they
    are: PERF.md section 7 says which."""
    mine = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert {"step.forward_ms", "step.backward_ms", "step.optimizer_ms",
            "step.unscoped_pct", "head_loss.ms", "attention.ms",
            "attention.layout_ms", "step.interval_p90_ms", "setup.init_s",
            "setup.compile_s", "step.dispatch_ms", "step.device_busy_ms",
            "device.idle_pct", "device.peak_hbm_gb"} <= mine
    assert not mine & {"flash_fwd_roofline", "moe_ffn.ms", "rotary.ms",
                       "sparse_attention.ms", "collective.total_ms"}
    e2e = {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"tokens_per_s_per_chip", "mfu_pct", "setup_s"}


def _sizes():
    return reference_sambay.sizes_from_config(
        mf.config_of(MANIFEST, "phi-4-mini-flash"))


def test_parameter_count_is_the_issues():
    """119.9M a state-space layer, 98.3M an attention layer, 104.9M a
    memory unit, 91.7M a cross-attention layer, 64.0M of embedding: 697.1M,
    11.15 GB at 16 bytes a parameter (ISSUE 39's table, with the norms,
    biases and lam vectors it leaves out)."""
    s = _sizes()
    flat = jax.tree_util.tree_leaves(
        reference_sambay.param_shapes(s),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], tuple))
    total = sum(math.prod(shape) for shape, _ in flat)
    mlp = 3 * 2560 * 10240
    mamba = (2560 * 10240 + 4 * 5120 + 5120 + 5120 * 192 + 160 * 5120 + 5120
             + 5120 * 16 + 5120 + 5120 * 2560)
    lam = 4 * 64 + 128
    attention = 2560 * 5120 + 2560 * 2560 + lam
    cross = 2 * 2560 * 2560 + lam
    gmu = 2 * 2560 * 5120
    norms = 4 * 2560
    assert (mlp, mamba, attention) == (78_643_200, 41_241_600, 19_661_184)
    assert total == (6 * (mlp + norms) + 2 * mamba + 2 * attention + cross
                     + gmu + 25_008 * 2560 + 2 * 2560) == 697_073_792
    assert round(total * 16 / 1e9, 2) == 11.15


def test_train_flops_per_token_against_a_brute_force_count():
    s, T = _sizes(), 8192
    assert flops_sambay.mixer_weights(s, "mamba") == (
        2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560)
    assert flops_sambay.mixer_weights(s, "full_attention") == 19_660_800
    assert flops_sambay.mixer_weights(s, "cross_attention") == 13_107_200
    assert flops_sambay.mixer_weights(s, "gmu") == 26_214_400
    t = np.arange(T)
    band, causal = np.minimum(t + 1, 512).sum(), (t + 1).sum()
    # a pair of heads: two maps of a 64-wide score and a 128-wide value,
    # forward, and four products of those widths backward
    pair = 2 * (64 + 128) * 2
    assert flops_sambay.attention_flops(s, "sliding_attention", T) == \
        3 * 20 * pair * band / T
    assert flops_sambay.attention_flops(s, "cross_attention", T) == \
        3 * 20 * pair * causal / T
    assert flops_sambay.attention_flops(s, "mamba", T) == 0
    want = 6 * 25_008 * 2560
    for kind in s["layer_types"]:
        want += 6 * (flops_sambay.mixer_weights(s, kind) + 78_643_200) \
            + flops_sambay.attention_flops(s, kind, T)
    assert flops_sambay.train_flops_per_token(s, T) == want
    # ISSUE 39: about 34 TFLOP of matmuls a step and 3.6 of attention
    assert 37e12 < want * T < 38e12


def test_scan_costs_by_hand_and_never_above_what_the_kernel_holds():
    shape = dict(batch=1, seq=8192, d_inner=5120, d_state=16)
    tokens, states = 8192 * 5120 * 4, 8192 * 16 * 4
    weights = (5120 * 16 + 5120) * 4
    ops, nbytes = kernels_scan.scan_fwd_cost(**shape)
    assert nbytes == 3 * tokens + 2 * states + weights
    assert ops == 7 * 8192 * 5120 * 16
    ops, back = kernels_scan.scan_bwd_cost(**shape)
    assert back == 5 * tokens + 4 * states + 2 * weights
    assert ops == 14 * 8192 * 5120 * 16
    # What the kernels' operands hold at the default blocking: the forward
    # also writes the chunk-boundary states, the backward reads them and
    # writes a part of dB and dC a channel block and dA, dDskip a sequence.
    from horovod_tpu.ops import selective_scan as S

    chunk, block = S.DEFAULT_BLOCKS
    boundary = (8192 // chunk) * 5120 * 16 * 4
    assert nbytes <= 3 * tokens + 2 * states + weights + boundary
    parts = (5120 // block) * 2 * states
    assert back <= 5 * tokens + 2 * states + parts + 2 * weights + boundary
    peak = peaks.for_device_kind("TPU v5 lite")
    assert kernels_scan.least_seconds(nbytes, peak) == pytest.approx(
        nbytes / 819e9)
    assert nbytes / 819e9 == pytest.approx(0.616e-3, rel=1e-2)


def test_diff_attention_costs_by_hand():
    shape = dict(batch=1, seq=8192, heads=40, kv_heads=20, head_dim=64,
                 window=512, act_bytes=2)
    band = kernels_window.visible_pairs(8192, 512)
    q, kv, out = (8192 * n * 2 for n in (40 * 64, 20 * 64, 40 * 128))
    rows = 40 * 8192 * 4
    flops, nbytes = kernels_diff.attn_fwd_cost(**shape)
    assert flops == 40 * band * 2 * (64 + 128)
    assert nbytes == q + 2 * kv + out + rows
    flops, nbytes = kernels_diff.attn_bwd_cost(**shape)
    assert flops == 40 * band * 2 * (128 + 128 + 64 + 64)
    assert nbytes == 2 * q + 4 * kv + out + 2 * rows
    # never above the count of the 128-wide call the program makes
    wide = dict(shape, kv_heads=10, head_dim=128)
    assert kernels_diff.attn_fwd_cost(**shape)[0] == \
        0.75 * kernels_window.attn_fwd_cost(**wide)[0]
    assert kernels_diff.attn_fwd_cost(**shape)[1] < \
        kernels_window.attn_fwd_cost(**wide)[1]
    # the FLOPs file and the kernel cost file count the same pairs
    s = _sizes()
    full = dict(shape, window=None)
    per_token = (kernels_diff.attn_fwd_cost(**full)[0]
                 + kernels_diff.attn_bwd_cost(**full)[0]) / 8192
    assert per_token == flops_sambay.attention_flops(s, "full_attention",
                                                     8192)


# -- the new readers on hand-made ops -----------------------------------------

GRAD = "jit(spmd)/shard_map/hvd.grad/"
BACK = GRAD + "transpose(hvd.grad)/"
SSM = "jvp(SambaY)/h0/hvd.ssm/mixer/"
SCAN = SSM + "hvd.selective_scan/"
WIN = "jvp(SambaY)/h1/mixer/hvd.flash_attention/hvd.flash_window/"
FULL = "jvp(SambaY)/h3/mixer/hvd.flash_attention/"
F, B = ho.FORWARD, ho.BACKWARD


def _kernel(name, n, start, dur, path):
    return ((f"%{name}.{n} = (f32[1,8192,40,128]) custom-call()", start, dur,
             path + name + "/pallas_call"))


# One step of 40 ms: the state-space in-projection (1 ms), the scan's
# forward (2), the windowed and the full flash forward (1, 3), the memory
# unit (1), the differential subtraction (0.5); then the backward: the full
# call's dq and dk/dv (3, 4), the windowed (1, 1), the scan's backward (12)
# with the sum of its parts (0.5), the in-projection's (2).
OPS = [
    ("%fusion.1 = bf16[8192,10240] fusion()", 0.0000, 0.001,
     GRAD + SSM + "dot_general"),
    _kernel("hvd_selective_scan_fwd", 1, 0.0015, 0.002, GRAD + SCAN),
    _kernel("hvd_flash_fwd_win", 1, 0.0040, 0.001, GRAD + WIN),
    _kernel("hvd_flash_fwd", 1, 0.0055, 0.003, GRAD + FULL),
    ("%fusion.2 = bf16[8192,5120] fusion()", 0.0090, 0.001,
     GRAD + "jvp(SambaY)/h4/hvd.gmu/mixer/dot_general"),
    ("%fusion.3 = f32[8192,20,128] fusion()", 0.0105, 0.0005,
     GRAD + "jvp(SambaY)/h3/mixer/hvd.diff_attention/sub"),
    _kernel("hvd_flash_bwd_dq", 1, 0.0120, 0.003, BACK + FULL),
    _kernel("hvd_flash_bwd_dkv", 1, 0.0155, 0.004, BACK + FULL),
    _kernel("hvd_flash_bwd_dq_win", 1, 0.0200, 0.001, BACK + WIN),
    _kernel("hvd_flash_bwd_dkv_win", 1, 0.0215, 0.001, BACK + WIN),
    _kernel("hvd_selective_scan_bwd", 1, 0.0230, 0.012, BACK + SCAN),
    ("%fusion.4 = f32[8192,16] fusion()", 0.0355, 0.0005,
     BACK + SCAN + "reduce_sum"),
    ("%fusion.5 = f32[2560,10240] fusion()", 0.0365, 0.002,
     BACK + SSM + "dot_general"),
]
OWNERS = {"fusion.1": {("hvd.ssm", F): 1.0},
          "fusion.2": {("hvd.gmu", F): 1.0},
          "fusion.3": {("hvd.diff_attention", F): 1.0},
          "fusion.4": {("hvd.selective_scan", B): 1.0},
          "fusion.5": {("hvd.ssm", B): 1.0}}
for _op in OPS:
    _name = ho.instruction_name(_op[0])
    if _name not in OWNERS:
        _scope = ("hvd.selective_scan" if "scan" in _name
                  else "hvd.flash_attention")
        OWNERS[_name] = {(_scope, B if "bwd" in _name else F): 1.0}
SHAPES = {
    "selective_scan": dict(batch=1, seq=8192, d_inner=5120, d_state=16),
    "diff_window_attention": dict(batch=1, seq=8192, heads=40, kv_heads=20,
                                  head_dim=64, window=512, act_bytes=2),
    "diff_attention": dict(batch=1, seq=8192, heads=40, kv_heads=20,
                           head_dim=64, window=None, act_bytes=2)}


def _traced_run(ops, shapes=SHAPES, owned=OWNERS):
    run = types.SimpleNamespace(
        trace=object(), peak=peaks.for_device_kind("TPU v5 lite"),
        kernel_shapes=shapes, notes=[])
    run.note = run.notes.append
    run.scoped_ops = scopes.ScopedOps(sorted(ops, key=lambda o: o[1]),
                                      [(0.0, 0.040)])
    run.owned = owners.join(run.scoped_ops, owned)
    return run


def test_new_readers_on_the_hand_made_step():
    run = _traced_run(OPS)
    got = {name: mf.load_module("layers", name).read(run)
           for name in NEW_METRICS}
    peak = run.peak
    scan = {which: kernels_scan.least_seconds(
        cost(**SHAPES["selective_scan"])[1], peak) for which, cost in (
            ("fwd", kernels_scan.scan_fwd_cost),
            ("bwd", kernels_scan.scan_bwd_cost))}
    least = {(entry, which): kernels.roofline(*cost(**SHAPES[entry]),
                                              peak)[0]
             for entry in ("diff_window_attention", "diff_attention")
             for which, cost in (("fwd", kernels_diff.attn_fwd_cost),
                                 ("bwd", kernels_diff.attn_bwd_cost))}
    assert got == pytest.approx({
        "ssm.ms": 3.0, "gmu.ms": 1.0, "attention.diff_ms": 0.5,
        "ssm_scan.ms": 14.5,
        "selective_scan_fwd_roofline": 100 * scan["fwd"] / 0.002,
        "selective_scan_bwd_roofline": 100 * scan["bwd"] / 0.012,
        "diff_attn_fwd_roofline":
            100 * least["diff_attention", "fwd"] / 0.003,
        "diff_attn_bwd_roofline":
            100 * least["diff_attention", "bwd"] / 0.007,
        "diff_attn_window_fwd_roofline":
            100 * least["diff_window_attention", "fwd"] / 0.001,
        "diff_attn_window_bwd_roofline":
            100 * least["diff_window_attention", "bwd"] / 0.002})
    assert all(0 < got[n] < 100 for n in SHARES)
    assert any("no vector-unit peak" in line for line in run.notes)
    assert mf.load_module("layers", "attention.ms").read(run) == \
        pytest.approx(13.0)


def test_new_readers_read_nothing_from_a_program_without_them():
    """The parent's program under this PR's benchmark files: no scan, no
    such scopes, no such ``kernel_shapes`` entries; nothing is reported and
    nothing raises."""
    old = [op for op in OPS if "hvd_flash" in op[0]]
    run = _traced_run(old, shapes={"gqa_attention": dict(
        batch=1, seq=8192, heads=32, kv_heads=4, head_dim=128, window=None,
        act_bytes=2)}, owned={ho.instruction_name(op[0]): {
            ("hvd.flash_attention", F): 1.0} for op in old})
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(run) is None, name
    untraced = types.SimpleNamespace(trace=None, peak=None, kernel_shapes={},
                                     note=lambda text: None)
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(untraced) is None, name
