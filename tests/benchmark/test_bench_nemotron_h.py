"""The ``nemotron_h`` builder (one sublayer a layer: Mamba-2 through the
chunked scan, attention with no position, two-matrix ``relu2`` experts
walked in a latent beside a shared expert, the routers' biases as state)
under the real harness at a tiny size on the CPU
(tests/benchmark/bench_tiny_nemotron_h.py): a sound run is correct, and the
faults the comparison exists to catch are not; the cell's files, parameter
and FLOP counts against hand-worked numbers; the new readers on hand-made
ops. Everything about the manifest is held by MEMBERSHIP, not position: the
next cell does not turn it red.
"""

import dataclasses
import json
import math
import os
import types

import jax
import pytest

import horovod_tpu as hvd
from benchmarks.lib import (compare, flops_nemotron_h as flops, harness,
                            kernels, kernels_ssd, manifest as mf, peaks,
                            reference_nemotron_h as ref, scopes)
from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

import bench_tiny_nemotron_h as tiny

MANIFEST = mf.load()
CONFIG = "nemotron-3-super-120b-a12b"
CELL = "nemotron-3-super-120b-a12b.train-8k-1chip"
SHARES = ("ssd_fwd_roofline", "ssd_bwd_roofline")
NEW_METRICS = ("ssd_scan.ms", "moe_latent.ms") + SHARES
JOINED = ("step.forward_ms", "step.backward_ms", "step.optimizer_ms",
          "step.unscoped_pct", "head_loss.ms", "attention.ms",
          "attention.layout_ms", "moe_ffn.ms", "moe_route.ms", "ssm.ms",
          "step.interval_p90_ms", "nope_attn_fwd_roofline",
          "nope_attn_bwd_roofline")
# The lists a test of the accepted benchmark pins letter for letter.
PINNED = ("shared_expert.ms", "router_bias.ms", "proj.ms", "mlp.ms",
          "norm.ms", "rotary.ms", "grad.unowned_ms", "grad.remat_ms",
          "optimizer.ms", "step.unowned_pct", "step.mixed_pct",
          "moe.tiles_per_step")
CATALOG_FILE = "/opt/skills/guides/model-configs/architectures.jsonl"


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


# -- a sound run, the faults, the control -------------------------------------

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
    for name in ("hvd_flash_fwd_in_program", "grouped_matmuls_in_program",
                 "ssd_scan_forward_layers", "ssd_scan_backward_layers",
                 "ssd_scan_recomputed_layers",
                 "state_a_token_arrays_in_program", "router_bias_moved"):
        assert "ok" in _row(lines, name)


def _fault(monkeypatch, **overrides):
    """The model built with one field of its configuration wrong."""
    from horovod_tpu.models import HybridMambaMoEConfig

    real = HybridMambaMoEConfig.from_dict.__func__
    monkeypatch.setattr(HybridMambaMoEConfig, "from_dict", classmethod(
        lambda cls, cfg, **kw: dataclasses.replace(real(cls, cfg, **kw),
                                                   **overrides)))


@pytest.mark.parametrize("overrides, fails, seed", [
    # the gates left unscaled: every routed expert's gradient, a fifth
    (dict(routed_scaling_factor=1.0), ("grad_norm_gap",), 3),
    # ... or left un-normalised: the chosen scores as they are
    (dict(norm_topk_prob=False), ("grad_norm_gap",), 4)])
def test_a_fault_is_not_correct(session_mesh_restored, tmp_path,
                                monkeypatch, overrides, fails, seed):
    _fault(monkeypatch, **overrides)
    lines = []
    result = _run(tmp_path, seed, lines)
    assert result["correct"] is False, "\n".join(lines)
    for name in fails:
        assert "FAIL" in _row(lines, name), "\n".join(lines)


@pytest.mark.parametrize("seed", [1, 3, 5])
def test_lower_precision_control_is_not_correct(session_mesh_restored, seed):
    """The control: the reference with float8 matmul operands (the router's
    and the state's read through C included) put in the program's place. It
    has to fail a number of the cell, not each."""
    session = mf.load_module("builders", "nemotron_h").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    sound = session.reference(seed, tiny.LIMITS["steps"])
    low = session.reference(seed, tiny.LIMITS["steps"], precision="float8")
    rows = compare.judge(low, sound, tiny.LIMITS)
    # finite on every number: a control that overflows tells nothing
    assert all(math.isfinite(value) for _, value, _, _, _ in rows)
    verdict = {name: ok for name, _, _, ok, _ in rows}
    assert verdict["grad_norm_gap"] is False


def test_the_program_warms_up_as_its_reference(session_mesh_restored):
    """One schedule, read by both sides: 3e-4 / 2000 at the first update."""
    opt = tiny.CONFIG["optimizer"]
    rate = ref.warmup_schedule(opt)
    assert float(rate(0)) == pytest.approx(opt["lr"] / 2000)
    assert float(rate(1999)) == float(rate(5000)) == pytest.approx(opt["lr"])
    session = mf.load_module("builders", "nemotron_h").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    assert callable(session.opt["lr"]) and session.config["optimizer"] == opt


def test_structure_rows_read_the_text():
    builder = mf.load_module("builders", "nemotron_h")
    scan = "mixer/hvd.ssm/hvd.ssd_scan/"
    text = ("HloModule m\n\nfused {\n"
            f" x = f32[] dot(a, b), op_name=\"jit/h0/{scan}dot_general\"\n"
            "}\n\nENTRY main {\n"
            f" a = f32[] fusion(), op_name=\"jit/h0/{scan}mul\"\n"
            f" b = f32[] convolution(a), op_name=\"jit/h2/{scan}dot_general\"\n"
            f" c = f32[] dot(a, b), op_name=\"jit/transpose(jvp)/h2/{scan}d\"\n"
            f" d = f32[] dot(a, b), op_name=\"jit/transpose(jvp)/"
            f"rematted_computation/h4/{scan}d\"\n"
            " e = f32[] dot(a, b), op_name=\"jit/h4/mixer/hvd.ssm/dot\"\n"
            "}\n")
    # h0 and h2 scan forward (an elementwise op alone is no scan), h2
    # backward; h4's recomputed forward holds a matmul of the scan
    assert builder.scan_layers(text) == {
        "forward": {0, 2}, "backward": {2}, "remat": {4}}


# -- the cell's files ---------------------------------------------------------

def test_manifest_stays_valid_and_holds_the_new_entries():
    assert mf.validate(MANIFEST) == []
    with open(os.path.join(mf.ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) < 64 * 1024
    cell = mf.cell(MANIFEST, CELL)
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "train-8k-1chip")
    assert len(cell["why"]) <= 200
    for said in ("64 chunks", "352", "52%", "spread"):
        assert said in cell["why"], said
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    config = mf.config_of(MANIFEST, CONFIG)
    assert (entry["reduced"], entry["source"], entry["file"]) == (
        config["reduced"], config["source"],
        "benchmarks/configs/nemotron-3-super-120b-a12b.json")
    job = mf.job_of(cell["traffic"])
    assert (job["kind"], job["seq_len"], job["tokens"],
            job["pool_batches"]) == ("closed_loop_training", 8192,
                                     "uniform", 8)              # ISSUE 48
    e2e = {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"tokens_per_s_per_chip", "mfu_pct", "setup_s"}
    limits = mf.limits_of(CELL)
    assert set(compare.NUMBERS) <= set(limits) and "set_from" in limits
    assert limits["steps"] in (1, 2)
    for name in compare.NUMBERS:
        assert limits["set_from"][name]


def test_configuration_holds_every_published_width():
    """Every key of the catalog row's ``config`` is in the file under the
    same name, equal unless ``reduced`` lists it; no width is cut."""
    with open(CATALOG_FILE) as f:
        rows = [json.loads(line) for line in f]
    row = next((r for r in rows if r["name"]
                == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"), None)
    if row is None:
        pytest.skip("the catalog has no such row here")
    config = mf.config_of(MANIFEST, CONFIG)
    assert config["source"] == row["source_url"]
    differ = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differ == {"mamba_num_heads", "n_groups", "num_attention_heads",
                      "num_key_value_heads", "vocab_size",
                      "num_nextn_predict_layers"}
    assert differ <= set(config["reduced"]) <= differ | {
        "layers", "num_local_experts"}
    assert set(config["reduced"]) <= set(config["departures"])
    for key in config["reduced"]:
        assert not any(w in key for w in mf.WIDTH_WORDS), key
    for key, whole in config["published"].items():
        assert whole % config[key] == 0 and key in config["departures"]
    assert [row["config"]["hybrid_override_pattern"][i]
            for i in config["layers"]] == list("MEMEMEMEM*E")
    # the group's size, as published: 16 heads of 64, 1,024 channels
    assert (config["mamba_num_heads"] * config["mamba_head_dim"]
            // config["n_groups"]) == 8192 // 8
    for key in ("assumed", "deployment", "memory", "catalog", "source"):
        assert config[key]
    for key in ("position", "gated_norm", "router", "latent_experts",
                "balancing_rule", "initialisers", "precision", "optimizer"):
        assert config["assumed"][key]
    trinity = mf.config_of(MANIFEST, "trinity-mini")
    assert config["load_balance_coeff"] == trinity["load_balance_coeff"]
    small = mf.config_of(MANIFEST, "smallthinker-21b-a3b")
    assert config["optimizer"] == small["optimizer"]       # like for like


@pytest.mark.parametrize("name", NEW_METRICS)
def test_new_metric_is_declared_for_the_new_cell(name):
    reader = mf.load_module("layers", name)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (entry["name"], entry["unit"], entry["layer"], entry["moves"]) \
        == (reader.NAME, reader.UNIT, reader.LAYER, reader.MOVES)
    assert CELL in entry["workloads"]
    assert entry["source"] == ("device_trace" if name.endswith("_roofline")
                               else "program_span")
    assert entry["better"] == ("higher" if name.endswith("_roofline")
                               else "lower")
    with open(os.path.join(mf.ROOT, "PERF.md")) as f:
        section = f.read().split("\n## 3. Layers", 1)[1].split("\n## ")[0]
    assert f"| {reader.LAYER} |" in section


def test_the_new_cell_joins_thirteen_lists_and_no_pinned_one():
    by_name = {m["name"]: m for m in MANIFEST["per_layer"]}
    for name in JOINED:
        assert CELL in by_name[name]["workloads"], name
    for name in PINNED:
        assert CELL not in by_name[name].get("workloads", [CELL][:0]), name
    reported = {m["name"] for m in
                mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(JOINED) | set(NEW_METRICS) <= reported
    assert {"hvd.ssd_scan", "hvd.moe_latent"} <= set(DEVICE_SCOPES)


def test_parameter_count_is_the_issues():
    config = mf.config_of(MANIFEST, CONFIG)
    s = ref.sizes_from_config(config)
    shapes = jax.tree.leaves(ref.param_shapes(s), is_leaf=ref._is_spec)
    total = sum(math.prod(shape) for shape, _ in shapes)
    biases = len(ref.expert_layers(s)) * s["experts"]
    assert total + biases == 700_865_520                      # ISSUE 48
    by_layer = {name: sum(math.prod(shape) for shape, _ in jax.tree.leaves(
        tree, is_leaf=ref._is_spec))
        for name, tree in ref.param_shapes(s).items() if name[0] == "h"
        and name != "head"}
    assert by_layer["h0"] == 13_708_592                       # M
    assert by_layer["h9"] == 5_246_976                        # *
    assert by_layer["h1"] + s["experts"] == 98_570_752        # E


def test_train_flops_are_the_issues_terms():
    config = mf.config_of(MANIFEST, CONFIG)
    s = ref.sizes_from_config(config)
    assert 6 * flops.mamba_weights(s) == 6 * 13_697_024
    assert 6 * flops.attention_weights(s) == 6 * 5_242_880
    parts = flops.expert_weights(s)
    assert parts == {"router": 4096 * 512, "latent": 2 * 4096 * 1024,
                     "shared": 2 * 4096 * 5376,
                     "routed": 22 * 8 / 512 * 2 * 1024 * 2688}
    scan = flops.scan_flops(s, 8192)
    # a chunk of 128: 8,256 pairs; C B^T a group, the product a head, the
    # chunk state and its read 2 x 2 x 64 x 128 a token a head
    assert scan == pytest.approx(
        (2 * 128 * 8256 + 16 * (2 * 64 * 8256 + 4 * 64 * 128 * 128
                                + 2 * 64 * 128)) / 128)
    total = flops.train_flops_per_token(s, 8192)
    want = (5 * (6 * 13_697_024 + 3 * scan)
            + 6 * 5_242_880 + 12 * 4 * 128 * 8193 / 2
            + 5 * 6 * sum(parts.values()) + 6 * 16384 * 4096)
    assert total == pytest.approx(want)
    # ISSUE 48's 2,563 MFLOP a token and the scan's 10 beside them
    assert total / 1e6 == pytest.approx(2563 + 10, abs=1.5)
    assert 5 * 6 * parts["shared"] / total == pytest.approx(0.51, abs=0.01)


def test_kernel_shapes_and_costs_by_hand(session_mesh_restored):
    session = mf.load_module("builders", "nemotron_h").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    assert session.kernel_shapes == {
        "ssd_scan": dict(batch=1, seq=64, heads=4, head_dim=8, groups=2,
                         d_state=16, chunk=16, act_bytes=2, calls=2),
        "nope_attention": dict(batch=1, seq=64, heads=4, kv_heads=2,
                               head_dim=16, act_bytes=2, window=None)}
    assert session.tokens_per_step == 64
    shape = dict(batch=1, seq=8192, heads=16, head_dim=64, groups=1,
                 d_state=128, chunk=128, act_bytes=2)
    fwd = 64 * (2 * 128 * 8256 + 16 * (2 * 64 * 8256 + 4 * 64 * 128 * 128
                                       + 2 * 64 * 128))
    tensors = (2 * 8192 * 1024 * 2 + 8192 * 16 * 4 + 2 * 8192 * 128 * 2
               + 2 * 16 * 4)
    assert kernels_ssd.ssd_fwd_cost(**shape) == (fwd, tensors)
    assert kernels_ssd.ssd_bwd_cost(**shape) == (
        2 * fwd, 2 * tensors + 64 * 16 * 64 * 128 * 4)
    peak = peaks.for_device_kind("TPU v5 lite")
    least, bound = kernels.roofline(*kernels_ssd.ssd_fwd_cost(**shape), peak)
    assert bound == "bytes" and least == pytest.approx(46.7e-6, rel=1e-2)
    # a T no chunk divides is padded: whole chunks are counted
    assert kernels_ssd.ssd_fwd_cost(**dict(shape, seq=8200))[0] == \
        fwd * 65 / 64


# -- the new readers on hand-made ops -----------------------------------------

GRAD = "jit(spmd)/shard_map/hvd.grad/"
BACK = GRAD + "transpose(hvd.grad)/"
H0 = "jvp(HybridMambaMoE)/h0/"
H1 = "jvp(HybridMambaMoE)/h1/"
SCAN = "mixer/hvd.ssm/hvd.ssd_scan/"
LATENT = "moe/hvd.moe_latent/"
SHAPES = {"ssd_scan": dict(batch=1, seq=8192, heads=16, head_dim=64,
                           groups=1, d_state=128, chunk=128, act_bytes=2,
                           calls=2)}

# One step of 300 ms with two scans a direction: forward 1.5 + 0.5 ms of
# matmuls and 0.4 of what stands between them; the mixer around them (3);
# the latent's two projections (2 + 1.5) around the walk (6); backward the
# scans' 5 + 4 ms, the latent's 3 and 0.8 in the rematerialised forward.
OPS = [
    ("%fusion.1 = bf16[8192,2320] fusion()", 0.000, 0.003,
     GRAD + H0 + "mixer/hvd.ssm/dot_general"),
    ("%fusion.2 = f32[64,16,128,128] fusion()", 0.004, 0.0015,
     GRAD + H0 + SCAN + "dot_general"),
    ("%fusion.3 = f32[64,16,128,128] fusion()", 0.006, 0.0004,
     GRAD + H0 + SCAN + "exp"),
    ("%fusion.4 = f32[64,16,64,128] fusion()", 0.007, 0.0005,
     GRAD + H0 + SCAN + "dot_general"),
    ("%fusion.5 = bf16[8192,1024] fusion()", 0.010, 0.002,
     GRAD + H1 + LATENT + "dot_general"),
    ("%gather.1 = bf16[512,1024] gather()", 0.0122, 0.0005,
     GRAD + H1 + "moe/hvd.moe_ffn/gather"),
    # the compiler's grouped matmul: its path is its own name
    ("%ragged-dot-none.1 = bf16[512,2688] custom-call()", 0.013, 0.006,
     "ragged-dot-none"),
    ("%fusion.6 = bf16[8192,4096] fusion()", 0.020, 0.0015,
     GRAD + H1 + LATENT + "dot_general"),
    ("%fusion.7 = bf16[8192,1024] fusion()", 0.100, 0.003,
     BACK + H1 + LATENT + "transpose"),
    ("%fusion.8 = bf16[8192,1024] fusion()", 0.104, 0.0008,
     BACK + "rematted_computation/" + H1 + LATENT + "dot_general"),
    ("%fusion.9 = f32[64,16,128,128] fusion()", 0.110, 0.005,
     BACK + H0 + SCAN + "transpose"),
    ("%fusion.10 = f32[64,16,64,128] fusion()", 0.120, 0.004,
     BACK + H0 + SCAN + "transpose"),
]


def _traced_run(ops, shapes=SHAPES):
    run = types.SimpleNamespace(
        trace=object(), peak=peaks.for_device_kind("TPU v5 lite"),
        kernel_shapes=shapes, notes=[])
    run.note = run.notes.append
    run.scoped_ops = scopes.ScopedOps(sorted(ops, key=lambda o: o[1]),
                                      [(0.0, 0.300)])
    return run


def test_new_readers_on_the_hand_made_step():
    run = _traced_run(OPS)
    got = {name: mf.load_module("layers", name).read(run)
           for name in NEW_METRICS}
    shape = {k: v for k, v in SHAPES["ssd_scan"].items() if k != "calls"}
    fwd = kernels.roofline(*kernels_ssd.ssd_fwd_cost(**shape), run.peak)[0]
    bwd = kernels.roofline(*kernels_ssd.ssd_bwd_cost(**shape), run.peak)[0]
    assert got == pytest.approx({
        "ssd_scan.ms": 1.5 + 0.4 + 0.5 + 5 + 4,
        "moe_latent.ms": 2 + 1.5 + 3 + 0.8,
        "ssd_fwd_roofline": 100 * 2 * fwd / 2.4e-3,
        "ssd_bwd_roofline": 100 * 2 * bwd / 9e-3})
    assert all(0 < got[n] < 100 for n in SHARES)
    assert any("2 calls a step" in line and "bound by bytes" in line
               for line in run.notes)
    # the entry the builder states is not eaten by a reader
    assert run.kernel_shapes["ssd_scan"]["calls"] == 2
    # the shared readers take the new scopes for what they are
    assert mf.load_module("layers", "moe_ffn.ms").read(run) == \
        pytest.approx(0.5 + 6.0)
    assert mf.load_module("layers", "ssm_scan.ms").read(run) is None


def test_new_readers_read_nothing_from_a_program_without_them():
    """The parent's program under this PR's benchmark files (another
    family's cell): no ``hvd.ssd_scan`` or ``hvd.moe_latent`` scope and no
    ``ssd_scan`` shape; nothing is reported and nothing raises."""
    old = [(op[0], op[1], op[2],
            op[3].replace("hvd.ssd_scan", "hvd.selective_scan")
            .replace("hvd.moe_latent", "hvd.moe_ffn")) for op in OPS]
    run = _traced_run(old, shapes={"selective_scan": dict(
        batch=1, seq=8192, d_inner=5120, d_state=16)})
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(run) is None, name
    untraced = types.SimpleNamespace(trace=None, peak=None, kernel_shapes={},
                                     note=lambda text: None)
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(untraced) is None, name
    # a cell with the shape and nothing under the scope in its trace
    bare = _traced_run([op for op in OPS if "hvd.ssd_scan" not in op[3]])
    for name in SHARES:
        assert mf.load_module("layers", name).read(bare) is None, name
