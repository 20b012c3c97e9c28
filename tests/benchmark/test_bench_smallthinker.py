"""The ``smallthinker`` builder (a router that reads the block's input ahead
of attention, ReLU-gated experts, a NoPE full layer and sliding layers at 7
query heads a KV head) under the real harness at a tiny size on the CPU
(tests/benchmark/bench_tiny_smallthinker.py): a sound run is correct, and
the faults the comparison exists to catch are not; the cell's files and
FLOP count against hand-worked numbers; the new readers on hand-made ops.
Everything about the manifest is held by MEMBERSHIP, not position: the next
cell does not turn it red.
"""

import dataclasses
import importlib.util
import json
import math
import os
import types

import jax
import pytest

import horovod_tpu as hvd
from benchmarks.lib import (compare, flops_smallthinker as flops, harness,
                            kernels, kernels_window, manifest as mf, peaks,
                            reference_smallthinker as ref, scopes)
from horovod_tpu.monitor.span_audit import DEVICE_SCOPES

import bench_tiny_smallthinker as tiny

MANIFEST = mf.load()
CONFIG, CELL = "smallthinker-21b-a3b", "smallthinker-21b-a3b.train-16k-1chip"
SHARES = ("swa_attn_fwd_roofline", "swa_attn_bwd_roofline",
          "nope_attn_fwd_roofline", "nope_attn_bwd_roofline")
NEW_METRICS = ("moe_route.ms", "attention.swa_ms") + SHARES
JOINED = ("step.forward_ms", "step.backward_ms", "step.optimizer_ms",
          "step.unscoped_pct", "head_loss.ms", "attention.ms",
          "attention.layout_ms", "moe_ffn.ms", "step.interval_p90_ms")
# The lists a test of the accepted benchmark pins letter for letter.
PINNED = ("proj.ms", "mlp.ms", "norm.ms", "rotary.ms", "grad.unowned_ms",
          "grad.remat_ms", "optimizer.ms", "step.unowned_pct",
          "step.mixed_pct", "moe.tiles_per_step", "attention.window_ms",
          "window_attn_fwd_roofline", "window_attn_bwd_roofline",
          "gqa_attn_fwd_roofline", "gqa_attn_bwd_roofline")


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
    for name in ("hvd_flash_fwd_win_in_program", "hvd_flash_bwd_dkv_in_program",
                 "grouped_matmuls_in_program",
                 "layers_routed_ahead_of_attention"):
        assert "ok" in _row(lines, name)


def _fault(monkeypatch, **overrides):
    """The model built with one field of its configuration wrong."""
    from horovod_tpu.models import SparseMoEConfig

    real = SparseMoEConfig.from_dict.__func__
    monkeypatch.setattr(SparseMoEConfig, "from_dict", classmethod(
        lambda cls, cfg, **kw: dataclasses.replace(real(cls, cfg, **kw),
                                                   **overrides)))


@pytest.mark.parametrize("overrides, fails, seed", [
    # SiLU where ReLU belongs: every expert's gradient, by a third
    (dict(expert_activation="silu"), ("grad_norm_gap",), 3),
    # the router fed the normed stream after attention
    (dict(router_input="mlp_input"), ("grad_norm_gap",), 4)])
def test_a_fault_is_not_correct(session_mesh_restored, tmp_path,
                                monkeypatch, overrides, fails, seed):
    _fault(monkeypatch, **overrides)
    lines = []
    result = _run(tmp_path, seed, lines)
    assert result["correct"] is False, "\n".join(lines)
    for name in fails:
        assert "FAIL" in _row(lines, name), "\n".join(lines)
    if "router_input" in overrides:    # ... and no routing stands ahead
        assert " 0 limit ==2" in _row(lines,
                                      "layers_routed_ahead_of_attention")


@pytest.mark.parametrize("seed", [1, 3, 5])
def test_lower_precision_control_is_not_correct(session_mesh_restored, seed):
    """The control: the reference with float8 matmul operands (router
    included) put in the program's place. It has to fail a number of the
    cell, not each."""
    session = mf.load_module("builders", "smallthinker").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    sound = session.reference(seed, tiny.LIMITS["steps"])
    low = session.reference(seed, tiny.LIMITS["steps"], precision="float8")
    rows = compare.judge(low, sound, tiny.LIMITS)
    # finite on every number: a control that overflows tells nothing
    assert all(math.isfinite(value) for _, value, _, _, _ in rows)
    verdict = {name: ok for name, _, _, ok, _ in rows}
    assert verdict["grad_norm_gap"] is False


def test_the_faults_script_reads_both_faults(session_mesh_restored,
                                             tmp_path):
    """scripts/smallthinker_faults.py (PERF.md section 2's fault readings
    at the cell's size) at the tiny size: both faults, not correct."""
    spec = importlib.util.spec_from_file_location(
        "smallthinker_faults",
        os.path.join(mf.ROOT, "scripts", "smallthinker_faults.py"))
    script = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(script)
    root = tiny.make_root(tmp_path)
    assert script.main(["--workload", tiny.CELL, "--seeds", "2", "--root",
                        root, "--any-device", "--dir", root]) == 0
    with open(os.path.join(root, f"faults.{tiny.CELL}.json")) as f:
        rows = json.load(f)
    assert [r["fault"] for r in rows] == ["silu", "mlp_input"]
    for row in rows:
        assert row["correct"] is False
        value, limit, ok, _ = row["grad_norm_gap"]
        assert ok is False and value > 2 * limit


@pytest.mark.parametrize("tokens, scale", [(4096, 2.0 ** -4), (256, 1.0)])
def test_the_control_trains_under_a_loss_scale(tokens, scale):
    """``finite_under_scale``: float8's cotangents are e4m3 too, which
    turns what passes 448 into NaN. A weight's gradient summed over 4,096
    tokens of ones reads 4,096: NaN as it stands, finite and exact once the
    loss is scaled by 2^-4 (256 an entry); one summed over 256 tokens keeps
    the scale it was handed."""
    import jax.numpy as jnp

    from benchmarks.lib import reference_smallthinker as ref
    from benchmarks.lib.reference_gpt2 import _mm

    mm = _mm("float8")
    x = jnp.ones((tokens, 8), jnp.float32)
    w = jnp.full((8, 4), 0.5, jnp.float32)

    def run(scale):
        loss, g = jax.value_and_grad(
            lambda w: scale * mm("tc,cf->tf", x, w).sum())(w)
        return {"loss": [loss / scale],
                "grad_norm": {"w": jnp.linalg.norm(g / scale)}}

    out, used = ref.finite_under_scale(run)
    assert used == scale
    assert float(out["loss"][0]) == tokens * 8 * 4 * 0.5
    assert float(out["grad_norm"]["w"]) == pytest.approx(
        tokens * math.sqrt(32), rel=1e-6)


def test_a_loss_scale_moves_nothing_in_the_reference(session_mesh_restored):
    """A power of two scales exactly: the float32 reference under
    ``loss_scale`` reads what it reads without one."""
    from benchmarks.builders import smallthinker

    session = mf.load_module("builders", "smallthinker").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    plain = session.reference(1, 2)
    from benchmarks.builders import gpt_decoder
    from benchmarks.lib import traffic

    toks = traffic.token_pool(tiny.JOB, seed=1, global_batch=1,
                              vocab=session.sizes["vocab"])[:2]
    fn = smallthinker._reference_fn(
        gpt_decoder._freeze(session.sizes), gpt_decoder._freeze(tiny.CONFIG["optimizer"]),
        1, tiny.CONFIG["reference"]["q_block"], "float32")
    scaled = gpt_decoder.as_floats(jax.device_get(
        fn(session._seed(1), toks, loss_scale=2.0 ** -6)))
    assert scaled["loss"] == plain["loss"]
    for name in ("grad_norm", "delta_norm"):
        for leaf, value in plain[name].items():
            assert scaled[name][leaf] == pytest.approx(value, rel=1e-6)


def test_the_program_warms_up_as_its_reference(session_mesh_restored):
    """Two steps under a warm-up of 4 train at a quarter and at a half of
    the rate: AdamW's first steps are sign steps, so the parameters move
    between a quarter and a half as far as under a warm-up of 1, which IS
    the whole rate, and the program moves them as its reference does."""
    def change(steps):
        config = dict(tiny.CONFIG, optimizer=dict(
            tiny.CONFIG["optimizer"], warmup_steps=steps))
        session = mf.load_module("builders", "smallthinker").build(
            config, tiny.JOB, jax.devices()[:1])
        session.init_state(7)
        session.place_inputs(7)
        session.compile()
        program = harness.checked_steps(session, seed=7, steps=2)
        reference = session.reference(7, 2)["delta_norm"]
        for leaf, value in reference.items():
            assert program["delta_norm"][leaf] == pytest.approx(
                value, rel=0.05), (steps, leaf)
        return sum(v ** 2 for v in reference.values()) ** 0.5

    assert 0.25 < change(4) / change(1) < 0.5


def test_structure_rows_read_the_text():
    builder = mf.load_module("builders", "smallthinker")
    text = ("HloModule m\n\nfused {\n x = op_name=\"a/h1/attn/hvd_flash_fwd\"\n}\n"
            "\nENTRY main {\n"
            " a = f32[] op_name=\"jit/h0/moe/hvd.moe_route/dot\"\n"
            " b = f32[] custom-call() op_name=\"jit/h0/attn/hvd_flash_fwd\"\n"
            " c = f32[] custom-call() op_name=\"jit/h1/attn/hvd_flash_fwd_win\"\n"
            " d = f32[] op_name=\"jit/h1/moe/hvd.moe_route/dot\"\n"
            " e = f32[] op_name=\"jit/transpose(jvp)/h2/moe/hvd.moe_route/dot\"\n"
            " f = f32[] custom-call() op_name=\"jit/h2/attn/hvd_flash_fwd_win\"\n"
            "}\n")
    # h0 routes ahead; h1's routing stands after its kernel; h2 has only a
    # backward instruction under the scope
    assert builder.layers_routed_ahead(text, 3) == 1
    assert builder.whole_name_count(text, "hvd_flash_fwd") == 2
    assert builder.whole_name_count(text, "hvd_flash_fwd_win") == 2


# -- the cell's files ---------------------------------------------------------

def test_manifest_stays_valid_and_holds_the_new_entries():
    assert mf.validate(MANIFEST) == []
    with open(os.path.join(mf.ROOT, "BENCHMARK.json"), "rb") as f:
        assert len(f.read()) < 64 * 1024
    cell = mf.cell(MANIFEST, CELL)
    assert cell in MANIFEST["workloads"]
    assert (cell["chips"], cell["config"], cell["traffic"]) == (
        1, CONFIG, "train-16k-1chip")
    assert len(cell["why"]) <= 200
    for said in ("43.7%", "7 heads a KV head", "1,536", "seeds spread"):
        assert said in cell["why"], said
    assert sum(w["chips"] == 4 for w in MANIFEST["workloads"]) == 1
    entry = next(c for c in MANIFEST["configs"] if c["name"] == CONFIG)
    config = mf.config_of(MANIFEST, CONFIG)
    assert (entry["reduced"], entry["source"], entry["file"]) == (
        config["reduced"], config["source"],
        "benchmarks/configs/smallthinker-21b-a3b.json")
    job = mf.job_of(cell["traffic"])
    assert (job["kind"], job["seq_len"], job["tokens"],
            job["pool_batches"]) == ("closed_loop_training", 16384,
                                     "uniform", 8)              # ISSUE 45
    e2e = {m["name"] for m in mf.metrics_for(MANIFEST, "end_to_end", CELL)}
    assert e2e == {"tokens_per_s_per_chip", "mfu_pct", "setup_s"}
    limits = mf.limits_of(CELL)
    assert set(compare.NUMBERS) <= set(limits) and "set_from" in limits
    assert limits["steps"] in (1, 2)
    for name in compare.NUMBERS:
        assert limits["set_from"][name]


def test_configuration_holds_every_published_width():
    config = mf.config_of(MANIFEST, CONFIG)
    from bench_tiny_smallthinker import CATALOG

    cut = set(config["reduced"])
    assert cut == {"layers", "sliding_window_layout", "rope_layout",
                   "num_local_experts", "vocab_size"}
    for key, value in CATALOG.items():
        if key not in cut:
            assert config[key] == value, key
    assert (config["hidden_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["moe_num_primary_experts"],
            config["moe_num_active_primary_experts"],
            config["moe_ffn_hidden_size"], config["sliding_window_size"],
            config["rope_theta"], config["rms_norm_eps"],
            config["num_hidden_layers"]) == (
        2560, 28, 4, 128, 64, 6, 768, 4096, 1500000, 1e-06, 52)
    assert (config["layers"], config["sliding_window_layout"],
            config["rope_layout"], config["num_local_experts"],
            config["first_local_expert"]) == (4, [0, 1, 1, 1], [0, 1, 1, 1],
                                              16, 0)
    assert config["vocab_size"] in (37984, 18992)
    assert config["vocab_size"] * (4 if config["vocab_size"] == 37984
                                   else 8) == 151936
    assert cut <= set(config["departures"])
    for key, said in (("layers", "52"), ("num_local_experts", "64"),
                      ("vocab_size", "151,936"), ("rope_layout", "52"),
                      ("sliding_window_layout", "52")):
        assert said in config["departures"][key]
    for key in ("assumed", "deployment", "memory", "catalog", "source"):
        assert config[key]
    for key in ("router_input", "router", "expert", "no_secondary_experts",
                "aux_loss", "block", "rope", "window", "initializer_range",
                "precision", "remat"):
        assert config["assumed"][key]
    assert "thirteen pipeline stages" in config["deployment"]
    assert "compiled" in config["memory"]
    assert (config["builder"], config["per_chip_batch"]) == ("smallthinker",
                                                             1)
    # the other mixture cells' AdamW, number for number, behind a warm-up
    keye = mf.config_of(MANIFEST, "keye-vl2-30b-a3b")
    assert config["optimizer"] == dict(keye["optimizer"], warmup_steps=2000)
    assert "warm-up" in config["assumed"]["optimizer"]


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
    if hasattr(reader, "SCOPE"):
        assert reader.SCOPE in DEVICE_SCOPES


def test_the_new_cell_joins_the_nine_lists_and_no_pinned_one():
    mine = {m["name"] for m in mf.metrics_for(MANIFEST, "per_layer", CELL)}
    assert set(JOINED) | set(NEW_METRICS) <= mine
    assert not mine & set(PINNED)
    for name in JOINED:
        entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
        assert CELL in entry["workloads"]
    # every metric without a list is read here too
    free = {m["name"] for m in MANIFEST["per_layer"] if "workloads" not in m}
    assert free <= mine


def _sizes():
    return ref.sizes_from_config(mf.config_of(MANIFEST, CONFIG))


def _parameters(s):
    flat = jax.tree_util.tree_leaves(
        ref.param_shapes(s), is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[1], tuple))
    return sum(math.prod(shape) for shape, _ in flat)


def test_parameter_count_is_the_issues():
    """q 9,175,040 + k, v 2,621,440 + o 9,175,040 + router 163,840 + two
    norms 5,120 = 21,140,480 a layer outside its experts; 16 experts of
    5,898,240; embedding and head 2 x 37,984 x 2,560; the final norm:
    656,529,920, 10.50 GB at 16 bytes a parameter (ISSUE 45); with an
    eighth of the vocabulary 559,290,880."""
    s = _sizes()
    outside = 9_175_040 + 2_621_440 + 9_175_040 + 163_840 + 5_120
    assert outside == 21_140_480
    assert flops.expert_weights(s) == 5_898_240
    layer = outside + 16 * 5_898_240
    assert layer == 115_512_320
    want = {37984: 656_529_920, 18992: 559_290_880}[s["vocab"]]
    assert 4 * layer + 2 * s["vocab"] * 2560 + 2560 == want
    assert _parameters(s) == want == flops.parameter_count(s)
    quarter = dict(s, vocab=37984)
    assert _parameters(quarter) == 656_529_920
    assert round(656_529_920 * 16 / 1e9, 2) == 10.50
    assert _parameters(dict(s, vocab=18992)) == 559_290_880
    # whole, a layer is 398.6M parameters: a chip holds two and no more
    whole = outside + 64 * 5_898_240
    assert round(whole / 1e6, 1) == 398.6


def test_train_flops_are_the_issues_terms():
    s, T = dict(_sizes(), vocab=37984), 16384
    assert flops.attention_weights(s) == 20_971_520
    assert 6 * flops.layer_matmul_weights(s) == 6 * (
        20_971_520 + 163_840 + 1.5 * 5_898_240)
    assert round(6 * flops.layer_matmul_weights(s) / 1e6, 1) == 179.9
    assert 12 * 28 * 128 == 43_008
    assert flops.mean_visible(s, 0, T) == 8192.5          # the full layer
    assert flops.mean_visible(s, 1, T) == 3584.125        # a sliding one
    assert round(43_008 * 8192.5 / 1e6, 1) == 352.3
    assert round(43_008 * 3584.125 / 1e6, 1) == 154.1
    assert round(6 * 37_984 * 2_560 / 1e6, 1) == 583.4
    per_token = flops.train_flops_per_token(s, T)
    assert per_token == (4 * 6 * flops.layer_matmul_weights(s)
                         + 43_008 * (8192.5 + 3 * 3584.125)
                         + 6 * 37_984 * 2_560)
    # ISSUE 45 says 2,117.7: its rounded terms, summed
    assert per_token == 2_117_800_704 and round(per_token / 1e6, 1) == 2117.8
    assert round(per_token * T / 1e12, 1) == 34.7
    pairs = 43_008 * (8192.5 + 3 * 3584.125)
    assert 0.37 < pairs / per_token < 0.39               # "38%"
    held = 4 * 6 * 1.5 * 5_898_240
    assert 0.09 < held / per_token < 0.11                # "10%"
    # the band is 43.7% of the causal pairs
    assert round(kernels_window.visible_pairs(T, 4096)
                 / kernels_window.visible_pairs(T), 3) == 0.437


def test_kernel_shapes_and_costs_by_hand(session_mesh_restored):
    session = mf.load_module("builders", "smallthinker").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    call = dict(batch=1, seq=128, heads=7, kv_heads=1, head_dim=16,
                act_bytes=2)
    assert session.kernel_shapes == {
        "swa_attention": dict(call, window=32),
        "nope_attention": dict(call, window=None)}
    assert session.tokens_per_step == 128
    shape = dict(batch=1, seq=16384, heads=28, kv_heads=4, head_dim=128,
                 act_bytes=2)
    band = 4096 * 4097 // 2 + 12288 * 4096
    q, kv, rows = 16384 * 28 * 128 * 2, 16384 * 4 * 128 * 2, 28 * 16384 * 4
    assert kernels_window.attn_fwd_cost(**shape, window=4096) == (
        28 * band * 4 * 128, 2 * q + 2 * kv + rows)
    assert kernels_window.attn_bwd_cost(**shape, window=None) == (
        28 * (16384 * 16385 // 2) * 8 * 128, 3 * q + 4 * kv + 2 * rows)
    peak = peaks.for_device_kind("TPU v5 lite")
    least, bound = kernels.roofline(
        *kernels_window.attn_fwd_cost(**shape, window=None), peak)
    assert bound == "flops" and least == pytest.approx(9.77e-3, rel=5e-3)


# -- the new readers on hand-made ops -----------------------------------------

GRAD = "jit(spmd)/shard_map/hvd.grad/"
BACK = GRAD + "transpose(hvd.grad)/"
H0 = "jvp(SparseMoEDecoder)/h0/"
H1 = "jvp(SparseMoEDecoder)/h1/"
ROUTE = "moe/hvd.moe_route/"
FULL = "attn/hvd.flash_attention/"
WIN = FULL + "hvd.flash_window/"
SHAPES = {
    "swa_attention": dict(batch=1, seq=16384, heads=28, kv_heads=4,
                          head_dim=128, window=4096, act_bytes=2),
    "nope_attention": dict(batch=1, seq=16384, heads=28, kv_heads=4,
                           head_dim=128, window=None, act_bytes=2)}


def _kernel(name, n, start, dur, path):
    return (f"%{name}.{n} = (bf16[1,16384,3584]) custom-call()", start, dur,
            path + name + "/pallas_call")


# One step of 400 ms: h0 routes (0.4 ms), its full call (60); h1 routes
# (0.5), its windowed call (27) with a layout op (0.5); the walk's grouped
# matmul (5); then the backward: the windowed dq and dk/dv (20, 25), the
# full ones (45, 55), the router's backward (0.3) and its matmul made again
# in the rematerialised forward (0.2).
OPS = [
    ("%fusion.1 = f32[16384,64] fusion()", 0.000, 0.0004,
     GRAD + H0 + ROUTE + "dot_general"),
    _kernel("hvd_flash_fwd", 1, 0.001, 0.060, GRAD + H0 + FULL),
    ("%sort.1 = s32[98304] sort()", 0.062, 0.0005,
     GRAD + H1 + ROUTE + "sort"),
    _kernel("hvd_flash_fwd_win", 1, 0.063, 0.027, GRAD + H1 + WIN),
    ("%copy.1 = bf16[1,16384,3584] copy()", 0.091, 0.0005,
     GRAD + H1 + WIN + "reshape"),
    ("%ragged-dot-none.1 = bf16[512,768] custom-call()", 0.092, 0.005,
     ""),
    _kernel("hvd_flash_bwd_dq_win", 1, 0.100, 0.020, BACK + H1 + WIN),
    _kernel("hvd_flash_bwd_dkv_win", 1, 0.121, 0.025, BACK + H1 + WIN),
    _kernel("hvd_flash_bwd_dq", 1, 0.150, 0.045, BACK + H0 + FULL),
    _kernel("hvd_flash_bwd_dkv", 1, 0.200, 0.055, BACK + H0 + FULL),
    ("%fusion.2 = f32[2560,64] fusion()", 0.260, 0.0003,
     BACK + H0 + ROUTE + "transpose"),
    ("%fusion.3 = f32[16384,64] fusion()", 0.261, 0.0002,
     BACK + "rematted_computation/" + H0 + ROUTE + "dot_general"),
]


def _traced_run(ops, shapes=SHAPES):
    run = types.SimpleNamespace(
        trace=object(), peak=peaks.for_device_kind("TPU v5 lite"),
        kernel_shapes=shapes, notes=[])
    run.note = run.notes.append
    run.scoped_ops = scopes.ScopedOps(sorted(ops, key=lambda o: o[1]),
                                      [(0.0, 0.400)])
    return run


def test_new_readers_on_the_hand_made_step():
    run = _traced_run(OPS)
    got = {name: mf.load_module("layers", name).read(run)
           for name in NEW_METRICS}
    swa, nope = SHAPES["swa_attention"], SHAPES["nope_attention"]
    least = {
        "swa_f": kernels.roofline(*kernels_window.attn_fwd_cost(**swa),
                                  run.peak)[0],
        "swa_b": kernels.roofline(*kernels_window.attn_bwd_cost(**swa),
                                  run.peak)[0],
        "nope_f": kernels.roofline(*kernels_window.attn_fwd_cost(**nope),
                                   run.peak)[0],
        "nope_b": kernels.roofline(*kernels_window.attn_bwd_cost(**nope),
                                   run.peak)[0]}
    assert got == pytest.approx({
        "moe_route.ms": 0.4 + 0.5 + 0.3 + 0.2,
        "attention.swa_ms": 27 + 0.5 + 20 + 25,
        "swa_attn_fwd_roofline": 100 * least["swa_f"] / 0.027,
        "swa_attn_bwd_roofline": 100 * least["swa_b"] / 0.045,
        "nope_attn_fwd_roofline": 100 * least["nope_f"] / 0.060,
        "nope_attn_bwd_roofline": 100 * least["nope_b"] / 0.100})
    assert all(0 < got[n] < 100 for n in SHARES)
    assert any("visible pairs a head" in line for line in run.notes)
    # the shared readers take the new scopes for what they are
    assert mf.load_module("layers", "attention.ms").read(run) == \
        pytest.approx(60 + 27 + 0.5 + 20 + 25 + 45 + 55)
    assert mf.load_module("layers", "moe_ffn.ms").read(run) is None


def test_new_readers_read_nothing_from_a_program_without_them():
    """The parent's program under this PR's benchmark files (another
    family's cell): no ``hvd.moe_route`` scope and no ``swa_attention`` /
    ``nope_attention`` shape; nothing is reported and nothing raises."""
    old = [(op[0], op[1], op[2], op[3].replace(ROUTE, "moe/hvd.moe_ffn/"))
           for op in OPS]
    run = _traced_run(old, shapes={
        "window_attention": SHAPES["swa_attention"],
        "gqa_attention": SHAPES["nope_attention"]})
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(run) is None, name
    untraced = types.SimpleNamespace(trace=None, peak=None, kernel_shapes={},
                                     note=lambda text: None)
    for name in NEW_METRICS:
        assert mf.load_module("layers", name).read(untraced) is None, name
    # a cell with the shapes and no such kernel in its trace
    bare = _traced_run([op for op in OPS if "hvd_flash" not in op[0]])
    for name in SHARES:
        assert mf.load_module("layers", name).read(bare) is None, name
