"""The sparse-attention / routed-experts builder under the real harness at
a tiny size on the CPU (tests/benchmark/bench_tiny_sparse.py): a sound run
is correct, and the faults the comparison exists to catch are not; the
cell's files, FLOP and kernel cost functions against hand-worked numbers.
"""

import jax
import pytest

import horovod_tpu as hvd
from benchmarks.lib import (compare, flops_sparse_moe, harness, kernels,
                            kernels_sparse, manifest as mf, peaks,
                            reference_sparse_moe)

import bench_tiny_sparse as tiny

MANIFEST = mf.load()
CELL = "keye-vl2-30b-a3b.train-16k-1chip"


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


@pytest.mark.parametrize("seed", [1, 10])
def test_sound_run_is_correct(session_mesh_restored, tmp_path, seed):
    lines = []
    result = _run(tmp_path, seed, lines)
    assert result["correct"] is True, "\n".join(lines)
    assert result["failed"] == 0 and result["attempted"] > 4
    assert {"tokens_per_s_per_chip", "setup_s"} <= set(result["metrics"])
    for name in compare.NUMBERS + ("non_finite_losses",
                                   "compilations_in_window"):
        assert " limit " in _row(lines, name) and "ok" in _row(lines, name)
    for kernel in ("hvd_sparse_attn_fwd", "hvd_sparse_attn_bwd",
                   "hvd_index_select"):
        assert "ok" in _row(lines, f"{kernel}_in_program")


def test_an_expert_share_dropped_is_not_correct(session_mesh_restored,
                                                tmp_path, monkeypatch):
    """One of the four held experts adds nothing (its output weights read
    as zero): every step is as fast, the loss hardly moves, and the
    gradient and the change of that expert's weights give it away."""
    import horovod_tpu.models.sparse_moe_decoder as model

    real = model.moe_ffn_dropless

    def dropped(x, params, **kw):
        return real(x, dict(params, w2=params["w2"].at[-1].set(0.0)), **kw)

    monkeypatch.setattr(model, "moe_ffn_dropless", dropped)
    lines = []
    result = _run(tmp_path, 3, lines)
    assert result["correct"] is False
    assert "FAIL" in _row(lines, "grad_norm_gap")
    assert "FAIL" in _row(lines, "delta_norm_gap")
    assert "ok" in _row(lines, "loss_gap")


def test_a_selection_of_half_the_keys_is_not_correct(session_mesh_restored,
                                                     tmp_path, monkeypatch):
    """Attention over topk/2 keys a query is twice as sparse and as fast
    again: not correct, by the gradient."""
    import horovod_tpu.models.sparse_moe_decoder as model

    real = model.sparse_attention
    monkeypatch.setattr(
        model, "sparse_attention",
        lambda *a, topk, **kw: real(*a, topk=topk // 2, **kw))
    lines = []
    result = _run(tmp_path, 4, lines)
    assert result["correct"] is False
    assert "FAIL" in _row(lines, "grad_norm_gap")


@pytest.mark.parametrize("seed", [1, 2, 4])
def test_lower_precision_control_is_not_correct(session_mesh_restored, seed):
    """The control: the reference with float8 matmul operands (index
    scores and router included) put in the program's place. It has to fail
    a number of the cell (the gradient), not each."""
    session = mf.load_module("builders", "sparse_moe_decoder").build(
        tiny.CONFIG, tiny.JOB, jax.devices()[:1])
    sound = session.reference(seed, tiny.LIMITS["steps"])
    low = session.reference(seed, tiny.LIMITS["steps"], precision="float8")
    verdict = {name: ok for name, _, _, ok, _ in
               compare.judge(low, sound, tiny.LIMITS)}
    assert verdict["grad_norm_gap"] is False
    assert not all(verdict.values())


def test_manifest_stays_valid_and_the_cells_files_are_found():
    assert mf.validate(MANIFEST) == []
    cell = mf.cell(MANIFEST, CELL)
    assert (cell["chips"], cell["traffic"]) == (1, "train-16k-1chip")
    config = mf.config_of(MANIFEST, cell["config"])
    job = mf.job_of(cell["traffic"])
    assert (job["seq_len"], job["pool_batches"]) == (16384, 8)   # ISSUE 26
    assert config["builder"] == "sparse_moe_decoder"
    # every published number of the catalog row, the cut ones apart
    published = {
        "head_dim": 128, "hidden_size": 2048, "intermediate_size": 6144,
        "max_position_embeddings": 262144, "moe_intermediate_size": 768,
        "num_attention_heads": 32, "num_experts": 128,
        "num_experts_per_tok": 8, "num_hidden_layers": 48,
        "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
        "rope_theta": 10000000, "decoder_sparse_step": 1,
        "max_window_layers": 48}
    assert {k: config[k] for k in published} == published
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert (config["layers"], config["num_local_experts"],
            config["vocab_size"]) == (6, 16, 18992)
    assert sorted(config["reduced"]) == ["layers", "num_local_experts",
                                         "vocab_size"]
    assert set(config["reduced"]) <= set(config["departures"])
    limits = mf.limits_of(CELL)
    assert set(compare.NUMBERS) <= set(limits) and "set_from" in limits


def test_parameter_count_is_the_configurations():
    """96.9M a layer, 659M in all: 10.5 GB at 16 bytes a parameter."""
    import math

    s = reference_sparse_moe.sizes_from_config(
        mf.config_of(MANIFEST, "keye-vl2-30b-a3b"))
    flat = jax.tree_util.tree_leaves(
        reference_sparse_moe.param_shapes(s),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[1], tuple))
    total = sum(math.prod(shape) for shape, _ in flat)
    # layer: attention 18,874,368 + q/k norms 256 + indexer 2,260,992 +
    # router 262,144 + two norms 4,096 + 16 experts x 4,718,592
    layer = 18_874_368 + 256 + 2_260_992 + 262_144 + 4_096 + 16 * 4_718_592
    assert layer == 96_899_328
    assert total == 6 * layer + 2 * 18_992 * 2_048 + 2_048 == 659_189_248


def test_train_flops_per_token_by_hand():
    s = reference_sparse_moe.sizes_from_config(
        mf.config_of(MANIFEST, "keye-vl2-30b-a3b"))
    # weights a token multiplies in a layer: attention 2048 * 128 * (64 + 8)
    # = 18,874,368; router 262,144; 8 * 16/128 = 1 expert of 3 * 2048 * 768
    assert flops_sparse_moe.layer_matmul_weights(s) == 23_855_104
    assert flops_sparse_moe.indexer_weights(s) == 2_260_992
    # mean_t min(t + 1, 2048) at T = 16384: (2048 * 2049 / 2 + 14336 * 2048)
    # / 16384
    assert flops_sparse_moe.mean_selected(16384, 2048) == 1920.0625
    assert flops_sparse_moe.mean_selected(1024, 2048) == 512.5
    # a layer: 6 * 23,855,104 + 2 * 2,260,992 + 2 * 1024 * 8192.5
    # + 12 * 32 * 128 * 1920.0625 = 143,130,624 + 4,521,984 + 16,778,240
    # + 94,374,912 = 258,805,760; six of them + 6 * 18,992 * 2,048
    assert flops_sparse_moe.train_flops_per_token(s, 16384) == \
        6 * 258_805_760 + 233_373_696 == 1_786_208_256


def test_kernel_costs_by_hand():
    shape = dict(batch=1, seq=16384, heads=32, kv_heads=4, head_dim=128,
                 topk=2048)
    pairs = 2048 * 2049 // 2 + 14336 * 2048
    assert kernels_sparse.selected_pairs(16384, 2048) == pairs == 31_458_304
    fwd_flops, fwd_bytes = kernels_sparse.sparse_attn_fwd_cost(**shape)
    assert fwd_flops == 4 * pairs * 32 * 128 == 515_412_852_736
    # q and o 128 MiB each, k and v 16 MiB each, a byte a selected pair,
    # the fp32 log-sum-exp rows
    assert fwd_bytes == 2 * 2 ** 27 + 2 * 2 ** 24 + pairs + 32 * 16384 * 4
    bwd_flops, bwd_bytes = kernels_sparse.sparse_attn_bwd_cost(**shape)
    assert bwd_flops == 2 * fwd_flops
    assert bwd_bytes == (3 * 2 ** 27 + 4 * 2 ** 24 + pairs
                         + 2 * 32 * 16384 * 4)
    idx_flops, idx_bytes = kernels_sparse.index_select_cost(
        batch=1, seq=16384, idx_heads=16, idx_dim=64, topk=2048)
    causal = 16384 * 16385 // 2
    assert idx_flops == 2 * causal * 1024
    assert idx_bytes == 16384 * (2048 + 128 + 64) + causal + 16384 * 4
    # the forward at the cell's shape: 2.62 ms, bound by FLOPs; a kernel
    # that computes every causal pair (4.27 x the selected ones) cannot
    # read above 23.4% of it
    peak = peaks.for_device_kind("TPU v5 lite")
    secs, bound = kernels.roofline(fwd_flops, fwd_bytes, peak)
    assert bound == "flops" and secs == pytest.approx(2.616e-3, rel=1e-3)
    assert pairs / causal == pytest.approx(0.2344, abs=1e-4)


# -- the five readers ---------------------------------------------------------

NEW_READERS = ("sparse_attention.ms", "sparse_indexer.ms", "moe_ffn.ms",
               "sparse_attn_fwd_roofline", "sparse_attn_bwd_roofline")
SHAPES = {"sparse_attention": dict(
    batch=1, seq=16384, heads=32, kv_heads=4, head_dim=128, topk=2048,
    idx_heads=16, idx_dim=64, act_bytes=2, calls_per_step=1)}


def _reader_run(ops=None):
    """What a reader is handed: nothing traced, or two steps of ``ops``
    [(HLO text, start, seconds, path)] on the first device."""
    import types

    from benchmarks.lib import scopes

    run = types.SimpleNamespace(
        trace=None, chips=1, kernel_shapes=SHAPES, notes=[],
        peak=peaks.for_device_kind("TPU v5 lite"))
    run.note = run.notes.append
    if ops is not None:
        run.trace = object()
        run.scoped_ops = scopes.ScopedOps(
            [(n, t + step, d, p) for step in (0.0, 1.0)
             for n, t, d, p in ops], [(0.0, 1.0), (1.0, 2.0)])
    return run


@pytest.mark.parametrize("name", NEW_READERS)
def test_reader_is_declared_and_reads_nothing_where_nothing_is(name):
    """A program without the scopes and kernels (the parent commit, a GPT-2
    cell) gives None and raises nothing."""
    reader = mf.load_module("layers", name)
    entry = next(m for m in MANIFEST["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["layer"], entry["moves"]) == (
        reader.UNIT, reader.LAYER, reader.MOVES)
    assert CELL in entry["workloads"]
    assert reader.read(_reader_run()) is None
    plain = [("%fusion.1 = f32[8] fusion(%p)", 0.1, 0.2,
              "jit(spmd)/hvd.grad/jvp(GPT)/h0/attn/hvd.flash_attention/mul")]
    assert reader.read(_reader_run(plain)) is None


def test_readers_read_scopes_and_kernels_by_name():
    """0.1 s of forward kernel, 0.05 + 0.07 of backward kernels, 0.02 of
    layout under the attention scope, 0.03 of indexer, 0.04 of experts
    under their scope and 0.01 of grouped matmul under the compiler's own
    path a step; an op that consumes a kernel's result is not the
    kernel."""
    base = "jit(spmd)/hvd.grad/jvp(SparseMoEDecoder)/h0/"
    back = "jit(spmd)/hvd.grad/transpose(jvp(SparseMoEDecoder))/h0/"
    att = "attn/hvd.sparse_attention/"
    ops = [
        ("%hvd_index_select.3 = (s8[1,64,64]) custom-call(%a)", 0.00, 0.03,
         base + "attn/hvd.sparse_indexer/hvd_index_select/pallas_call"),
        ("%hvd_sparse_attn_fwd.2 = (bf16[4]) custom-call(%q)", 0.05, 0.10,
         base + att + "hvd_sparse_attn_fwd/pallas_call"),
        ("%copy.9 = bf16[4] copy(%hvd_sparse_attn_fwd.2)", 0.16, 0.02,
         base + att + "transpose"),
        ("%fusion.7 = bf16[8] fusion(%x)", 0.20, 0.04,
         base + "moe/hvd.moe_ffn/gather"),
        ("%ragged-dot-none.1 = bf16[8] custom-call(%x)", 0.25, 0.01,
         "ragged-dot-none:"),
        ("%hvd_sparse_attn_bwd_dq.1 = bf16[4] custom-call(%q)", 0.30, 0.05,
         back + att + "hvd_sparse_attn_bwd_dq/pallas_call"),
        ("%hvd_sparse_attn_bwd_dkv.1 = (bf16[4]) custom-call(%q)", 0.40,
         0.07, back + att + "hvd_sparse_attn_bwd_dkv/pallas_call"),
    ]
    read = {n: mf.load_module("layers", n).read(_reader_run(ops))
            for n in NEW_READERS}
    assert read["sparse_indexer.ms"] == pytest.approx(30.0)
    assert read["sparse_attention.ms"] == pytest.approx(240.0)
    assert read["moe_ffn.ms"] == pytest.approx(50.0)
    # a program with grouped matmuls and no expert layer of ours: nothing
    assert mf.load_module("layers", "moe_ffn.ms").read(
        _reader_run(ops[4:5])) is None
    # least 2.616 ms forward, 5.233 ms backward over the selected pairs
    assert read["sparse_attn_fwd_roofline"] == pytest.approx(2.616, rel=1e-3)
    assert read["sparse_attn_bwd_roofline"] == pytest.approx(
        100 * 5.2326e-3 / 0.12, rel=1e-3)


BACK = ("jit(spmd)/hvd.grad/transpose(jvp(SparseMoEDecoder))/h0/attn/"
        "hvd.sparse_attention/")


def _backward(name, start, seconds):
    return (f"%{name} = (bf16[4]) custom-call(%q)", start, seconds,
            BACK + name.split(".")[0] + "/pallas_call")


@pytest.mark.parametrize("kernels_of_a_backward", [
    [("hvd_sparse_attn_bwd_dq.1", 0.05), ("hvd_sparse_attn_bwd_dkv.1", 0.07)],
    [("hvd_sparse_attn_bwd.4", 0.12)],
    [("hvd_sparse_attn_bwd_dq", 0.03), ("hvd_sparse_attn_bwd_dk.2", 0.04),
     ("hvd_sparse_attn_bwd_dv.2", 0.05)],
], ids=["dq_and_dkv", "one_kernel", "three_kernels"])
@pytest.mark.parametrize("calls", [1, 3])
def test_backward_roofline_counts_by_prefix_over_the_calls_a_step(
        kernels_of_a_backward, calls):
    """The backward is every kernel whose name begins
    ``hvd_sparse_attn_bwd``: 0.12 s a call whether two kernels make it up,
    one or three, over ``calls_per_step`` calls a step; the forward kernel,
    a consumer of a backward kernel's result and an event outside the
    traced steps are not part of it."""
    ops = [("%hvd_sparse_attn_fwd.2 = (bf16[4]) custom-call(%q)", 0.0, 0.01,
            BACK + "hvd_sparse_attn_fwd/pallas_call")]
    for call in range(calls):
        at = 0.02 + 0.3 * call
        for name, seconds in kernels_of_a_backward:
            ops.append(_backward(name, at, seconds))
            at += seconds
        ops.append((f"%copy.{call} = bf16[4] copy(%hvd_sparse_attn_bwd_dq.1)",
                    at, 0.01, BACK + "transpose"))
    run = _reader_run(ops)
    run.kernel_shapes = {"sparse_attention": dict(
        SHAPES["sparse_attention"], calls_per_step=calls)}
    run.scoped_ops.ops.append(_backward("hvd_sparse_attn_bwd_dq.9", 2.5, 9.0))
    reader = mf.load_module("layers", "sparse_attn_bwd_roofline")
    assert reader.read(run) == pytest.approx(100 * 5.2326e-3 / 0.12,
                                             rel=1e-3)
    assert f"{2 * calls} backward calls" in run.notes[-1]
    # a builder that does not say how many calls a step makes: nothing
    run.kernel_shapes = SHAPES | {"sparse_attention": {
        k: v for k, v in SHAPES["sparse_attention"].items()
        if k != "calls_per_step"}}
    assert reader.read(run) is None

