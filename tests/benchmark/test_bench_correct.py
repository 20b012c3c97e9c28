"""``correct`` at a tiny size on the CPU mesh: sound runs pass, and the
faults the comparison exists to catch come out as not correct.

The harness's look for a chip is skipped (``run_cell`` is handed CPU
devices); everything after it is the code a chip run drives.
"""

import json

import jax
import pytest

import horovod_tpu as hvd
from benchmarks.lib import compare, harness, manifest as mf

import bench_tiny as tiny


@pytest.fixture()
def session_mesh_restored():
    """The builder owns hvd.init/shutdown; hand the session its mesh back."""
    yield
    hvd.shutdown()
    hvd.init()


def _run(tmp_path, cell, seed, lines=None):
    root = tiny.make_root(tmp_path)
    return harness.run_cell(
        cell, seed=seed, seconds=0.3, trace=False, root=root,
        devices=jax.devices()[:tiny.CELLS[cell]],
        log=(lines.append if lines is not None else lambda _: None))


def _row(lines, name):
    return next(ln for ln in lines if ln.startswith(f"[check] {name} "))


@pytest.mark.parametrize("cell", sorted(tiny.CELLS))
def test_sound_run_is_correct(session_mesh_restored, tmp_path, cell):
    lines = []
    result = _run(tmp_path, cell, 2147483900, lines)
    assert result["correct"] is True, "\n".join(lines)
    assert result["failed"] == 0 and result["attempted"] > 6
    # no mfu off a TPU; step_ms_p90 only where the short window held ten
    # intervals, which a loaded test machine may not give
    assert {"tokens_per_s_per_chip", "setup_s"} <= set(result["metrics"]) <= {
        "tokens_per_s_per_chip", "step_ms_p90", "setup_s"}
    assert result["device"]["count"] == tiny.CELLS[cell]
    assert result["device"]["memory_peak_bytes"] > 0
    json.dumps(result)
    # every compared number is printed beside its limit
    for name in compare.NUMBERS + ("non_finite_losses",
                                   "compilations_in_window"):
        assert " limit " in _row(lines, name) and "ok" in _row(lines, name)
    if tiny.CELLS[cell] > 1:
        assert "ok" in _row(lines, "devices_holding_state")
        assert "ok" in _row(lines, "all_reduce_in_program")


def test_gradient_divided_by_the_world_once_too_often_is_not_correct(
        session_mesh_restored, tmp_path, monkeypatch):
    """AdamW behind a clip trains to the same losses on a gradient scaled
    by 1/4 (ROADMAP D12 was exactly that): only the norm of what the
    optimizer is handed gives it away."""
    import optax

    real = hvd.DistributedOptimizer
    monkeypatch.setattr(
        hvd, "DistributedOptimizer",
        lambda inner, **kw: real(optax.chain(optax.scale(0.25), inner), **kw))
    lines = []
    result = _run(tmp_path, "tiny.train-4chip", 11, lines)
    assert result["correct"] is False
    assert "FAIL" in _row(lines, "grad_norm_gap")
    assert "ok" in _row(lines, "loss_gap")


def test_step_that_returns_its_state_unchanged_is_not_correct(
        session_mesh_restored, tmp_path, monkeypatch):
    """The timed path broken underneath: the update is dropped, every
    step is as fast as ever, and ``correct`` comes out false."""
    import optax

    monkeypatch.setattr(optax, "apply_updates", lambda params, updates: params)
    lines = []
    result = _run(tmp_path, "tiny.train-1chip", 12, lines)
    assert result["correct"] is False
    assert "FAIL" in _row(lines, "delta_norm_gap")


def test_compilation_in_the_window_is_not_correct(
        session_mesh_restored, tmp_path, monkeypatch):
    real = harness.window

    def compiling_window(session, run, seconds, **kw):
        jax.jit(lambda x: x * 3 + 1)(jax.numpy.ones((3, 5)))
        return real(session, run, seconds, **kw)

    monkeypatch.setattr(harness, "window", compiling_window)
    lines = []
    result = _run(tmp_path, "tiny.train-1chip", 13, lines)
    assert result["correct"] is False
    assert "FAIL" in _row(lines, "compilations_in_window")


@pytest.mark.parametrize("seed", [21, 22, 23])
def test_lower_precision_control_is_not_correct(seed):
    """The control: the reference with float8 matmul operands, the nearest
    precision below the bfloat16 the configurations state, put in the
    program's place. It has to fail a number of the cell (the gradient),
    not each."""
    builder = mf.load_module("builders", "gpt_decoder")
    fn32 = builder._reference_fn(
        builder._freeze(tiny.SIZES), builder._freeze(tiny.CONFIG["optimizer"]),
        2, "float32")
    fn8 = builder._reference_fn(
        builder._freeze(tiny.SIZES), builder._freeze(tiny.CONFIG["optimizer"]),
        2, "float8")
    from benchmarks.lib import traffic

    toks = traffic.token_pool(tiny.JOB, seed=seed, global_batch=2,
                              vocab=tiny.CONFIG["vocab_size"])[:3]

    def numbers(fn):
        return builder.as_floats(
            jax.device_get(fn(jax.numpy.uint32(seed), toks)))

    rows = compare.judge(numbers(fn8), numbers(fn32), tiny.LIMITS)
    verdict = {name: ok for name, _, _, ok, _ in rows}
    assert verdict["grad_norm_gap"] is False
    assert not all(verdict.values())


def test_worst_leaf_gap_measures_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    # the all-but-zero leaf is held to the median leaf's norm, not its own
    gap, leaf = compare.worst_leaf_gap({"a": 1.0, "b": 2.0, "c": 1e-3}, ref)
    assert leaf == "c" and gap == pytest.approx(1e-3, rel=1e-3)
    gap, leaf = compare.worst_leaf_gap({"a": 1.0, "b": 2.5, "c": 0.0}, ref)
    assert leaf == "b" and gap == pytest.approx(0.25)
    assert compare.worst_leaf_gap({"a": 1.0}, ref)[0] == float("inf")
    assert compare.worst_leaf_gap(
        {"a": float("nan"), "b": 2.0, "c": 0.0}, ref)[0] == float("inf")
