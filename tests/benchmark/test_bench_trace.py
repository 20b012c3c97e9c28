"""The reduction from a profiler trace to numbers: interval arithmetic on
hand-made traces, and the same functions on a small piece recorded on the
chip (data/trace_1chip_2steps.json.gz says where it came from)."""

import gzip
import json
import os
import types

import pytest

from benchmarks.lib import manifest as mf, peaks, trace as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _trace(ops, async_ops=(), modules=(), host=(), window=None):
    window = window or (min(s for _, s, _ in ops),
                        max(s + d for _, s, d in ops))
    return tr.Trace({0: list(ops)}, {0: list(async_ops)}, {0: list(modules)},
                    list(host), window)


def test_union_subtract_clip():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)])
    assert merged == [(0, 3), (5, 8), (10, 11)]
    assert tr.measure(merged) == 7
    assert tr.subtract(merged, [(2, 6), (10.5, 20)]) == [
        (0, 2), (6, 8), (10, 10.5)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.clip(merged, 2, 10.5) == [(2, 3), (5, 8), (10, 10.5)]


def test_busy_is_a_union_not_a_sum():
    """Two lanes that mirror each other count once, and busy can never
    exceed the window (summing durations, as bench.py summarize_profile
    did, reads 2x here)."""
    ops = [("a", 0.0, 4.0), ("a_mirror", 0.0, 4.0), ("b", 6.0, 2.0)]
    t = _trace(ops, window=(0.0, 10.0))
    assert tr.busy_seconds(t, 0) == 6.0
    assert tr.idle_share(t, 0) == pytest.approx(0.4)
    assert tr.mean_busy_seconds(t) == 6.0


def test_busy_is_clipped_to_the_window():
    t = _trace([("a", -1.0, 3.0), ("b", 9.0, 5.0)], window=(0.0, 10.0))
    assert tr.busy_seconds(t, 0) == 3.0


COMPUTE = "%fusion.9 = f32[8]{0} fusion(f32[8]{0} %all-reduce-done.3), kind=kLoop"
AR_START = ("%all-reduce-start.3 = (f32[8]{0:T(8)S(1)}, f32[8]{0}) "
            "all-reduce-start(f32[8]{0} %fusion.2), replica_groups={}")
AR_DONE = "%all-reduce-done.3 = f32[8]{0} all-reduce-done((f32[8]{0}) %all-reduce-start.3)"


def test_collective_total_and_exposed():
    """A collective in flight from 8 to 15 beside compute that ends at 10:
    7 in all, 5 of it with nothing else running. An op that only CONSUMES
    a collective's result is compute."""
    assert tr._op_kind(COMPUTE) == "fusion"
    assert tr.is_collective(AR_START) and tr.is_collective(AR_DONE)
    assert not tr.is_collective(COMPUTE)
    ops = [(COMPUTE, 0.0, 10.0), (AR_START, 8.0, 0.1), (AR_DONE, 12.0, 3.0),
           (COMPUTE, 15.0, 5.0)]
    t = _trace(ops, async_ops=[(AR_START, 8.0, 7.0)],
               modules=[("jit_step", 0.0, 20.0)])
    total, exposed = tr.collective_seconds(t, 0)
    assert total == [pytest.approx(7.0)]
    assert exposed == [pytest.approx(5.0)]
    assert tr.step_busy_seconds(t, 0) == [pytest.approx(18.0)]


def test_steps_leave_out_small_programs():
    mods = [("jit_step", 0.0, 10.0), ("jit_copy", 10.0, 0.1),
            ("jit_step", 11.0, 10.0)]
    t = _trace([("a", 0.0, 21.0)], modules=mods)
    assert tr.steps(t, 0) == [(0.0, 10.0), (11.0, 21.0)]


def test_idle_gaps_are_named_after_the_open_host_span():
    ops = [("a", 0.0, 1.0), ("b", 3.0, 1.0), ("c", 4.5, 0.5), ("d", 9.0, 1.0)]
    host = [("step.enqueue", 0.9, 0.3), ("step.wait", 1.2, 1.9),
            ("setup.init", 5.0, 1.0)]
    t = _trace(ops, host=host, window=(0.0, 10.0))
    assert tr.idle_gaps(t, 0, k=2) == [["setup.init", 4.0],
                                       ["step.wait", 2.0]]
    assert tr.idle_gaps(t, 0)[-1] == ["(no span)", 0.5]


def test_top_ops_sums_by_name():
    t = _trace([("x", 0.0, 1.0), ("y", 1.0, 3.0), ("x", 4.0, 2.5)])
    assert tr.top_ops(t, 0, k=1) == [["x", 3.5]]


# -- the recorded piece -------------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(os.path.join(DATA, "trace_1chip_2steps.json.gz")) as f:
        return tr.Trace.from_json(json.load(f))


def test_recorded_steps_busy_and_idle(recorded):
    assert len(tr.steps(recorded, 0)) == 2
    busy = tr.step_busy_seconds(recorded, 0)
    assert busy == pytest.approx([0.196508, 0.196571], abs=2e-6)
    summed = sum(d for _, _, d in recorded.ops[0])
    assert tr.busy_seconds(recorded, 0) <= summed
    assert tr.idle_share(recorded, 0) == pytest.approx(0.00186, abs=2e-5)
    # the one gap of note is between the two steps, while the loop waits
    assert tr.idle_gaps(recorded, 0, k=1)[0][0] == "step.wait"
    assert tr.collective_seconds(recorded, 0) == ([0, 0], [0, 0])


def _stub_run(recorded):
    notes = []
    run = types.SimpleNamespace(
        trace=recorded, chips=1, peak=peaks.for_device_kind("TPU v5 lite"),
        kernel_shapes={"flash_attention": dict(
            batch=8, seq=1024, heads=16, head_dim=64, causal=True,
            act_bytes=2)}, note=notes.append)
    return run, notes


def test_recorded_flash_kernels_are_found_and_under_their_roofline(recorded):
    run, notes = _stub_run(recorded)
    fwd = mf.load_module("layers", "flash_fwd_roofline")
    bwd = mf.load_module("layers", "flash_bwd_roofline")
    # 24 layers x 2 steps of each kernel, told apart by their outputs
    assert len(tr.kernel_seconds(recorded, 0, fwd.PATTERN)) == 48
    assert [len(tr.kernel_seconds(recorded, 0, p))
            for p in bwd.PATTERNS] == [48, 48]
    assert fwd.read(run) == pytest.approx(11.30, abs=0.05)
    assert bwd.read(run) == pytest.approx(11.67, abs=0.05)
    assert "bound by flops" in notes[0] and "bound by flops" in notes[1]


def test_readers_with_nothing_to_read_return_nothing(recorded):
    run, _ = _stub_run(recorded)
    for name in ("collective.total_ms", "collective.exposed_ms"):
        assert mf.load_module("layers", name).read(run) is None   # one chip
    run.trace = None
    for m in mf.load()["per_layer"]:
        if m["source"] == "device_trace":
            assert mf.load_module("layers", m["name"]).read(run) is None


@pytest.fixture(scope="module")
def recorded_4chip():
    with gzip.open(os.path.join(DATA, "trace_4chip_allreduce.json.gz")) as f:
        return tr.Trace.from_json(json.load(f))


def test_recorded_all_reduces_are_wholly_exposed(recorded_4chip):
    """The default gradient all-reduce of the four-chip cell: 13 fusion
    buckets, each a synchronous all-reduce on the core with nothing beside
    it, so what is in flight and what is exposed are the same 24.6 ms."""
    coll = tr.collective_events(recorded_4chip, 0)
    assert len(coll) == 13
    assert {tr._op_kind(name) for name, _, _ in coll} == {"all-reduce"}
    total, exposed = tr.collective_seconds(recorded_4chip, 0)
    assert total == pytest.approx([0.024628], abs=1e-6)
    assert exposed == pytest.approx(total, abs=1e-9)
    assert sum(d for _, _, d in coll) == pytest.approx(total[0], abs=1e-9)
    run, _ = _stub_run(recorded_4chip)
    run.chips = 4
    for name in ("collective.total_ms", "collective.exposed_ms"):
        assert mf.load_module("layers", name).read(run) == pytest.approx(
            24.628, abs=1e-3)
