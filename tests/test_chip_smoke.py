"""chip_smoke.py rehearsed on the CPU mesh (its ``--rehearse`` path).

The script is the driver's proof that the program starts on the chip; here
its control flow runs in-process at toy sizes — no child process, nothing
that could touch a chip — and its failure contract is pinned: no TPU or a
failing phase means a non-zero exit and no result line.
"""

import json
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import horovod_tpu as hvd

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import chip_smoke  # noqa: E402


@pytest.fixture()
def session_mesh_restored():
    """The script owns hvd.init/shutdown; hand the session its mesh back."""
    yield
    hvd.shutdown()
    hvd.init()


def _result_lines(out: str):
    return [ln for ln in out.splitlines() if ln.startswith('{"ok"')]


def test_rehearsal_runs_train_and_serve(session_mesh_restored, capsys):
    assert chip_smoke.main(["--rehearse"]) == 0
    out = capsys.readouterr().out
    assert "[train] step 6" in out
    assert "8/8 requests finished" in out
    assert "greedy parity vs full recompute" in out
    assert "[cache]" in out and "[native]" in out
    last = json.loads(out.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": len(jax.devices())}}


@pytest.mark.parametrize("overlap", [False, True],
                         ids=["default_allreduce", "HOROVOD_OVERLAP"])
def test_rehearsal_four_chips_matches_one_chip(session_mesh_restored,
                                               monkeypatch, capsys, overlap):
    """Data-parallel over four (virtual) devices vs the same global batch
    micro-batched on one: the leg that found the tape + optimizer double
    division, which a world of one can never show. The default gradient
    allreduce unless the caller exports HOROVOD_OVERLAP=1."""
    if overlap:
        monkeypatch.setenv("HOROVOD_OVERLAP", "1")
    else:
        monkeypatch.delenv("HOROVOD_OVERLAP", raising=False)
    assert chip_smoke.main(["--rehearse", "--chips", "4"]) == 0
    out = capsys.readouterr().out
    assert "world=4" in out and "micro_batches=8" in out
    assert f"overlap={overlap}" in out
    assert "outputs on 4 devices" in out
    assert "[serve]" not in out    # that option runs nothing else
    assert json.loads(out.strip().splitlines()[-1])["ok"] is True


def test_four_chip_check_rejects_a_misscaled_gradient(monkeypatch):
    """A gradient divided by the world size twice keeps the early losses
    within tolerance of the reference; the decrease gives it away."""
    devices = jax.devices()[:4]
    mesh = jax.sharding.Mesh(devices, ("d",))
    spread = jax.device_put(jnp.zeros((4,)), NamedSharding(mesh, P("d")))
    ref = [10.0 - 0.001 * i for i in range(7)]
    quarter = [10.0 - 0.00025 * i for i in range(7)]
    runs = iter([(quarter, "all-reduce", spread), (ref, "", None)])
    monkeypatch.setattr(chip_smoke, "train_phase",
                        lambda *a, **k: next(runs))
    with pytest.raises(RuntimeError, match="mis-scaled"):
        chip_smoke.four_chip_phase(devices, chip_smoke.TINY)


def test_no_tpu_is_a_failure_without_a_result(session_mesh_restored,
                                              capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert not _result_lines(captured.out)
    assert "no TPU" in captured.err


@pytest.mark.parametrize("failing", ["train_phase", "serve_phase"])
def test_failing_phase_is_a_failure_without_a_result(
        session_mesh_restored, monkeypatch, capsys, failing):
    def boom(*a, **k):
        raise RuntimeError(f"{failing} broke")

    for phase in ("train_phase", "serve_phase"):
        monkeypatch.setattr(chip_smoke, phase,
                            boom if phase == failing else
                            (lambda *a, **k: None))
    assert chip_smoke.main(["--rehearse"]) != 0
    captured = capsys.readouterr()
    assert not _result_lines(captured.out)
    assert f"{failing} broke" in captured.err
