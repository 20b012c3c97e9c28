"""Native C++ core tests: single-process pipeline + multi-process localhost.

Mirrors the reference's test tiers (SURVEY §4): single-process logic tests
against the trivial world, and parallel tests running N real processes over
localhost TCP — the analogue of `mpirun -np 2 pytest test_tensorflow.py`.
"""

import os
import socket
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from horovod_tpu import cc

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "native_worker.py")
EAGER_WORKER = os.path.join(REPO, "tests", "eager_worker.py")


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module")
def ctx():
    """Single-process core context (world of one, full pipeline)."""
    # Ensure a clean world regardless of inherited env.
    for k in ("HOROVOD_RANK", "HOROVOD_SIZE"):
        os.environ.pop(k, None)
    c = cc.CoreContext()
    yield c
    c.close()


class TestSingleProcess:
    def test_world(self, ctx):
        assert ctx.rank() == 0
        assert ctx.size() == 1
        assert ctx.fusion_threshold() == 64 * 1024 * 1024

    def test_allreduce_identity(self, ctx):
        a = np.arange(8, dtype=np.float32)
        out = ctx.allreduce_async(a.copy(), "sp_ar").wait()
        assert np.allclose(out, a)

    def test_allreduce_postscale(self, ctx):
        out = ctx.allreduce_async(np.ones(4, np.float32), "sp_ps",
                                  postscale=0.25).wait()
        assert np.allclose(out, 0.25)

    def test_allgather(self, ctx):
        out = ctx.allgather_async(np.ones((3, 2), np.float32),
                                  "sp_ag").wait()
        assert out.shape == (3, 2)

    def test_broadcast(self, ctx):
        out = ctx.broadcast_async(np.arange(4, dtype=np.int64), "sp_bc",
                                  root=0).wait()
        assert (out == np.arange(4)).all()

    def test_alltoall(self, ctx):
        h = ctx.alltoall_async(np.arange(6, dtype=np.float64).reshape(6, 1),
                               "sp_a2a")
        out = h.wait()
        assert np.allclose(out.ravel(), np.arange(6))
        assert h.recv_splits() == [6]

    def test_barrier(self, ctx):
        ctx.barrier()

    def test_duplicate_name_rejected(self, ctx):
        # Reference: DUPLICATE_NAME_ERROR (common.h:163) surfaces when a
        # name is re-submitted while still in flight.
        h1 = ctx.allreduce_async(np.ones(1024, np.float32), "sp_dup")
        try:
            h2 = ctx.allreduce_async(np.ones(1024, np.float32), "sp_dup")
        except cc.NativeError as e:
            assert "same name" in str(e)
        else:
            h2.wait()  # raced past the first completion — legal
        h1.wait()

    def test_int_dtypes(self, ctx):
        for dt in (np.uint8, np.int8, np.int32, np.int64):
            out = ctx.allreduce_async(np.ones(4, dt), f"sp_{dt.__name__}"
                                      ).wait()
            assert (out == 1).all()

    def test_cache_steady_state(self, ctx):
        for _ in range(20):
            out = ctx.allreduce_async(np.ones(4, np.float32),
                                      "sp_steady").wait()
            assert np.allclose(out, 1.0)

    def test_timeline(self, ctx, tmp_path):
        path = str(tmp_path / "tl.json")
        ctx.start_timeline(path)
        for i in range(5):
            ctx.allreduce_async(np.ones(4, np.float32), f"sp_tl{i}").wait()
        ctx.stop_timeline()
        import json
        text = open(path).read().rstrip().rstrip(",")
        events = json.loads(text + "]") if not text.endswith("]") else \
            json.loads(text)
        names = {e["name"] for e in events}
        assert any(n.startswith("NEGOTIATE_") for n in names)
        assert "ALLREDUCE" in names or "TCP_ALLREDUCE" in names


def _run_world(n, extra_env=None, timeout=120, worker=WORKER,
               local_size=None):
    port = _free_port()
    procs = []
    # Each worker writes to a file of its own, never to a pipe: the pipes
    # were read one rank after the other, so a rank that printed more than
    # a pipe holds (64 KiB: jaxlib logs ~6 KB for every executable it
    # loads from a warm compile cache) blocked in write() while the rank
    # being read waited for it in a collective, until the timeout.
    logs = [tempfile.TemporaryFile(mode="w+") for _ in range(n)]
    for r in range(n):
        env = dict(os.environ)
        env.pop("XLA_FLAGS", None)  # workers don't need the 8-device mesh
        env.update({
            "PYTHONPATH": REPO,
            "HOROVOD_RANK": str(r),
            "HOROVOD_SIZE": str(n),
            "HOROVOD_CONTROLLER_ADDR": "127.0.0.1",
            "HOROVOD_CONTROLLER_PORT": str(port),
        })
        if local_size is not None:
            # Emulated multi-host topology: host-major rank packing
            # (reference hosts.py:100-150).
            env.update({
                "HOROVOD_LOCAL_RANK": str(r % local_size),
                "HOROVOD_LOCAL_SIZE": str(local_size),
                "HOROVOD_CROSS_RANK": str(r // local_size),
                "HOROVOD_CROSS_SIZE": str(n // local_size),
            })
        env.update(extra_env or {})
        procs.append(subprocess.Popen(
            [sys.executable, worker], env=env,
            stdout=logs[r], stderr=subprocess.STDOUT))
    ok = True
    for p in procs:
        try:
            p.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            p.kill()
            p.wait()
            ok = False
        ok = ok and p.returncode == 0
    outs = []
    for log in logs:
        log.seek(0)
        outs.append(log.read())
        log.close()
    assert ok, "worker failures:\n" + "\n----\n".join(outs)
    return outs


class TestMultiProcess:
    @pytest.mark.parametrize("n", [2, 4])
    def test_world(self, n):
        _run_world(n)

    def test_world_3_small_fusion(self):
        # Odd world + tiny fusion threshold forces multi-buffer fusion
        # rounds and non-divisible ring chunks.
        _run_world(3, {"HOROVOD_FUSION_THRESHOLD": str(256)})

    def test_hierarchical_2x2(self):
        # Full worker assertion suite with hierarchical allreduce+allgather
        # enabled on an emulated 2-host x 2-chip topology: numerics must be
        # identical to the flat ring paths (reference:
        # NCCLHierarchicalAllreduce nccl_operations.cc:190-380,
        # MPIHierarchicalAllgather mpi_operations.cc:180-280).
        _run_world(4, {
            "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
            "HOROVOD_HIERARCHICAL_ALLGATHER": "1",
        }, local_size=2)

    def test_hierarchical_3x2_small_fusion(self):
        # Non-power-of-2 host count + tiny fusion buffers: uneven cross-ring
        # chunks through the hierarchical legs.
        _run_world(6, {
            "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
            "HOROVOD_HIERARCHICAL_ALLGATHER": "1",
            "HOROVOD_FUSION_THRESHOLD": str(256),
        }, local_size=2)

    def test_autotune_smoke(self):
        # Small sample budget so the tuner converges inside the worker's
        # autotune traffic loop; the worker then asserts the tuned params
        # propagated identically to every rank.
        _run_world(2, {
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "2",
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "4",
        })

    def test_autotune_hierarchical_topology(self):
        # On a 2x2 topology the hierarchical flags join the search space;
        # the run must stay correct whichever way the tuner flips them
        # mid-stream (all the worker's numeric assertions still hold).
        _run_world(4, {
            "HOROVOD_AUTOTUNE": "1",
            "HOROVOD_AUTOTUNE_WARMUP_SAMPLES": "1",
            "HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE": "2",
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES": "6",
        }, local_size=2, timeout=180)


class TestEagerPythonAPI:
    """The full hvd.* Python surface across worker processes — the
    reference's `mpirun -np N pytest test_tensorflow.py` tier."""

    @pytest.mark.parametrize("n", [2, 4])
    def test_world(self, n):
        _run_world(n, timeout=240, worker=EAGER_WORKER)
