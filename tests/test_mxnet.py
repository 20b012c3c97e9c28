"""MXNet binding tests (reference analogue: test/parallel/test_mxnet.py).

World-1 semantics run in-process against the fake-mxnet shim
(tests/fake_mxnet.py — MXNet is EOL and uninstallable here, same strategy
as the Ray tests vs fake_ray.py); multi-process numerics run 2 real worker
processes over the native TCP data plane (tests/mxnet_worker.py).
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import fake_mxnet  # noqa: E402
from test_native_core import _run_world  # noqa: E402

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "mxnet_worker.py")


def _mxnet_modules():
    return [n for n in sys.modules
            if n == "mxnet" or n.startswith("mxnet.")
            or n.startswith("horovod_tpu.mxnet")]


@pytest.fixture()
def mx():
    """Install the shim for one test and restore sys.modules exactly
    afterwards — a leaked fake 'mxnet' would break the import-gate tests
    elsewhere in the suite (e.g. test_tensorflow's TestMXNetGate)."""
    saved = {n: sys.modules[n] for n in _mxnet_modules()}
    for n in saved:
        del sys.modules[n]
    mod = fake_mxnet.install()
    yield mod
    for n in _mxnet_modules():
        del sys.modules[n]
    sys.modules.update(saved)


class TestWorldOne:
    def test_allreduce_identity(self, mx):
        import horovod_tpu.mxnet as hvd

        hvd.init()
        t = mx.nd.array(np.arange(6, dtype=np.float32))
        out = hvd.allreduce(t)
        assert np.allclose(out.asnumpy(), np.arange(6))
        out = hvd.allreduce(t, average=False, prescale_factor=2.0)
        assert np.allclose(out.asnumpy(), 2 * np.arange(6))

    def test_allgather_broadcast_alltoall_identity(self, mx):
        import horovod_tpu.mxnet as hvd

        hvd.init()
        t = mx.nd.array(np.ones((2, 3), np.float32))
        assert hvd.allgather(t).shape == (2, 3)
        assert np.allclose(hvd.broadcast(t, 0).asnumpy(), 1.0)
        assert np.allclose(hvd.alltoall(t).asnumpy(), 1.0)
        assert hvd.broadcast_object({"a": 1}) == {"a": 1}
        assert hvd.allgather_object(5) == [5]

    def test_distributed_optimizer_world1(self, mx):
        import horovod_tpu.mxnet as hvd

        hvd.init()
        w = mx.nd.array(np.ones(3, np.float32))
        g = mx.nd.array(np.full(3, 2.0, np.float32))
        opt = hvd.DistributedOptimizer(mx.optimizer.SGD(learning_rate=0.5))
        opt.update(0, w, g, None)
        assert np.allclose(w.asnumpy(), 1.0 - 0.5 * 2.0)
        # delegation surface: setter routes to the wrapped optimizer, and
        # __getattr__ reads back through it
        opt.set_learning_rate(0.1)
        assert opt.lr == 0.1

    def test_predivide_cancels_at_world1(self, mx):
        """gradient_predivide_factor folds f into rescale_grad and 1/f into
        the wire prescale; at world 1 both must still apply so updates match
        the unwrapped optimizer exactly (regression: the early-return skip
        of the prescale left updates scaled by f)."""
        import horovod_tpu.mxnet as hvd

        hvd.init()
        w = mx.nd.array(np.ones(3, np.float32))
        g = mx.nd.array(np.full(3, 2.0, np.float32))
        opt = hvd.DistributedOptimizer(mx.optimizer.SGD(learning_rate=0.5),
                                       gradient_predivide_factor=4.0)
        opt.update(0, w, g, None)
        assert np.allclose(w.asnumpy(), 1.0 - 0.5 * 2.0)

        p = mx.gluon.parameter.Parameter("w")
        p.initialize(np.ones(2, np.float32))
        tr = hvd.DistributedTrainer([p], "sgd", {"learning_rate": 0.5},
                                    gradient_predivide_factor=4.0)
        p.list_grad()[0][:] = np.full(2, 2.0, np.float32)
        tr.step(batch_size=1)
        assert np.allclose(p.data().asnumpy(), 1.0 - 0.5 * 2.0)

    def test_trainer_unwraps_distributed_optimizer(self, mx):
        import horovod_tpu.mxnet as hvd

        hvd.init()
        inner = mx.optimizer.SGD(learning_rate=0.5)
        wrapped = hvd.DistributedOptimizer(inner)
        with pytest.warns(UserWarning, match="unwrapped"):
            trainer = hvd.DistributedTrainer([], wrapped)
        assert trainer._optimizer is inner

    def test_broadcast_parameters_world1_noop(self, mx):
        import horovod_tpu.mxnet as hvd

        hvd.init()
        p = mx.gluon.parameter.Parameter("w")  # never initialized:
        hvd.broadcast_parameters({"w": p})     # world-1 returns before touch

    def test_import_error_without_mxnet(self, monkeypatch):
        for name in [n for n in sys.modules
                     if n.startswith("horovod_tpu.mxnet") or n == "mxnet"
                     or n.startswith("mxnet.")]:
            monkeypatch.delitem(sys.modules, name, raising=False)
        monkeypatch.setitem(sys.modules, "mxnet", None)
        with pytest.raises(ImportError, match="fake_mxnet"):
            import horovod_tpu.mxnet  # noqa: F401


class TestMultiProcess:
    def test_world_2(self):
        _run_world(2, timeout=300, worker=WORKER)
