"""PyTorch binding tests.

Reference analogue: test/parallel/test_torch.py (op matrix, handle API,
optimizer wrapping, state broadcast) run as single-process semantics checks
plus real multi-process workers over localhost TCP (SURVEY §4).
"""

import os

import numpy as np
import pytest
import torch

import horovod_tpu.torch as hvd_torch
from test_native_core import _run_world

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "torch_worker.py")


class TestOpsSingleProcess:
    """World-of-one semantics (every op must be exact identity modulo
    scaling, reference test_torch.py runs the same matrix at np=1)."""

    def test_allreduce_identity(self):
        t = torch.arange(6, dtype=torch.float32)
        assert torch.allclose(hvd_torch.allreduce(t), t)
        assert torch.allclose(hvd_torch.allreduce(t, op=hvd_torch.Sum), t)

    def test_allreduce_scaling(self):
        t = torch.ones(4)
        out = hvd_torch.allreduce(t, op=hvd_torch.Sum, prescale_factor=3.0)
        assert torch.allclose(out, torch.full((4,), 3.0))

    def test_allreduce_product_scaling(self):
        # Pre/postscale must apply for op=Product at np=1 too (the native
        # core applies them around the reduction for every op).
        t = torch.full((4,), 2.0)
        out = hvd_torch.allreduce(t, op=hvd_torch.Product,
                                  prescale_factor=2.0)
        assert torch.allclose(out, torch.full((4,), 4.0))

    def test_allreduce_inplace(self):
        t = torch.ones(4)
        out = hvd_torch.allreduce_(t, op=hvd_torch.Sum, postscale_factor=2.0)
        assert out is t
        assert torch.allclose(t, torch.full((4,), 2.0))

    def test_allreduce_grad(self):
        x = torch.ones(3, requires_grad=True)
        y = hvd_torch.allreduce(x).sum()
        y.backward()
        assert torch.allclose(x.grad, torch.ones(3))

    def test_allreduce_average_op_conflict(self):
        with pytest.raises(ValueError):
            hvd_torch.allreduce(torch.ones(2), average=True, op=hvd_torch.Sum)

    def test_allreduce_average_flag(self):
        out = hvd_torch.allreduce(torch.ones(2), average=False)
        assert torch.allclose(out, torch.ones(2))

    def test_allgather_identity(self):
        t = torch.randn(3, 2)
        assert torch.allclose(hvd_torch.allgather(t), t)

    def test_broadcast_identity(self):
        t = torch.randn(4)
        assert torch.allclose(hvd_torch.broadcast(t, root_rank=0), t)

    def test_broadcast_inplace(self):
        t = torch.randn(4)
        out = hvd_torch.broadcast_(t, root_rank=0)
        assert out is t

    def test_alltoall_identity(self):
        t = torch.arange(4, dtype=torch.float32)
        out, splits = hvd_torch.alltoall(t)
        assert torch.allclose(out, t)
        assert splits.tolist() == [4]

    def test_handle_api(self):
        h = hvd_torch.allreduce_async(torch.ones(5), name="sp.h1")
        assert hvd_torch.poll(h)
        out = hvd_torch.synchronize(h)
        assert torch.allclose(out, torch.ones(5))

    def test_duplicate_name_rejected(self):
        h = hvd_torch.allreduce_async(torch.ones(2), name="sp.dup")
        with pytest.raises(Exception, match="sp.dup"):
            hvd_torch.allreduce_async(torch.ones(2), name="sp.dup")
        hvd_torch.synchronize(h)

    def test_bf16_roundtrip(self):
        t = torch.ones(4, dtype=torch.bfloat16) * 1.5
        out = hvd_torch.allreduce(t, op=hvd_torch.Sum)
        assert out.dtype == torch.bfloat16
        assert torch.allclose(out.float(), torch.full((4,), 1.5))

    def test_join(self):
        assert hvd_torch.join() == 0

    def test_world_queries(self):
        assert hvd_torch.size() >= 1
        assert hvd_torch.rank() >= 0
        assert hvd_torch.local_size() >= 1
        assert hvd_torch.is_homogeneous()


class TestCompression:
    def test_fp16_roundtrip(self):
        t = torch.randn(8)
        c, ctx = hvd_torch.Compression.fp16.compress(t)
        assert c.dtype == torch.float16
        d = hvd_torch.Compression.fp16.decompress(c, ctx)
        assert d.dtype == torch.float32
        assert torch.allclose(d, t, atol=1e-2)

    def test_bf16(self):
        t = torch.randn(8)
        c, ctx = hvd_torch.Compression.bf16.compress(t)
        assert c.dtype == torch.bfloat16

    def test_none(self):
        t = torch.randn(8)
        c, ctx = hvd_torch.Compression.none.compress(t)
        assert c is t
        assert hvd_torch.Compression.none.decompress(c, ctx) is t

    def test_int_passthrough(self):
        t = torch.ones(4, dtype=torch.int64)
        c, ctx = hvd_torch.Compression.fp16.compress(t)
        assert c.dtype == torch.int64


class TestDistributedOptimizer:
    def test_elastic_construction_before_init(self, monkeypatch):
        """Elastic scripts build the optimizer BEFORE the first rendezvous
        initializes the world (examples/pytorch_elastic.py); the hook gate
        must tolerate that and register hooks anyway, since an elastic
        world of 1 can grow (reference optimizer.py:77: `size() > 1 or
        HOROVOD_ELASTIC == '1'`)."""
        from horovod_tpu.common.exceptions import NotInitializedError
        from horovod_tpu.torch import optimizer as opt_mod

        def _raise():
            raise NotInitializedError()

        monkeypatch.setattr(opt_mod.mpi_ops, "_world", _raise)

        def build():
            model = torch.nn.Linear(4, 2)
            return hvd_torch.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=model.named_parameters())

        # Static job, no init: constructing is a caller error, as before.
        monkeypatch.delenv("HOROVOD_ELASTIC", raising=False)
        with pytest.raises(NotInitializedError):
            build()
        # Elastic job: construction succeeds and hooks are registered.
        monkeypatch.setenv("HOROVOD_ELASTIC", "1")
        opt = build()
        assert len(opt._requires_update) == 2  # weight + bias hooked

    def test_wraps_class(self):
        model = torch.nn.Linear(4, 2)
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.Adam(model.parameters(), lr=1e-3),
            named_parameters=model.named_parameters())
        assert isinstance(opt, torch.optim.Adam)

    def test_training_decreases_loss(self):
        torch.manual_seed(0)
        model = torch.nn.Sequential(torch.nn.Linear(8, 16), torch.nn.Tanh(),
                                    torch.nn.Linear(16, 1))
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters())
        x = torch.randn(32, 8)
        y = x.sum(dim=1, keepdim=True)
        first = None
        for _ in range(20):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(x), y)
            loss.backward()
            opt.step()
            first = first if first is not None else loss.item()
        assert loss.item() < first * 0.5

    def test_duplicate_named_parameters_rejected(self):
        model = torch.nn.Linear(2, 2)
        p = list(model.named_parameters())
        with pytest.raises(ValueError):
            hvd_torch.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=p + p)

    def test_missing_named_parameters_rejected(self):
        model = torch.nn.Linear(2, 2)
        partial = list(model.named_parameters())[:1]
        with pytest.raises(ValueError, match="missing"):
            hvd_torch.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                named_parameters=partial)

    def test_predivide_requires_average(self):
        model = torch.nn.Linear(2, 2)
        with pytest.raises(ValueError):
            hvd_torch.DistributedOptimizer(
                torch.optim.SGD(model.parameters(), lr=0.1),
                op=hvd_torch.Sum, gradient_predivide_factor=2.0)

    def test_adasum_optimizer_single(self):
        torch.manual_seed(0)
        model = torch.nn.Linear(3, 1)
        opt = hvd_torch.DistributedOptimizer(
            torch.optim.SGD(model.parameters(), lr=0.1),
            named_parameters=model.named_parameters(), op=hvd_torch.Adasum)
        x = torch.randn(4, 3)
        opt.zero_grad()
        model(x).sum().backward()
        opt.step()  # world of one: plain step


class TestFunctions:
    def test_broadcast_parameters_world1(self):
        model = torch.nn.Linear(3, 3)
        before = {k: v.clone() for k, v in model.state_dict().items()}
        hvd_torch.broadcast_parameters(model.state_dict(), root_rank=0)
        for k, v in model.state_dict().items():
            assert torch.allclose(v, before[k])

    def test_broadcast_object_world1(self):
        obj = {"a": 1}
        assert hvd_torch.broadcast_object(obj) == obj

    def test_allgather_object_world1(self):
        assert hvd_torch.allgather_object(42) == [42]

    def test_broadcast_optimizer_state_world1(self):
        model = torch.nn.Linear(3, 3)
        opt = torch.optim.SGD(model.parameters(), lr=0.1, momentum=0.9)
        hvd_torch.broadcast_optimizer_state(opt, root_rank=0)
        # dummy-step trick must have populated state
        assert len(opt.state_dict()["state"]) > 0


class TestSyncBatchNorm:
    def test_matches_batchnorm_world1(self):
        torch.manual_seed(0)
        sbn = hvd_torch.SyncBatchNorm(4)
        bn = torch.nn.BatchNorm2d(4)
        x = torch.randn(8, 4, 3, 3)
        # world of one falls back to plain batch_norm
        assert torch.allclose(sbn(x), bn(x), atol=1e-5)
        assert torch.allclose(sbn.running_mean, bn.running_mean, atol=1e-5)

    def test_eval_mode(self):
        sbn = hvd_torch.SyncBatchNorm(4)
        sbn.eval()
        x = torch.randn(2, 4)
        out = sbn(x)
        assert out.shape == x.shape

    def test_momentum_none_cumulative(self):
        # momentum=None = cumulative moving average; must not crash and
        # must track num_batches
        sbn = hvd_torch.SyncBatchNorm(4, momentum=None)
        bn = torch.nn.BatchNorm2d(4, momentum=None)
        x = torch.randn(8, 4, 3, 3)
        assert torch.allclose(sbn(x), bn(x), atol=1e-5)
        assert sbn.num_batches_tracked.item() == 1
        assert torch.allclose(sbn.running_mean, bn.running_mean, atol=1e-5)
        sbn.eval()
        sbn(x)  # eval with momentum=None must not crash either

    def test_rejects_1d(self):
        sbn = hvd_torch.SyncBatchNorm(4)
        with pytest.raises(ValueError):
            sbn(torch.randn(4))


class TestTorchElastic:
    def test_state_save_restore(self):
        model = torch.nn.Linear(2, 2)
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        state = hvd_torch.elastic.TorchState(model=model, optimizer=opt,
                                             epoch=5)
        state.save()
        with torch.no_grad():
            for p in model.parameters():
                p.fill_(77.0)
        state.epoch = 9
        state.restore()
        for p in model.parameters():
            assert not torch.allclose(p, torch.full_like(p, 77.0))
        assert state.epoch == 5

    def test_sampler_shards_and_records(self):
        sampler = hvd_torch.elastic.ElasticSampler(list(range(10)),
                                                   shuffle=False)
        idx = list(iter(sampler))
        assert idx == list(range(10))
        sampler.record_batch(0, 4)
        sampler.reset()
        assert len(set(iter(sampler)) & set(range(4))) == 0
        assert len(sampler) == 6

    def test_sampler_state_dict(self):
        sampler = hvd_torch.elastic.ElasticSampler(list(range(8)),
                                                   shuffle=False)
        sampler.record_batch(0, 2)
        sd = sampler.state_dict()
        sampler.reset()
        s2 = hvd_torch.elastic.ElasticSampler(list(range(8)), shuffle=False)
        s2.load_state_dict(sd)
        assert set(iter(s2)) == set(iter(sampler))


class TestMultiProcess:
    @pytest.mark.parametrize("n", [2, 4])
    def test_world(self, n):
        _run_world(n, timeout=180, worker=WORKER)
