"""Systematic collective-op matrix: op x dtype x path.

Models the reference's exhaustive parallel tier
(test/parallel/test_tensorflow.py — every dtype x dim x error case over a
real multi-process world) across this framework's three data planes:

* compiled — shard_map over the 8-device CPU mesh (the XLA/ICI plane);
* eager    — host-path ops in a single process (identity semantics);
* native   — a real 2-process world through the C++ controller + TCP
  data plane (tests/matrix_worker.py), including the cross-rank
  mismatch ERROR cases (shape/dtype/op/reduce-op/root), asserting the
  controller's error text reaches every rank.

64-bit dtypes run under ``jax.experimental.enable_x64`` (JAX truncates
them to 32-bit otherwise).
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from test_native_core import _run_world, REPO  # noqa: F401

import os

MATRIX_WORKER = os.path.join(REPO, "tests", "matrix_worker.py")

N = 8

DTYPES = [np.uint8, np.int8, np.int32, np.int64, np.float16,
          jnp.bfloat16, np.float32, np.float64]


def _is64(dtype):
    return np.dtype(dtype).itemsize == 8


def _ctx(dtype):
    if not _is64(dtype):
        return contextlib.nullcontext()
    return jax.enable_x64(True)


def spmd(f, in_specs, out_specs):
    return hvd.shard_map(f, mesh=hvd.mesh(), in_specs=in_specs,
                         out_specs=out_specs)


def as_f64(a):
    return np.asarray(a, dtype=np.float64)


class TestCompiledMatrix:
    """Every op in every wire dtype on the compiled plane. Values stay
    tiny so the sums are exact in every dtype (incl. uint8/fp16/bf16)."""

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_allreduce(self, dtype):
        with _ctx(dtype):
            base = np.arange(8) % 3
            x = np.stack([base + r for r in range(N)]).astype(
                np.dtype(dtype) if not _is64(dtype) else dtype)
            out = spmd(lambda v: hvd.allreduce(v[0], op=hvd.Sum),
                       in_specs=P(hvd.HVD_AXES), out_specs=P())(
                jnp.asarray(x, dtype=dtype))
            exp = base * N + sum(range(N))
            assert np.array_equal(as_f64(out), as_f64(exp))

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_grouped_allreduce(self, dtype):
        with _ctx(dtype):
            a = np.ones((N, 3)); b = np.full((N, 2), 2)

            def f(x, y):
                return tuple(hvd.grouped_allreduce([x[0], y[0]],
                                                   op=hvd.Sum))

            outs = spmd(f, in_specs=(P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
                        out_specs=(P(), P()))(
                jnp.asarray(a, dtype=dtype), jnp.asarray(b, dtype=dtype))
            assert np.array_equal(as_f64(outs[0]), np.full(3, N))
            assert np.array_equal(as_f64(outs[1]), np.full(2, 2 * N))

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_allgather(self, dtype):
        with _ctx(dtype):
            x = np.stack([np.full((2, 2), r) for r in range(N)])
            # all_gather output carries a varying mark (each rank holds
            # its own identical copy): stack per-rank copies.
            out = spmd(lambda v: hvd.allgather(v[0])[None],
                       in_specs=P(hvd.HVD_AXES),
                       out_specs=P(hvd.HVD_AXES))(
                jnp.asarray(x, dtype=dtype))
            assert out.shape == (N, 2 * N, 2)
            for r in range(N):
                for s in range(N):
                    assert (as_f64(out[r, 2 * s:2 * s + 2]) == s).all()

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_broadcast(self, dtype):
        with _ctx(dtype):
            x = np.stack([np.full(4, r) for r in range(N)])
            out = spmd(lambda v: hvd.broadcast(v[0], root_rank=3),
                       in_specs=P(hvd.HVD_AXES), out_specs=P())(
                jnp.asarray(x, dtype=dtype))
            assert (as_f64(out) == 3).all()

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_alltoall(self, dtype):
        with _ctx(dtype):
            # rank r sends value r in row-block k to rank k.
            x = np.stack([np.arange(N).repeat(1)[:, None] * 0 + r
                          for r in range(N)])  # [N, N, 1] value r

            def f(v):
                out, sp = hvd.alltoall(v[0])
                return out, sp

            out, sp = spmd(f, in_specs=P(hvd.HVD_AXES),
                           out_specs=(P(hvd.HVD_AXES), P(hvd.HVD_AXES)))(
                jnp.asarray(x, dtype=dtype))
            out = as_f64(out).reshape(N, N)
            for r in range(N):
                assert (out[r] == np.arange(N)).all()
            assert (np.asarray(sp) == 1).all()


class TestEagerMatrix:
    """Host-path ops, process world of 1: identity semantics in every
    dtype (reference: single-process eager behavior of each binding)."""

    @pytest.mark.parametrize("dtype", DTYPES)
    def test_all_ops(self, dtype):
        with _ctx(dtype):
            x = jnp.asarray(np.arange(6).reshape(3, 2), dtype=dtype)
            assert np.array_equal(
                as_f64(hvd.allreduce(x, op=hvd.Sum)), as_f64(x))
            assert np.array_equal(as_f64(hvd.allgather(x)), as_f64(x))
            assert np.array_equal(as_f64(hvd.broadcast(x, 0)), as_f64(x))
            out, sp = hvd.alltoall(x)
            assert np.array_equal(as_f64(out), as_f64(x))
            assert np.asarray(sp).tolist() == [3]
            outs = hvd.grouped_allreduce([x, x + x], op=hvd.Sum)
            assert np.array_equal(as_f64(outs[1]), 2 * as_f64(x))


class TestNativeMatrix:
    """Real 2- and 3-process worlds through the C++ controller + TCP
    plane: the full dtype matrix per op plus the cross-rank mismatch
    ERROR cases (shape, dtype, collective-op, reduce-op, root) —
    asserting the controller's ERROR text reaches every rank."""

    @pytest.mark.parametrize("n", [2, 3])
    def test_world(self, n):
        _run_world(n, timeout=180, worker=MATRIX_WORKER)

    def test_world_2_hierarchical(self):
        _run_world(2, {
            "HOROVOD_HIERARCHICAL_ALLREDUCE": "1",
            "HOROVOD_HIERARCHICAL_ALLGATHER": "1",
        }, timeout=180, worker=MATRIX_WORKER, local_size=1)
