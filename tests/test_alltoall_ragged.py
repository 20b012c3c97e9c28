"""Compiled uneven alltoall (static-capacity protocol) on the 8-CPU mesh.

Models the reference's uneven-split alltoall coverage
(test/parallel/test_tensorflow.py test_horovod_alltoall_uneven; runtime
recv-splits negotiation in operations.cc:1031-1092): compiled-ragged vs a
host-side numpy simulation vs the eager world-1 path, plus overflow
clamping and the gradient of the padded exchange.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd

N = 8


def spmd(f, in_specs, out_specs):
    return hvd.shard_map(f, mesh=hvd.mesh(), in_specs=in_specs,
                         out_specs=out_specs)


def ragged_sim(x_all, splits_all, cap):
    """Numpy reference: returns (out [N, N*cap, ...], recv [N, N])."""
    n = x_all.shape[0]
    rest = x_all.shape[2:]
    out = np.zeros((n, n * cap) + rest, x_all.dtype)
    recv = np.zeros((n, n), np.int32)
    for d in range(n):  # destination rank
        rows = []
        for r in range(n):  # source rank
            offs = np.cumsum(splits_all[r]) - splits_all[r]
            k = min(int(splits_all[r, d]), cap)
            rows.append(x_all[r, offs[d]:offs[d] + k])
            recv[d, r] = k
        block = np.concatenate(rows, axis=0) if rows else \
            np.zeros((0,) + rest, x_all.dtype)
        out[d, :block.shape[0]] = block
    return out, recv


def run_compiled(x_all, splits_all, cap):
    def f(x, sp):
        out, rsp = hvd.alltoall_ragged(x[0], sp[0], capacity=cap)
        return out, rsp

    out, rsp = spmd(f, in_specs=(P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
                    out_specs=(P(hvd.HVD_AXES), P(hvd.HVD_AXES)))(
        jnp.asarray(x_all), jnp.asarray(splits_all, jnp.int32))
    rest = x_all.shape[2:]
    return (np.asarray(out).reshape((N, N * cap) + rest),
            np.asarray(rsp).reshape(N, N))


@pytest.mark.parametrize("shape", [(), (5,)])
def test_ragged_matches_simulation(shape):
    rng = np.random.RandomState(0)
    # Random split matrix with rows summing to <= T.
    splits_all = rng.randint(0, 4, size=(N, N)).astype(np.int32)
    T = int(splits_all.sum(axis=1).max())
    x_all = rng.randn(N, T, *shape).astype(np.float32)
    cap = 4  # >= max split: lossless
    out, rsp = run_compiled(x_all, splits_all, cap)
    exp_out, exp_recv = ragged_sim(x_all, splits_all, cap)
    np.testing.assert_array_equal(rsp, exp_recv)
    np.testing.assert_array_equal(out, exp_out)


def test_ragged_overflow_clamped():
    rng = np.random.RandomState(1)
    splits_all = rng.randint(0, 6, size=(N, N)).astype(np.int32)
    T = int(splits_all.sum(axis=1).max())
    x_all = rng.randn(N, T).astype(np.float32)
    cap = 3  # below max split: rows beyond cap dropped, counts clamped
    out, rsp = run_compiled(x_all, splits_all, cap)
    exp_out, exp_recv = ragged_sim(x_all, splits_all, cap)
    assert rsp.max() == cap
    np.testing.assert_array_equal(rsp, exp_recv)
    np.testing.assert_array_equal(out, exp_out)


def test_ragged_gradient():
    # loss = psum over ranks of sum(out^2)/2  =>  dL/dx = x for delivered
    # rows, 0 for clamped-away rows (the exchange is a permutation+drop).
    rng = np.random.RandomState(2)
    splits_all = rng.randint(0, 5, size=(N, N)).astype(np.int32)
    T = int(splits_all.sum(axis=1).max())
    x_all = rng.randn(N, T).astype(np.float32)
    cap = 3

    def loss(x, sp):
        out, _ = hvd.alltoall_ragged(x[0], sp[0], capacity=cap)
        return jax.lax.psum(jnp.sum(out * out) / 2, hvd.HVD_AXES)

    def per_rank(x, sp):
        return jax.grad(lambda xx: loss(xx, sp))(x)

    g = spmd(per_rank, in_specs=(P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
             out_specs=P(hvd.HVD_AXES))(
        jnp.asarray(x_all), jnp.asarray(splits_all, jnp.int32))
    g = np.asarray(g).reshape(N, T)
    exp = np.zeros_like(x_all)
    for r in range(N):
        offs = np.cumsum(splits_all[r]) - splits_all[r]
        for d in range(N):
            k = min(int(splits_all[r, d]), cap)
            exp[r, offs[d]:offs[d] + k] = x_all[r, offs[d]:offs[d] + k]
    np.testing.assert_allclose(g, exp, rtol=1e-6)


def test_ragged_world1_eager():
    # Outside shard_map the process world is 1: everything loops back,
    # padded to the capacity contract.
    x = jnp.arange(6.0).reshape(3, 2)
    out, rsp = hvd.alltoall_ragged(x, [2], capacity=4)
    assert out.shape == (4, 2)
    np.testing.assert_array_equal(np.asarray(rsp), [2])
    np.testing.assert_array_equal(np.asarray(out[:2]), np.asarray(x[:2]))
    np.testing.assert_array_equal(np.asarray(out[2:]), 0)


def test_ragged_validation():
    with pytest.raises(ValueError):
        hvd.alltoall_ragged(jnp.zeros(4), [1], capacity=0)
    with pytest.raises(ValueError):
        hvd.alltoall_ragged(jnp.asarray(1.0), [1], capacity=2)
    with pytest.raises(ValueError):
        hvd.alltoall_ragged(jnp.zeros(4), [1, 2], capacity=2)
