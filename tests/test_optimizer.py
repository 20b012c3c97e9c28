"""DistributedOptimizer / tape tests.

Key invariant (the reference's core correctness property): N-way data
parallel training with gradient averaging must match single-device training
on the concatenated global batch (test/parallel/test_torch.py optimizer
tests assert the same)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd

N = 8


def make_data(rng, n=64, d=5):
    w = rng.randn(d, 1).astype(np.float32)
    x = rng.randn(n, d).astype(np.float32)
    y = x @ w + 0.1 * rng.randn(n, 1).astype(np.float32)
    return x, y


def loss_fn(params, batch):
    x, y = batch
    pred = x @ params["w"] + params["b"]
    return jnp.mean((pred - y) ** 2)


def init_params(d=5):
    return {"w": jnp.zeros((d, 1)), "b": jnp.zeros((1,))}


def dp_train(tx, steps, x, y):
    """shard_map data-parallel training over the 8-device mesh."""
    params = init_params()
    opt_state = tx.init(params)
    mesh = hvd.mesh()

    @jax.jit
    def step(params, opt_state, xb, yb):
        def spmd_full(params, opt_state, xb, yb):
            loss, grads = jax.value_and_grad(loss_fn)(params, (xb, yb))
            updates, new_state = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_state, hvd.allreduce(loss)

        rep = jax.tree.map(lambda _: P(), (params, opt_state))
        return hvd.shard_map(
            spmd_full, mesh=mesh,
            in_specs=(rep[0], rep[1], P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(rep[0], rep[1], P()))(params, opt_state, xb, yb)

    bs = x.shape[0] // steps
    for i in range(steps):
        xb = jnp.asarray(x[i * bs:(i + 1) * bs])
        yb = jnp.asarray(y[i * bs:(i + 1) * bs])
        params, opt_state, loss = step(params, opt_state, xb, yb)
    return params


def single_train(tx, steps, x, y):
    params = init_params()
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, xb, yb):
        grads = jax.grad(loss_fn)(params, (xb, yb))
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    bs = x.shape[0] // steps
    for i in range(steps):
        params, opt_state = step(params, opt_state,
                                 jnp.asarray(x[i * bs:(i + 1) * bs]),
                                 jnp.asarray(y[i * bs:(i + 1) * bs]))
    return params


def test_dp_matches_single_device_global_batch():
    rng = np.random.RandomState(0)
    x, y = make_data(rng, n=8 * 4 * N)
    dist_tx = hvd.DistributedOptimizer(optax.sgd(0.1))
    ref_tx = optax.sgd(0.1)
    p_dist = dp_train(dist_tx, 4, x, y)
    p_ref = single_train(ref_tx, 4, x, y)
    for k in p_ref:
        np.testing.assert_allclose(np.asarray(p_dist[k]),
                                   np.asarray(p_ref[k]), rtol=1e-4, atol=1e-6)


def test_distributed_optimizer_sum_op():
    rng = np.random.RandomState(1)
    x, y = make_data(rng, n=8 * N)
    # op=Sum multiplies the effective lr by N vs Average.
    p_sum = dp_train(hvd.DistributedOptimizer(optax.sgd(0.01), op=hvd.Sum),
                     1, x, y)
    p_avg = dp_train(hvd.DistributedOptimizer(optax.sgd(0.01 * N)), 1, x, y)
    for k in p_sum:
        np.testing.assert_allclose(np.asarray(p_sum[k]),
                                   np.asarray(p_avg[k]), rtol=1e-4, atol=1e-6)


def test_gradient_predivide_factor():
    # predivide splits the averaging divisor (tensorflow/__init__.py:462-476);
    # final result must equal plain averaging.
    rng = np.random.RandomState(2)
    x, y = make_data(rng, n=8 * N)
    p_pre = dp_train(
        hvd.DistributedOptimizer(optax.sgd(0.1),
                                 gradient_predivide_factor=4.0), 1, x, y)
    p_avg = dp_train(hvd.DistributedOptimizer(optax.sgd(0.1)), 1, x, y)
    for k in p_avg:
        np.testing.assert_allclose(np.asarray(p_pre[k]),
                                   np.asarray(p_avg[k]), rtol=1e-4, atol=1e-6)


def test_predivide_requires_average():
    with pytest.raises(ValueError):
        hvd.DistributedOptimizer(optax.sgd(0.1), op=hvd.Sum,
                                 gradient_predivide_factor=2.0)


def test_backward_passes_per_step_accumulates():
    # k accumulation steps at lr then one apply ≈ one step on the averaged
    # grads (reference: torch/optimizer.py:133-149). With SGD the result
    # equals a single step with the mean of the k microbatch gradients.
    rng = np.random.RandomState(3)
    x, y = make_data(rng, n=2 * 8 * N)
    tx = hvd.DistributedOptimizer(optax.sgd(0.1), backward_passes_per_step=2)
    p2 = dp_train(tx, 2, x, y)  # two microbatches → exactly one apply

    # Single big batch with plain averaging must match.
    tx1 = hvd.DistributedOptimizer(optax.sgd(0.1))
    p1 = dp_train(tx1, 1, x, y)
    for k in p1:
        np.testing.assert_allclose(np.asarray(p2[k]), np.asarray(p1[k]),
                                   rtol=1e-4, atol=1e-6)


def test_value_and_grad_allreduces():
    rng = np.random.RandomState(4)
    xs = rng.randn(N, 3).astype(np.float32)

    def f(p, x):
        return jnp.sum(p * x)

    def spmd(p, x):
        val, g = hvd.value_and_grad(f)(p, x[0])
        return g

    out = hvd.shard_map(spmd, mesh=hvd.mesh(),
                        in_specs=(P(), P(hvd.HVD_AXES)),
                        out_specs=P())(jnp.ones(3), jnp.asarray(xs))
    np.testing.assert_allclose(np.asarray(out), xs.mean(0), rtol=1e-5)


def _one_sgd_update(tx, **tape_kw):
    """One sgd(1.0) update of zeros(3) on f(p, x) = sum(p * x), rank r
    holding row r of xs: the right answer is -mean(xs, 0)."""
    xs = np.arange(N * 3, dtype=np.float32).reshape(N, 3)
    p = jnp.zeros(3)

    def spmd(p, s, x):
        _, g = hvd.value_and_grad(
            lambda p, x: jnp.sum(p * x), **tape_kw)(p, x[0])
        return tx.update(g, s, p)[0]

    out = jax.jit(hvd.shard_map(
        spmd, mesh=hvd.mesh(), in_specs=(P(), P(), P(hvd.HVD_AXES)),
        out_specs=P()))(p, tx.init(p), jnp.asarray(xs))
    return np.asarray(out), -xs.mean(0)


@pytest.mark.parametrize("pairing", ["optimizer_owns", "tape_owns"])
def test_one_reducer_gives_the_mean_update(pairing):
    # Either owner alone averages exactly once over the 8 ranks.
    if pairing == "optimizer_owns":
        out, want = _one_sgd_update(
            hvd.DistributedOptimizer(optax.sgd(1.0)), reduce=False)
    else:
        out, want = _one_sgd_update(optax.sgd(1.0))
    np.testing.assert_allclose(out, want, rtol=1e-6)


@pytest.mark.parametrize("opt_kw", [
    {}, {"backward_passes_per_step": 2}, {"zero": True},
    {"overlap": True, "backward_passes_per_step": 2}],
    ids=["plain", "multisteps", "zero", "overlap_multisteps"])
def test_two_reducers_raise_at_trace_time(opt_kw):
    # The reducing tape's averages handed to DistributedOptimizer would be
    # divided by the world a second time (update / 8 here, silently).
    tx = hvd.DistributedOptimizer(optax.sgd(1.0), **opt_kw)
    with pytest.raises(ValueError, match="Keep one reducer"):
        _one_sgd_update(tx)


def test_distributed_gradient_tape_shim():
    rng = np.random.RandomState(5)
    xs = rng.randn(N, 3).astype(np.float32)

    def f(p, x):
        return jnp.sum(p * x)

    tape = hvd.DistributedGradientTape(f)

    def spmd(p, x):
        loss, g = tape.gradient(p, x[0])
        return g

    out = hvd.shard_map(spmd, mesh=hvd.mesh(),
                        in_specs=(P(), P(hvd.HVD_AXES)),
                        out_specs=P())(jnp.ones(3), jnp.asarray(xs))
    np.testing.assert_allclose(np.asarray(out), xs.mean(0), rtol=1e-5)


def test_grad_has_aux_contract():
    # Regression: hvd.grad(has_aux=True) must return (grads, aux) like
    # jax.grad.
    def f(p):
        return jnp.sum(p ** 2), {"aux": 7}

    g, aux = hvd.grad(f, has_aux=True)(jnp.ones(3))
    np.testing.assert_allclose(np.asarray(g), 2 * np.ones(3))
    assert aux == {"aux": 7}


def test_allreduce_pytree_collective_semantics_on_replicated():
    # Regression: public allreduce_pytree defaults to plain collective
    # semantics — Min on a replicated leaf is the identity, not an error.
    def f(_):
        tree = {"m": jnp.asarray([4.0, 5.0])}
        return hvd.allreduce_pytree(tree, op=hvd.Min)

    out = hvd.shard_map(f, mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
                        out_specs=P())(jnp.zeros(N))
    np.testing.assert_array_equal(np.asarray(out["m"]), [4.0, 5.0])


def test_adasum_with_compression():
    # Regression: op=Adasum must honor compression (wire dtype) and still
    # produce float32 output close to the uncompressed result.
    rng = np.random.RandomState(11)
    x = rng.randn(N, 16).astype(np.float32)

    def f(v):
        return hvd.allreduce(v[0], op=hvd.Adasum,
                             compression=hvd.Compression.bf16)

    out = hvd.shard_map(f, mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
                        out_specs=P())(jnp.asarray(x))
    ref = hvd.shard_map(lambda v: hvd.allreduce(v[0], op=hvd.Adasum),
                        mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
                        out_specs=P())(jnp.asarray(x))
    assert out.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=5e-2,
                               atol=0.1)
