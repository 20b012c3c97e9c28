"""Model zoo + SyncBatchNorm tests (reference: sync-batch-norm tests in
test/parallel/test_torch.py; benchmark models in examples/)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import MnistNet, ResNet18, ResNet50
from horovod_tpu.parallel.sync_batch_norm import SyncBatchNorm

N = 8


def test_mnist_forward():
    model = MnistNet()
    x = jnp.zeros((2, 28, 28, 1))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out = model.apply(variables, x, train=False)
    assert out.shape == (2, 10)


def test_resnet18_forward_small():
    model = ResNet18(num_classes=10, dtype=jnp.float32)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    out, _ = model.apply(variables, x, train=False,
                         mutable=["batch_stats"])
    assert out.shape == (2, 10)


def test_resnet_space_to_depth_stem():
    """Folded stem: same output shape, 4x4x12 stem kernel, odd spatial
    dims rejected."""
    import pytest

    from horovod_tpu.models.resnet import ResNet18

    model = ResNet18(num_classes=10, dtype=jnp.float32,
                     space_to_depth=True)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    assert variables["params"]["conv_init"]["kernel"].shape == (4, 4, 12, 64)
    out, _ = model.apply(variables, x, train=False,
                         mutable=["batch_stats"])
    ref = ResNet18(num_classes=10, dtype=jnp.float32)
    rv = ref.init(jax.random.PRNGKey(0), x, train=False)
    ref_out, _ = ref.apply(rv, x, train=False, mutable=["batch_stats"])
    assert out.shape == ref_out.shape

    with pytest.raises(ValueError, match="even spatial"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 33, 33, 3)),
                   train=False)


def test_resnet50_param_count():
    # ~25.6M params is the well-known ResNet-50 size; catches structural bugs.
    model = ResNet50(num_classes=1000, dtype=jnp.float32)
    x = jnp.zeros((1, 64, 64, 3))
    variables = model.init(jax.random.PRNGKey(0), x, train=False)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree.leaves(variables["params"]))
    assert 25.4e6 < n_params < 25.8e6, n_params


def test_sync_batch_norm_global_moments():
    """SyncBatchNorm must normalize with global-batch statistics: feeding
    rank-dependent data, the normalized global batch has mean≈0, var≈1
    (reference: test_horovod_sync_batch_norm in test/parallel/test_torch.py).
    """
    model = SyncBatchNorm(use_running_average=False, momentum=0.9)
    rng = np.random.RandomState(0)
    data = (rng.randn(N * 4, 3) * 5 + 7).astype(np.float32)

    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((4, 3)))

    def f(xb):
        out, _ = model.apply(variables, xb, mutable=["batch_stats"])
        return out

    out = hvd.shard_map(f, mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
                        out_specs=P(hvd.HVD_AXES))(jnp.asarray(data))
    out = np.asarray(out)
    np.testing.assert_allclose(out.mean(axis=0), 0.0, atol=1e-3)
    np.testing.assert_allclose(out.std(axis=0), 1.0, atol=1e-2)


def test_sync_batch_norm_matches_big_batch():
    """Per-rank SyncBatchNorm output must equal single-device BatchNorm on
    the concatenated batch."""
    import flax.linen as nn

    rng = np.random.RandomState(1)
    data = (rng.randn(N * 2, 5) * 3 + 1).astype(np.float32)

    sync = SyncBatchNorm(use_running_average=False)
    plain = nn.BatchNorm(use_running_average=False)
    v_sync = sync.init(jax.random.PRNGKey(0), jnp.zeros((2, 5)))
    v_plain = plain.init(jax.random.PRNGKey(0), jnp.zeros((2, 5)))

    def f(xb):
        out, _ = sync.apply(v_sync, xb, mutable=["batch_stats"])
        return out

    out_sync = np.asarray(
        hvd.shard_map(f, mesh=hvd.mesh(), in_specs=P(hvd.HVD_AXES),
                      out_specs=P(hvd.HVD_AXES))(jnp.asarray(data)))
    out_plain, _ = plain.apply(v_plain, jnp.asarray(data),
                               mutable=["batch_stats"])
    np.testing.assert_allclose(out_sync, np.asarray(out_plain), atol=1e-4)


def test_mnist_dp_training_step_decreases_loss():
    """End-to-end: one DP training epoch on synthetic data lowers loss —
    the reference's MNIST example smoke test (examples/tensorflow2_mnist.py)."""
    model = MnistNet()
    rng = np.random.RandomState(0)
    x = rng.randn(N * 8, 28, 28, 1).astype(np.float32)
    y = rng.randint(0, 10, N * 8)

    variables = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28, 1)))
    params = variables["params"]
    tx = hvd.DistributedOptimizer(optax.sgd(0.05))
    opt_state = tx.init(params)

    def loss_fn(p, xb, yb):
        logits = model.apply({"params": p}, xb)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, yb).mean()

    @jax.jit
    def step(params, opt_state, xb, yb):
        def spmd(params, opt_state, xb, yb):
            loss, grads = hvd.value_and_grad(
                loss_fn, reduce=False)(params, xb, yb)
            updates, new_state = tx.update(grads, opt_state, params)
            return (optax.apply_updates(params, updates), new_state,
                    hvd.allreduce(loss))

        return hvd.shard_map(
            spmd, mesh=hvd.mesh(),
            in_specs=(P(), P(), P(hvd.HVD_AXES), P(hvd.HVD_AXES)),
            out_specs=(P(), P(), P()))(params, opt_state, xb, yb)

    losses = []
    for _ in range(5):
        params, opt_state, loss = step(params, opt_state,
                                       jnp.asarray(x), jnp.asarray(y))
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses
