"""Data-parallel JAX training example (reference analogue:
examples/tensorflow2/tensorflow2_synthetic_benchmark.py, adapted to the
JAX-first API).

Single-process: uses every local device through the Horovod mesh.
Multi-process (one process per TPU host):

    hvdrun -np 2 -H localhost:2 python examples/jax_synthetic.py
"""

import _path_setup  # noqa: F401  (repo-root import shim)
from _path_setup import add_cpu_flag, apply_cpu_flag

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.models import MnistNet


def main():
    ap = add_cpu_flag(argparse.ArgumentParser())
    args = ap.parse_args()
    apply_cpu_flag(args)
    hvd.init()
    mesh = hvd.mesh()
    print(f"rank {hvd.rank()}/{hvd.size()} devices={mesh.devices.shape}")

    model = MnistNet(num_classes=10)
    rng = jax.random.PRNGKey(42)
    params = model.init(rng, jnp.zeros((1, 28, 28, 1)))["params"]

    # DistributedOptimizer averages gradients across the mesh in-jit.
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))
    opt_state = tx.init(params)

    rs = np.random.RandomState(0)
    global_batch = 32 * hvd.size()
    images = jnp.asarray(rs.randn(global_batch, 28, 28, 1), jnp.float32)
    labels = jnp.asarray(rs.randint(0, 10, global_batch))

    def loss_fn(p, xb, yb):
        logits = model.apply({"params": p}, xb)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, yb).mean()

    @jax.jit
    def train_step(p, s, xb, yb):
        def spmd(p, s, xb, yb):
            loss, grads = hvd.value_and_grad(
                loss_fn, reduce=False)(p, xb, yb)  # tx owns the reduction
            updates, ns = tx.update(grads, s, p)
            return optax.apply_updates(p, updates), ns, hvd.allreduce(loss)

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(), hvd.data_pspec(), hvd.data_pspec()),
            out_specs=(P(), P(), P()))(p, s, xb, yb)

    losses = []
    for step in range(20):
        params, opt_state, loss = train_step(params, opt_state,
                                             images, labels)
        losses.append(float(loss))
        if hvd.rank() == 0 and step % 5 == 0:
            print(f"step {step}: loss {losses[-1]:.4f}")

    assert losses[-1] < losses[0], "loss did not decrease"
    if hvd.rank() == 0:
        print(f"OK: loss {losses[0]:.4f} -> {losses[-1]:.4f}")
    hvd.shutdown()


if __name__ == "__main__":
    main()
