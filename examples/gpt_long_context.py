"""Long-context GPT training: flash attention + sequence parallelism.

No reference analogue (the reference is a CNN-era data-parallel framework,
SURVEY §5.7); this demonstrates the TPU build's long-context flagship:

* ``--attention flash`` (default): the Pallas flash kernel
  (horovod_tpu/ops/flash_attention.py) trains at sequence lengths where
  the dense path cannot even allocate its score tensor — at seq 8192,
  batch 2, 12 heads, dense attention needs B*H*T^2 fp32 = 6.4 GB *per
  layer* for the scores alone; flash streams them through VMEM.
* ``--attention ring`` / ``--attention flash_ring``: sequence
  parallelism — shards the sequence over the mesh (`ppermute` ring over
  ICI) so per-chip memory is O(T/n); ``flash_ring`` runs the Pallas
  flash kernel at every ring step (scores stay in VMEM too). Run on the
  8-device CPU mesh to see an 8-way sequence shard:

      XLA_FLAGS=--xla_force_host_platform_device_count=8 \
          python examples/gpt_long_context.py --attention ring --platform cpu

  (On the CPU mesh the Pallas kernels run in interpreter mode — an
  emulator. For ``flash_ring`` there, shrink the model:
  ``--layers 2 --seq-len 256 --steps 2``. Real speed needs real chips.)

Single real chip: `python examples/gpt_long_context.py` (flash, seq 8192).
"""

import _path_setup  # noqa: F401  (repo-root import shim)

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--attention",
                    choices=["flash", "ring", "flash_ring", "dense"],
                    default="flash")
    ap.add_argument("--seq-len", type=int, default=8192)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--batch-size", type=int, default=2,
                    help="global batch (sequences)")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--platform", default=None,
                    help="force a jax platform (e.g. cpu for the virtual "
                         "8-device mesh)")
    args = ap.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)

    import horovod_tpu as hvd
    from horovod_tpu.models import GPT, GPTConfig

    hvd.init()
    mesh = hvd.mesh()
    print(f"world {hvd.size()} mesh={mesh.devices.shape} "
          f"attention={args.attention} seq={args.seq_len}")

    cfg = GPTConfig(vocab_size=8192, num_layers=args.layers, num_heads=12,
                    d_model=768, d_ff=3072, max_seq_len=args.seq_len,
                    attention=args.attention, seq_axis=hvd.HVD_AXES,
                    remat=True)
    model = GPT(cfg)

    rs = np.random.RandomState(0)
    toks = rs.randint(0, cfg.vocab_size, (args.batch_size,
                                          args.seq_len + 1))
    x = jnp.asarray(toks[:, :-1])
    y = jnp.asarray(toks[:, 1:])

    # Ring modes shard the SEQUENCE over the mesh; flash/dense shard the
    # batch (plain DP).
    data_spec = (P(None, hvd.HVD_AXES)
                 if args.attention in ("ring", "flash_ring")
                 else hvd.data_pspec())

    variables = model.init(jax.random.PRNGKey(0), x[:1, :128])
    tx = hvd.DistributedOptimizer(optax.adamw(3e-4),
                                  compression=hvd.Compression.bf16)
    opt_state = tx.init(variables["params"])

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
            in_specs=(P(), P(), data_spec, data_spec),
            out_specs=(P(), P(), P()))(p, s, xb, yb)

    import time

    params = variables["params"]
    losses = []
    for step in range(args.steps):
        t0 = time.perf_counter()
        params, opt_state, loss = train_step(params, opt_state, x, y)
        loss = float(jax.block_until_ready(loss))
        losses.append(loss)
        if hvd.rank() == 0:
            dt = time.perf_counter() - t0
            tps = args.batch_size * args.seq_len / dt
            print(f"step {step}: loss {loss:.4f}  "
                  f"({dt * 1e3:.0f} ms, {tps:,.0f} tok/s)")

    assert losses[-1] < losses[0], "loss did not decrease"
    if hvd.rank() == 0:
        print(f"OK: loss {losses[0]:.4f} -> {losses[-1]:.4f} at "
              f"seq {args.seq_len} ({args.attention})")
    hvd.shutdown()


if __name__ == "__main__":
    main()
