"""Builder for decoders with sparse (indexer-selected) attention and routed
experts, trained on the share of the model one chip holds.

The step is ``builders/gpt_decoder.py``'s, entry point for entry point:
``hvd.value_and_grad(loss_fn, reduce=False)`` + ``hvd.DistributedOptimizer``
inside ``hvd.shard_map`` over ``hvd.mesh()``, donated state, one AOT
``lower().compile()``, AdamW behind the recording clip, a pool of seeded
batches, weights made from the seed by the plain reference's own function
(``lib/reference_sparse_moe.py``) so that the reference can make them again.
What differs is the model (``horovod_tpu.models.SparseMoEDecoder``, built
from the configuration file's own keys), its untied head in
``hvd.lm_head_loss``, and what is stated about it: the FLOPs a token needs
(``lib/flops_sparse_moe.py``), the kernels' shapes (``lib/kernels_sparse.py``)
and the named kernels the compiled text has to hold.
"""

from __future__ import annotations

import functools

from benchmarks.builders import gpt_decoder
from benchmarks.lib import flops_sparse_moe, reference_sparse_moe, traffic

# Named kernels the compiled step has to hold, once a layer at the least;
# the backward by its prefix, however many kernels it is made of.
KERNELS = ("hvd_index_select", "hvd_sparse_attn_fwd", "hvd_sparse_attn_bwd")


class Session(gpt_decoder.Session):
    """``gpt_decoder.Session`` with another model behind it: the state,
    the feed, the timed call and what the check reads are inherited."""

    def __init__(self, config: dict, job: dict, devices):
        import horovod_tpu as hvd
        from horovod_tpu.models import SparseMoEConfig, SparseMoEDecoder

        self.config, self.job, self.devices = config, job, list(devices)
        self.sizes = s = reference_sparse_moe.sizes_from_config(config)
        self.opt = config["optimizer"]
        self.seq_len = job["seq_len"]
        if self.seq_len > config["max_position_embeddings"]:
            raise ValueError(f"job seq_len {self.seq_len} exceeds the "
                             f"configuration's positions")
        self.per_chip_batch = config["per_chip_batch"]
        self.global_batch = self.per_chip_batch * len(self.devices)
        self.tokens_per_step = self.global_batch * self.seq_len
        self.flops_per_token = flops_sparse_moe.train_flops_per_token(
            s, self.seq_len)
        # What one sparse-attention call sees on a chip
        # (lib/kernels_sparse.py takes these).
        self.kernel_shapes = {"sparse_attention": dict(
            batch=self.per_chip_batch, seq=self.seq_len, heads=s["heads"],
            kv_heads=s["kv_heads"], head_dim=s["head_dim"],
            topk=s["idx_topk"], idx_heads=s["idx_heads"],
            idx_dim=s["idx_dim"], act_bytes=2, calls_per_step=s["layers"])}

        hvd.shutdown()
        hvd.init(devices=self.devices, mesh_shape=(1, len(self.devices)))
        self.hvd, self.mesh = hvd, hvd.mesh()
        self.model_cfg = SparseMoEConfig.from_dict(config, return_hidden=True)
        self.model = SparseMoEDecoder(self.model_cfg)
        self.params = self.opt_state = self.compiled = None
        self.pool, self.cursor = [], 0
        self._build()

    def _loss_fn(self):
        hvd, model, dtype = self.hvd, self.model, self.model_cfg.dtype

        def loss_fn(p, x, y):
            h = model.apply({"params": p}, x)
            return hvd.lm_head_loss(h, p["head"].astype(dtype), y,
                                    mode="auto").mean()
        return loss_fn

    def _make(self):
        return functools.partial(reference_sparse_moe.make_params,
                                 s=self.sizes)

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        hvd, opt = self.hvd, self.opt
        self.tx = tx = hvd.DistributedOptimizer(optax.chain(
            gpt_decoder.recording_clip(opt["clip_norm"]),
            optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], weight_decay=opt["weight_decay"])))
        local_grads = hvd.value_and_grad(self._loss_fn(), reduce=False)

        def spmd(p, s, x, y):
            loss, grads = local_grads(p, x, y)
            updates, s = tx.update(grads, s, p)
            return optax.apply_updates(p, updates), s, hvd.allreduce(loss)

        self.step_fn = jax.jit(hvd.shard_map(
            spmd, mesh=self.mesh,
            in_specs=(P(), P(), hvd.data_pspec(), hvd.data_pspec()),
            out_specs=(P(), P(), P())), donate_argnums=(0, 1))
        self.replicated = NamedSharding(self.mesh, P())
        self.data_sharding = hvd.data_sharding()
        self._make_params = jax.jit(self._make(),
                                    out_shardings=self.replicated)
        self._init_opt = jax.jit(tx.init, out_shardings=self.replicated)
        self._delta = jax.jit(lambda p, p0: reference_sparse_moe.leaf_norms(
            jax.tree.map(jnp.subtract, p, p0)))

        want = jax.eval_shape(
            self.model.init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, self.seq_len), jnp.int32))["params"]
        got = self._abstract_params()
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise RuntimeError(
                "the program's parameter tree is not the tree "
                "benchmarks/lib/reference_sparse_moe.py makes")

    def _abstract_params(self):
        import jax
        import jax.numpy as jnp

        return jax.eval_shape(self._make(),
                              jax.ShapeDtypeStruct((), jnp.uint32))

    def abstract_args(self):
        import jax
        import jax.numpy as jnp

        def placed(tree, sharding):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=sharding), tree)

        params = self._abstract_params()
        state = jax.eval_shape(self.tx.init, params)
        tokens = jax.ShapeDtypeStruct((self.global_batch, self.seq_len),
                                      jnp.int32)
        return (placed(params, self.replicated),
                placed(state, self.replicated),
                placed(tokens, self.data_sharding),
                placed(tokens, self.data_sharding))

    def structure_checks(self) -> list:
        """The compiled program holds each named kernel once a layer at
        the least (on a TPU; the interpreter inlines a kernel's body)."""
        text = self.compiled.as_text()
        on_tpu = self.devices[0].platform == "tpu"
        layers = self.sizes["layers"]
        rows = []
        for name in KERNELS:
            n = text.count(name)
            rows.append((f"{name}_in_program", n, f">={layers}",
                         not on_tpu or n >= layers))
        n = text.count("ragged-dot") + text.count("ragged_dot")
        rows.append(("grouped_matmuls_in_program", n, f">={3 * layers}",
                     not on_tpu or n >= 3 * layers))
        return rows

    def reference(self, seed: int, steps: int,
                  precision: str = "float32") -> dict:
        import jax

        toks = traffic.token_pool(self.job, seed=seed,
                                  global_batch=self.global_batch,
                                  vocab=self.sizes["vocab"])[:steps]
        ref = self.config["reference"]
        fn = _reference_fn(gpt_decoder._freeze(self.sizes),
                           gpt_decoder._freeze(self.opt),
                           ref["micro_rows"], ref["q_block"], precision)
        dev = self.devices[0]
        out = fn(jax.device_put(self._seed(seed), dev),
                 jax.device_put(toks, dev))
        return gpt_decoder.as_floats(jax.device_get(out))


@functools.lru_cache(maxsize=None)
def _reference_fn(sizes: tuple, opt: tuple, micro_rows: int, q_block: int,
                  precision: str):
    import jax

    return jax.jit(functools.partial(
        reference_sparse_moe.train_steps, s=dict(sizes), opt=dict(opt),
        micro_rows=micro_rows, q_block=q_block, precision=precision))


def build(config: dict, job: dict, devices) -> Session:
    traffic.validate_job(job)
    return Session(config, job, devices)
