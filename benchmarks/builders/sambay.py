"""Builder for the decoder-hybrid-decoder family (``model_type``
``phi4flash``: state-space layers, differential attention with a window or
over every key, gated memory units and cross-attention that read what
earlier layers made), trained on the share of the model one chip holds.

The step is ``builders/gpt_decoder.py``'s, entry point for entry point:
``hvd.value_and_grad(loss_fn, reduce=False)`` + ``hvd.DistributedOptimizer``
inside ``hvd.shard_map`` over ``hvd.mesh()``, donated state, one AOT
``lower().compile()``, AdamW behind the recording clip, a pool of seeded
batches, weights made from the seed by the plain reference's own function
(``lib/reference_sambay.py``) so that the reference can make them again.
What differs: the model (``horovod_tpu.models.SambaY`` built from the
configuration file's own keys) and its TIED head: ``hvd.lm_head_loss`` is
handed the embedding, over the slice of the vocabulary the chip holds.

Stated about the model: the FLOPs a token needs (``lib/flops_sambay.py``),
the kernel calls a step makes and the named kernels the compiled text has
to hold. ``kernel_shapes``: ``selective_scan`` (what one scan sees, which
``lib/kernels_scan.py`` takes) and ``diff_window_attention`` /
``diff_attention`` (what one differential call sees: 40 softmax maps on 20
KV heads of 64, which ``lib/kernels_diff.py`` takes; no
``window_attention`` or ``gqa_attention`` entry: those readers' cost knows
one width for the score and the value).
"""

from __future__ import annotations

import functools

from benchmarks.builders import gpt_decoder
from benchmarks.lib import flops_sambay, reference_sambay, traffic

SCAN = ("hvd_selective_scan_fwd", "hvd_selective_scan_bwd")
WINDOWED = ("hvd_flash_fwd_win", "hvd_flash_bwd_dq_win",
            "hvd_flash_bwd_dkv_win")
FULL = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


class Session(gpt_decoder.Session):
    """``gpt_decoder.Session`` with another model behind it: the feed, the
    compile, the step and what the check reads are inherited."""

    def __init__(self, config: dict, job: dict, devices):
        import horovod_tpu as hvd
        # A tree from before the family fails here, at once and before a
        # device is touched (ImportError).
        from horovod_tpu.models import SambaY, SambaYConfig

        self.config, self.job, self.devices = config, job, list(devices)
        self.sizes = s = reference_sambay.sizes_from_config(config)
        self.opt = config["optimizer"]
        self.seq_len = job["seq_len"]
        if self.seq_len > config["max_position_embeddings"]:
            raise ValueError(f"job seq_len {self.seq_len} exceeds the "
                             f"configuration's positions")
        self.per_chip_batch = config["per_chip_batch"]
        self.global_batch = self.per_chip_batch * len(self.devices)
        self.tokens_per_step = self.global_batch * self.seq_len
        self.flops_per_token = flops_sambay.train_flops_per_token(
            s, self.seq_len)
        kinds = s["layer_types"]
        # One flash call: every head pair's two maps.
        call = dict(batch=self.per_chip_batch, seq=self.seq_len,
                    heads=s["heads"], kv_heads=s["kv_heads"],
                    head_dim=s["head_dim"], act_bytes=2)
        self.kernel_shapes = {}
        if reference_sambay.MAMBA in kinds:
            self.kernel_shapes["selective_scan"] = dict(
                batch=self.per_chip_batch, seq=self.seq_len,
                d_inner=s["d_inner"], d_state=s["d_state"])
        if reference_sambay.SLIDING in kinds:
            self.kernel_shapes["diff_window_attention"] = dict(
                call, window=s["window"])
        if {reference_sambay.FULL, reference_sambay.CROSS} & set(kinds):
            self.kernel_shapes["diff_attention"] = dict(call, window=None)

        hvd.shutdown()
        hvd.init(devices=self.devices, mesh_shape=(1, len(self.devices)))
        self.hvd, self.mesh = hvd, hvd.mesh()
        self.model_cfg = SambaYConfig.from_dict(config, return_hidden=True)
        self.model = SambaY(self.model_cfg)
        self.params = self.opt_state = self.compiled = None
        self.pool, self.cursor = [], 0
        self._build()

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        hvd, opt, model = self.hvd, self.opt, self.model
        dtype = self.model_cfg.dtype
        self.tx = tx = hvd.DistributedOptimizer(optax.chain(
            gpt_decoder.recording_clip(opt["clip_norm"]),
            optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], weight_decay=opt["weight_decay"])))

        def loss_fn(p, x, y):
            h = model.apply({"params": p}, x)
            return hvd.lm_head_loss(h, p["embed"].astype(dtype), y,
                                    mode="auto").mean()

        local_grads = hvd.value_and_grad(loss_fn, reduce=False)

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
        self._delta = jax.jit(lambda p, p0: reference_sambay.leaf_norms(
            jax.tree.map(jnp.subtract, p, p0)))

        want = jax.eval_shape(
            model.init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, self.seq_len), jnp.int32))["params"]
        got = self._abstract_params()
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise RuntimeError(
                "the program's parameter tree is not the tree "
                "benchmarks/lib/reference_sambay.py makes")

    def _make(self):
        return functools.partial(reference_sambay.make_params, s=self.sizes)

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
        """The compiled program holds each scan kernel once a state-space
        layer, each windowed flash kernel once a sliding layer and each full
        one once a full or cross-attention layer at the least (on a TPU; the
        interpreter inlines a kernel's body and runs the scan's fallback),
        and no array of a state a token ([T, d_inner, d_state])."""
        text = self.compiled.as_text()
        on_tpu = self.devices[0].platform == "tpu"
        kinds = self.sizes["layer_types"]
        want = {SCAN: kinds.count(reference_sambay.MAMBA),
                WINDOWED: kinds.count(reference_sambay.SLIDING),
                FULL: kinds.count(reference_sambay.FULL)
                + kinds.count(reference_sambay.CROSS)}
        rows = []
        for names, least in want.items():
            for name in names:
                n = text.count(name)
                if names == FULL:       # "hvd_flash_fwd" is in "..._win"
                    n -= text.count(name + "_win")
                rows.append((f"{name}_in_program", n, f">={least}",
                             not on_tpu or n >= least))
        state = (f"{self.seq_len},{self.sizes['d_inner']},"
                 f"{self.sizes['d_state']}]")
        n = text.count(state)
        rows.append(("state_a_token_arrays_in_program", n, "==0",
                     not on_tpu or n == 0))
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
        reference_sambay.train_steps, s=dict(sizes), opt=dict(opt),
        micro_rows=micro_rows, q_block=q_block, precision=precision))


def build(config: dict, job: dict, devices) -> Session:
    traffic.validate_job(job)
    return Session(config, job, devices)
