"""Builder for ``sdar_moe`` decoders: Qwen3-MoE's block trained under the
block-diffusion objective (a noised and a clean copy of every sequence in
one step, attention under the block-diffusion mask, a loss over the masked
positions weighted by 1/t), on the share of the model one chip holds.

The step is ``builders/sparse_moe_decoder.py``'s, entry point for entry
point: ``hvd.value_and_grad(loss_fn, reduce=False)`` +
``hvd.DistributedOptimizer`` inside ``hvd.shard_map`` over ``hvd.mesh()``,
donated state, one AOT ``lower().compile()``, AdamW behind the recording
clip, a pool of seeded batches, weights made from the seed by the plain
reference's own function (``lib/reference_sdar.py``). What differs:

* the model is built from the configuration's ``sdar_moe`` keys and handed
  ``[noised ; clean]`` rows that ``hvd.block_diffusion_noise`` makes INSIDE
  the step from ``fold_in(key(seed), step)``; the loss is
  ``hvd.block_diffusion_loss`` over the noised half's hidden states;
* a THIRD tree the step carries beside parameters and optimizer state, the
  noise's ``{"seed", "step"}`` (donated, replicated; the step adds one to
  ``step``), so that the reference makes the same draws from the seed;
* the feed: a sequence is the job's first ``seq_len`` ids (the objective
  has no shift, so the job's last id is not used), drawn from the
  vocabulary slice less its last row, the mask id.

Stated about the model: ``tokens_per_step`` counts the DATA tokens (the
``2 x`` rows are the objective's cost, not its yield), the FLOPs a data
token needs (``lib/flops_sdar.py``), the one kind of flash call a step
makes (``kernel_shapes["block_diffusion_attention"]``, which
``lib/kernels_block_diffusion.py`` takes) and the named kernels the
compiled text has to hold.
"""

from __future__ import annotations

import functools
import re

from benchmarks.builders import gpt_decoder, sparse_moe_decoder
from benchmarks.lib import flops_sdar, reference_sdar, traffic

KERNELS = ("hvd_flash_fwd_bd", "hvd_flash_bwd_dq_bd", "hvd_flash_bwd_dkv_bd")


def whole_name_count(text: str, name: str) -> int:
    """Occurrences of ``name`` as a whole name: ``hvd_flash_fwd`` is a
    prefix of ``hvd_flash_fwd_bd`` and of ``hvd_flash_fwd_win``, and is
    neither."""
    return len(re.findall(rf"(?<![A-Za-z0-9_]){re.escape(name)}"
                          rf"(?![A-Za-z0-9_])", text))


class Session(sparse_moe_decoder.Session):
    """``sparse_moe_decoder.Session`` with the objective in the step and
    the noise's state beside the optimizer's: the compile, the memory
    reckoning and what the check reads of the parameters are inherited."""

    def __init__(self, config: dict, job: dict, devices):
        import horovod_tpu as hvd
        # A tree from before the objective fails here, at once and before
        # a device is touched (ImportError / AttributeError).
        from horovod_tpu import block_diffusion_loss, block_diffusion_noise
        from horovod_tpu.models import SparseMoEConfig, SparseMoEDecoder
        from horovod_tpu.models.sparse_moe_decoder import BLOCK_DIFFUSION

        self._noise, self._loss = block_diffusion_noise, block_diffusion_loss
        self.config, self.job, self.devices = config, job, list(devices)
        self.sizes = s = reference_sdar.sizes_from_config(config)
        self.opt = config["optimizer"]
        self.seq_len = job["seq_len"]
        if self.seq_len > config["max_position_embeddings"]:
            raise ValueError(f"job seq_len {self.seq_len} exceeds the "
                             f"configuration's positions")
        self.per_chip_batch = config["per_chip_batch"]
        self.global_batch = self.per_chip_batch * len(self.devices)
        self.tokens_per_step = self.global_batch * self.seq_len
        self.flops_per_token = flops_sdar.train_flops_per_token(
            s, self.seq_len)
        # What one block-diffusion call sees on a chip: ``seq`` is L, the
        # call's rows are 2 L.
        self.kernel_shapes = {"block_diffusion_attention": dict(
            batch=self.per_chip_batch, seq=self.seq_len, heads=s["heads"],
            kv_heads=s["kv_heads"], head_dim=s["head_dim"],
            block=s["block_length"], act_bytes=2)}

        hvd.shutdown()
        hvd.init(devices=self.devices, mesh_shape=(1, len(self.devices)))
        self.hvd, self.mesh = hvd, hvd.mesh()
        self.model_cfg = SparseMoEConfig.from_dict(config, return_hidden=True)
        if set(self.model_cfg.layer_types) != {BLOCK_DIFFUSION}:
            raise RuntimeError("not a block-diffusion decoder")
        self.model = SparseMoEDecoder(self.model_cfg)
        self.params = self.opt_state = self.noise = self.compiled = None
        self.pool, self.cursor = [], 0
        self._build()

    def _make(self):
        return functools.partial(reference_sdar.make_params, s=self.sizes)

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        hvd, opt, model, s = self.hvd, self.opt, self.model, self.sizes
        noise_fn, loss_of = self._noise, self._loss
        dtype, rows = self.model_cfg.dtype, self.per_chip_batch
        self.tx = tx = hvd.DistributedOptimizer(optax.chain(
            gpt_decoder.recording_clip(opt["clip_norm"]),
            optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], weight_decay=opt["weight_decay"])))

        def loss_fn(p, noised, x0):
            h = model.apply({"params": p}, noised.rows)
            return loss_of(h, p["head"].astype(dtype), x0, noised.masked,
                           noised.t)

        local_grads = hvd.value_and_grad(loss_fn, reduce=False)

        def spmd(p, st, n, x0):
            key = jax.random.fold_in(jax.random.key(n["seed"]), n["step"])
            noised = noise_fn(
                x0, key, block_length=s["block_length"],
                mask_id=s["mask_id"], first_row=hvd.rank() * rows)
            loss, grads = local_grads(p, noised, x0)
            updates, st = tx.update(grads, st, p)
            n = {"seed": n["seed"], "step": n["step"] + 1}
            return (optax.apply_updates(p, updates), st, n,
                    hvd.allreduce(loss))

        self.step_fn = jax.jit(hvd.shard_map(
            spmd, mesh=self.mesh,
            in_specs=(P(), P(), P(), hvd.data_pspec()),
            out_specs=(P(), P(), P(), P())), donate_argnums=(0, 1, 2))
        self.replicated = NamedSharding(self.mesh, P())
        self.data_sharding = hvd.data_sharding()
        self._make_params = jax.jit(self._make(),
                                    out_shardings=self.replicated)
        self._make_noise = jax.jit(
            lambda seed: {"seed": seed, "step": jnp.zeros((), jnp.int32)},
            out_shardings=self.replicated)
        self._init_opt = jax.jit(tx.init, out_shardings=self.replicated)
        self._delta = jax.jit(lambda p, p0: reference_sdar.leaf_norms(
            jax.tree.map(jnp.subtract, p, p0)))

        want = jax.eval_shape(
            self.model.init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, 2 * self.seq_len), jnp.int32))["params"]
        got = self._abstract_params()
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise RuntimeError(
                "the program's parameter tree is not the tree "
                "benchmarks/lib/reference_sdar.py makes")

    def _token_pool(self, seed: int):
        """The job's batches with ids from the slice less its last row
        (the mask id)."""
        return traffic.token_pool(self.job, seed=seed,
                                  global_batch=self.global_batch,
                                  vocab=self.sizes["mask_id"])

    def init_state(self, seed: int) -> None:
        import jax

        super().init_state(seed)
        self.noise = self._make_noise(self._seed(seed))
        jax.block_until_ready(self.noise)

    def place_inputs(self, seed: int) -> None:
        import jax

        self.pool = [jax.device_put(b[:, :-1], self.data_sharding)
                     for b in self._token_pool(seed)]
        self.cursor = 0
        jax.block_until_ready(self.pool)

    def abstract_args(self):
        import jax
        import jax.numpy as jnp

        params, state, tokens, _ = super().abstract_args()
        noise = jax.eval_shape(self._make_noise,
                               jax.ShapeDtypeStruct((), jnp.uint32))
        return (params, state, jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=self.replicated), noise),
            tokens)

    def lower(self, args=None):
        if args is None:
            args = (self.params, self.opt_state, self.noise, self.pool[0])
        return self.step_fn.lower(*args)

    def step(self):
        x0 = self.pool[self.cursor % len(self.pool)]
        self.cursor += 1
        self.params, self.opt_state, self.noise, loss = self.compiled(
            self.params, self.opt_state, self.noise, x0)
        return loss

    def structure_checks(self) -> list:
        """The compiled program holds each ``*_bd`` kernel once a layer at
        the least, NO flash kernel without the suffix (whole names: the
        plain names are prefixes of these), the grouped matmuls of every
        layer, and no score array over the rows (on a TPU; the interpreter
        inlines a kernel's body)."""
        text = self.compiled.as_text()
        on_tpu = self.devices[0].platform == "tpu"
        layers, rows = self.sizes["layers"], 2 * self.seq_len
        out = []
        for name in KERNELS:
            n = whole_name_count(text, name)
            out.append((f"{name}_in_program", n, f">={layers}",
                        not on_tpu or n >= layers))
            plain = whole_name_count(text, name[:-len("_bd")])
            out.append((f"{name[:-len('_bd')]}_in_program", plain, "==0",
                        plain == 0))
        n = text.count("ragged-dot") + text.count("ragged_dot")
        out.append(("grouped_matmuls_in_program", n, f">={3 * layers}",
                    not on_tpu or n >= 3 * layers))
        squares = len(re.findall(
            rf"[\[,] ?({rows}|{self.seq_len}), ?{rows}\]", text))
        out.append(("score_arrays_over_the_rows", squares, "==0",
                    not on_tpu or squares == 0))
        return out

    def release(self) -> None:
        import jax

        for leaf in jax.tree.leaves(self.noise):
            if not leaf.is_deleted():
                leaf.delete()
        self.noise = None
        super().release()

    def reference(self, seed: int, steps: int,
                  precision: str = "float32") -> dict:
        import jax

        toks = self._token_pool(seed)[:steps]
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
        reference_sdar.train_steps, s=dict(sizes), opt=dict(opt),
        micro_rows=micro_rows, q_block=q_block, precision=precision))


def build(config: dict, job: dict, devices) -> Session:
    traffic.validate_job(job)
    return Session(config, job, devices)
