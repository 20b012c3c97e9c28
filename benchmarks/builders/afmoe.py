"""Builder for ``afmoe`` decoders (window and full grouped-KV attention, a
dense gated MLP or sigmoid-routed experts beside a shared one, a balancing
bias carried as state), trained on the share of the model one chip holds.

The step is ``builders/gpt_decoder.py``'s, entry point for entry point:
``hvd.value_and_grad(loss_fn, reduce=False)`` + ``hvd.DistributedOptimizer``
inside ``hvd.shard_map`` over ``hvd.mesh()``, donated state, one AOT
``lower().compile()``, AdamW behind the recording clip, a pool of seeded
batches, weights made from the seed by the plain reference's own function
(``lib/reference_afmoe.py``) so that the reference can make them again.
What differs: the model (``horovod_tpu.models.SparseMoEDecoder`` built from
the configuration file's own ``afmoe`` keys), its untied head in
``hvd.lm_head_loss``, and a THIRD tree the step carries beside parameters
and optimizer state: the routers' selection biases (the model's
``router_bias`` collection), donated and replicated like them. The tape
differentiates the parameters alone (``has_aux`` hands the step's expert
counts out), the optimizer never sees the biases (no gradient, no weight
decay), and after its update ``update_router_biases`` moves them from the
counts summed over the data axis by ``hvd.allreduce``.

Stated about the model: the FLOPs a token needs (``lib/flops_afmoe.py``),
the two kinds of flash call a step makes (``kernel_shapes``
``window_attention`` and ``gqa_attention``, which ``lib/kernels_window.py``
takes; no ``flash_attention`` entry: that reader's cost knows one head
count and no window) and the named kernels the compiled text has to hold.
"""

from __future__ import annotations

import functools

from benchmarks.builders import gpt_decoder
from benchmarks.lib import flops_afmoe, reference_afmoe, traffic

WINDOWED = ("hvd_flash_fwd_win", "hvd_flash_bwd_dq_win",
            "hvd_flash_bwd_dkv_win")
FULL = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


class Session(gpt_decoder.Session):
    """``gpt_decoder.Session`` with another model behind it and the
    routers' biases beside the state: the feed, the compile and what the
    check reads of the parameters are inherited."""

    def __init__(self, config: dict, job: dict, devices):
        import horovod_tpu as hvd
        # A tree from before the family fails here, at once and before a
        # device is touched (ImportError).
        from horovod_tpu.models import (SparseMoEConfig, SparseMoEDecoder,
                                        update_router_biases)

        self._update_biases = update_router_biases
        self.config, self.job, self.devices = config, job, list(devices)
        self.sizes = s = reference_afmoe.sizes_from_config(config)
        self.opt = config["optimizer"]
        self.seq_len = job["seq_len"]
        if self.seq_len > config["max_position_embeddings"]:
            raise ValueError(f"job seq_len {self.seq_len} exceeds the "
                             f"configuration's positions")
        self.per_chip_batch = config["per_chip_batch"]
        self.global_batch = self.per_chip_batch * len(self.devices)
        self.tokens_per_step = self.global_batch * self.seq_len
        self.flops_per_token = flops_afmoe.train_flops_per_token(
            s, self.seq_len)
        # What one flash call of each kind sees on a chip.
        call = dict(batch=self.per_chip_batch, seq=self.seq_len,
                    heads=s["heads"], kv_heads=s["kv_heads"],
                    head_dim=s["head_dim"], act_bytes=2)
        self.kernel_shapes = {}
        if reference_afmoe.SLIDING in s["layer_types"]:
            self.kernel_shapes["window_attention"] = dict(
                call, window=s["window"])
        if reference_afmoe.FULL in s["layer_types"]:
            self.kernel_shapes["gqa_attention"] = dict(call, window=None)

        hvd.shutdown()
        hvd.init(devices=self.devices, mesh_shape=(1, len(self.devices)))
        self.hvd, self.mesh = hvd, hvd.mesh()
        self.model_cfg = SparseMoEConfig.from_dict(
            config, return_hidden=True, return_load=True)
        self.model = SparseMoEDecoder(self.model_cfg)
        self.params = self.opt_state = self.biases = self.compiled = None
        self.pool, self.cursor = [], 0
        # One row of ``structure_checks``: filled by ``delta_norms``, which
        # the harness calls after the checked steps and before it reads
        # the rows.
        self._bias_row = ["router_bias_moved", float("nan"),
                          "not read yet", False]
        self._build()

    def _make(self):
        return functools.partial(reference_afmoe.make_params, s=self.sizes)

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        hvd, opt, model = self.hvd, self.opt, self.model
        update_router_biases = self._update_biases
        dtype, coeff = self.model_cfg.dtype, self.sizes["balance_coeff"]
        self.tx = tx = hvd.DistributedOptimizer(optax.chain(
            gpt_decoder.recording_clip(opt["clip_norm"]),
            optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], weight_decay=opt["weight_decay"])))

        def loss_fn(p, b, x, y):
            h, loads = model.apply({"params": p, "router_bias": b}, x)
            return hvd.lm_head_loss(h, p["head"].astype(dtype), y,
                                    mode="auto").mean(), loads

        local_grads = hvd.value_and_grad(loss_fn, has_aux=True, reduce=False)

        def spmd(p, s, b, x, y):
            (loss, loads), grads = local_grads(p, b, x, y)
            updates, s = tx.update(grads, s, p)
            b = update_router_biases(
                b, loads, coeff=coeff,
                reduce=lambda n: hvd.allreduce(n, op=hvd.Sum))
            return optax.apply_updates(p, updates), s, b, hvd.allreduce(loss)

        self.step_fn = jax.jit(hvd.shard_map(
            spmd, mesh=self.mesh,
            in_specs=(P(), P(), P(), hvd.data_pspec(), hvd.data_pspec()),
            out_specs=(P(), P(), P(), P())), donate_argnums=(0, 1, 2))
        self.replicated = NamedSharding(self.mesh, P())
        self.data_sharding = hvd.data_sharding()
        self._make_params = jax.jit(self._make(),
                                    out_shardings=self.replicated)
        self._make_biases = jax.jit(
            functools.partial(reference_afmoe.zero_biases, self.sizes),
            out_shardings=self.replicated)
        self._init_opt = jax.jit(tx.init, out_shardings=self.replicated)
        self._delta = jax.jit(lambda p, p0: reference_afmoe.leaf_norms(
            jax.tree.map(jnp.subtract, p, p0)))

        want = jax.eval_shape(
            self.model.init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, self.seq_len), jnp.int32))
        for name, got in (("params", self._abstract_params()),
                          ("router_bias", jax.eval_shape(self._make_biases))):
            if jax.tree.structure(want[name]) != jax.tree.structure(got) or \
                    any(a.shape != b.shape or a.dtype != b.dtype for a, b in
                        zip(jax.tree.leaves(want[name]),
                            jax.tree.leaves(got))):
                raise RuntimeError(
                    f"the program's {name} tree is not the tree "
                    f"benchmarks/lib/reference_afmoe.py makes")

    def _abstract_params(self):
        import jax
        import jax.numpy as jnp

        return jax.eval_shape(self._make(),
                              jax.ShapeDtypeStruct((), jnp.uint32))

    def init_state(self, seed: int) -> None:
        import jax

        super().init_state(seed)
        self.biases = self._make_biases()
        jax.block_until_ready(self.biases)

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
                placed(jax.eval_shape(self._make_biases), self.replicated),
                placed(tokens, self.data_sharding),
                placed(tokens, self.data_sharding))

    def lower(self, args=None):
        if args is None:
            args = (self.params, self.opt_state, self.biases, *self.pool[0])
        return self.step_fn.lower(*args)

    def step(self):
        x, y = self.pool[self.cursor % len(self.pool)]
        self.cursor += 1
        self.params, self.opt_state, self.biases, loss = self.compiled(
            self.params, self.opt_state, self.biases, x, y)
        return loss

    def delta_norms(self, seed: int) -> dict:
        """The parameters' change and, as leaves of the same dict, the
        biases' (they start at zero: a bias's change is the bias). Also
        fills the ``router_bias_moved`` row of ``structure_checks``."""
        import jax
        import numpy as np

        out = super().delta_norms(seed)
        biases = reference_afmoe.path_dict(jax.device_get(self.biases))
        out.update({k: float(np.linalg.norm(v)) for k, v in biases.items()})
        # One step of the rule moves every entry by coeff (the re-centring
        # apart), so a layer's bias has norm coeff * sqrt(E) after one and
        # never more than steps * 2 * coeff an entry; a frozen bias reads 0.
        coeff, steps = self.sizes["balance_coeff"], self.cursor
        least = min(float(np.linalg.norm(v)) / (coeff * np.sqrt(v.size))
                    for v in biases.values())
        bound = max(float(np.abs(v).max()) for v in biases.values())
        self._bias_row[1:] = [
            least, f">=0.5 (of one step's norm; largest entry {bound:.4g} "
                   f"<= {2 * steps * coeff:.4g})",
            least >= 0.5 and bound <= 2 * steps * coeff * (1 + 1e-3)]
        return out

    def structure_checks(self) -> list:
        """The compiled program holds each windowed kernel once a sliding
        layer and each full one once a full layer at the least, and the
        grouped matmuls of every routed layer (on a TPU; the interpreter
        inlines a kernel's body). The last row is filled after the checked
        steps (``delta_norms``): every router's bias has moved by the
        rule's step."""
        text = self.compiled.as_text()
        on_tpu = self.devices[0].platform == "tpu"
        kinds = self.sizes["layer_types"]
        sliding = kinds.count(reference_afmoe.SLIDING)
        full = kinds.count(reference_afmoe.FULL)
        routed = self.sizes["layers"] - self.sizes["dense_layers"]
        rows = []
        for win, name in zip(WINDOWED, FULL):
            n_win = text.count(win)
            rows.append((f"{win}_in_program", n_win, f">={sliding}",
                         not on_tpu or n_win >= sliding))
            n = text.count(name) - n_win
            rows.append((f"{name}_in_program", n, f">={full}",
                         not on_tpu or n >= full))
        n = text.count("ragged-dot") + text.count("ragged_dot")
        rows.append(("grouped_matmuls_in_program", n, f">={3 * routed}",
                     not on_tpu or n >= 3 * routed))
        rows.append(self._bias_row)
        return rows

    def release(self) -> None:
        import jax

        for leaf in jax.tree.leaves(self.biases):
            if not leaf.is_deleted():
                leaf.delete()
        self.biases = None
        super().release()

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
        reference_afmoe.train_steps, s=dict(sizes), opt=dict(opt),
        micro_rows=micro_rows, q_block=q_block, precision=precision))


def build(config: dict, job: dict, devices) -> Session:
    traffic.validate_job(job)
    return Session(config, job, devices)
