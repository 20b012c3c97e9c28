"""Builder for decoder-only GPT configurations trained data-parallel.

The construction ``chip_smoke.py train_phase`` ran on the chip in PR 21,
through the public API only and with no option the API defaults:
``hvd.value_and_grad(loss_fn, reduce=False)`` + ``hvd.DistributedOptimizer``
inside ``hvd.shard_map`` over ``hvd.mesh()``, donated state, one AOT
``lower().compile()``. What differs from the bring-up: the published
vocabulary, AdamW behind a global-norm clip, a pool of seeded batches, and
weights the benchmark makes itself from the seed so that the plain
reference can make the same ones again.

A builder gives the harness one ``Session`` (see benchmarks/README.md for
the interface). A later model family brings a builder file of its own.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

from benchmarks.lib import flops, reference_gpt2, traffic


class ClipState(NamedTuple):
    """State of ``recording_clip``: the L2 norm of each leaf of the gradient
    the transformation was last handed (before the clip)."""
    grad_norms: Any


def recording_clip(max_norm: float):
    """Clip by the global norm as ``optax.clip_by_global_norm`` does, and
    keep the per-leaf norms of the incoming gradient in the state.

    AdamW's update does not depend on the gradient's scale, and neither
    does a gradient clipped to unit norm: a gradient divided twice by the
    world size trains to the same losses. The norms of what
    ``DistributedOptimizer`` hands its inner transformation are where such a
    fault shows, so the check reads them here."""
    import jax
    import jax.numpy as jnp
    import optax

    def norms_of(tree):
        return jax.tree.map(
            lambda g: jnp.sqrt(jnp.sum(jnp.square(g.astype(jnp.float32)))),
            tree)

    def init(params):
        return ClipState(jax.tree.map(
            lambda _: jnp.zeros((), jnp.float32), params))

    def update(updates, state, params=None):
        del state, params
        norms = norms_of(updates)
        gnorm = jnp.sqrt(sum(n ** 2 for n in jax.tree.leaves(norms)))
        scale = jnp.where(gnorm < max_norm, 1.0, max_norm / gnorm)
        return (jax.tree.map(lambda g: g * scale.astype(g.dtype), updates),
                ClipState(norms))

    return optax.GradientTransformation(init, update)


def _find_clip_state(opt_state) -> ClipState:
    import jax

    found = [x for x in jax.tree.leaves(
        opt_state, is_leaf=lambda x: isinstance(x, ClipState))
        if isinstance(x, ClipState)]
    if len(found) != 1:
        raise RuntimeError(f"expected one ClipState in the optimizer state, "
                           f"found {len(found)}")
    return found[0]


class Session:
    """The compiled step with its state: built once in set-up, driven
    through the checked first steps, then handed to the window."""

    def __init__(self, config: dict, job: dict, devices):
        import horovod_tpu as hvd
        from horovod_tpu.models import GPT, GPTConfig

        self.config, self.job, self.devices = config, job, list(devices)
        self.sizes = s = reference_gpt2.sizes_from_config(config)
        self.opt = config["optimizer"]
        self.seq_len = job["seq_len"]
        if self.seq_len > s["positions"]:
            raise ValueError(f"job seq_len {self.seq_len} exceeds the "
                             f"configuration's {s['positions']} positions")
        self.per_chip_batch = config["per_chip_batch"]
        self.global_batch = self.per_chip_batch * len(self.devices)
        self.tokens_per_step = self.global_batch * self.seq_len
        self.flops_per_token = flops.decoder_train_flops_per_token(
            layers=s["layers"], d_model=s["d_model"], d_ff=s["d_ff"],
            vocab=s["vocab"], seq_len=self.seq_len)
        # What one flash call sees on a chip (lib/kernels.py takes these).
        self.kernel_shapes = {"flash_attention": dict(
            batch=self.per_chip_batch, seq=self.seq_len, heads=s["heads"],
            head_dim=s["d_model"] // s["heads"], causal=True, act_bytes=2)}

        hvd.shutdown()
        hvd.init(devices=self.devices, mesh_shape=(1, len(self.devices)))
        self.hvd, self.mesh = hvd, hvd.mesh()
        self.model_cfg = GPTConfig(
            vocab_size=s["vocab"], max_seq_len=s["positions"],
            attention="flash", num_layers=s["layers"], num_heads=s["heads"],
            d_model=s["d_model"], d_ff=s["d_ff"], return_hidden=True,
            embed_init_std=s["init_std"])
        self.model = GPT(self.model_cfg)
        self.params = self.opt_state = self.compiled = None
        self.pool, self.cursor = [], 0
        self._build()

    # -- construction -----------------------------------------------------

    def _build(self) -> None:
        import jax
        import jax.numpy as jnp
        import optax
        from jax.sharding import NamedSharding, PartitionSpec as P

        hvd, model, cfg, opt = self.hvd, self.model, self.model_cfg, self.opt

        def loss_fn(p, x, y):
            h = model.apply({"params": p}, x)
            return hvd.lm_head_loss(h, p["wte"].astype(cfg.dtype), y,
                                    mode="auto").mean()

        self.tx = tx = hvd.DistributedOptimizer(optax.chain(
            recording_clip(opt["clip_norm"]),
            optax.adamw(opt["lr"], b1=opt["b1"], b2=opt["b2"],
                        eps=opt["eps"], weight_decay=opt["weight_decay"])))
        # reduce=False: the optimizer's fused bucket allreduce is the one
        # gradient collective (chip_smoke.py; ROADMAP D12).
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
        self._make_params = jax.jit(
            functools.partial(reference_gpt2.make_params, s=self.sizes),
            out_shardings=self.replicated)
        self._init_opt = jax.jit(tx.init, out_shardings=self.replicated)
        self._delta = jax.jit(lambda p, p0: reference_gpt2.leaf_norms(
            jax.tree.map(jnp.subtract, p, p0)))

        # The benchmark makes the weights; the program's own tree says
        # whether they are the weights it expects.
        want = jax.eval_shape(
            model.init, jax.random.key(0),
            jax.ShapeDtypeStruct((1, self.seq_len), jnp.int32))["params"]
        got = jax.eval_shape(
            functools.partial(reference_gpt2.make_params, s=self.sizes),
            jax.ShapeDtypeStruct((), jnp.uint32))
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                a.shape != b.shape or a.dtype != b.dtype for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise RuntimeError(
                "the program's parameter tree is not the GPT-2 tree "
                "benchmarks/lib/reference_gpt2.py makes; this family needs "
                "a builder of its own")

    def _seed(self, seed: int):
        import numpy as np

        return np.uint32(int(seed) % 2 ** 32)

    def init_state(self, seed: int) -> None:
        """Parameters and optimizer state on the device(s), from the seed,
        in one jitted call each."""
        import jax

        self.params = self._make_params(self._seed(seed))
        self.opt_state = self._init_opt(self.params)
        jax.block_until_ready((self.params, self.opt_state))

    def place_inputs(self, seed: int) -> None:
        import jax

        toks = traffic.token_pool(self.job, seed=seed,
                                  global_batch=self.global_batch,
                                  vocab=self.sizes["vocab"])
        self.pool = [
            (jax.device_put(b[:, :-1], self.data_sharding),
             jax.device_put(b[:, 1:], self.data_sharding)) for b in toks]
        self.cursor = 0
        jax.block_until_ready(self.pool)

    def abstract_args(self):
        """The step's arguments as shapes with shardings: enough to trace
        and compile it with nothing on a device."""
        import jax
        import jax.numpy as jnp

        def placed(tree, sharding):
            return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype, sharding=sharding), tree)

        params = jax.eval_shape(
            functools.partial(reference_gpt2.make_params, s=self.sizes),
            jax.ShapeDtypeStruct((), jnp.uint32))
        state = jax.eval_shape(self.tx.init, params)
        tokens = jax.ShapeDtypeStruct((self.global_batch, self.seq_len),
                                      jnp.int32)
        return (placed(params, self.replicated),
                placed(state, self.replicated),
                placed(tokens, self.data_sharding),
                placed(tokens, self.data_sharding))

    def lower(self, args=None):
        """The step lowered for ``args`` (shapes with shardings) or for the
        live state and the first batch."""
        if args is None:
            args = (self.params, self.opt_state, *self.pool[0])
        return self.step_fn.lower(*args)

    def compile(self) -> None:
        self.compiled = self.lower().compile()

    # -- the timed call ---------------------------------------------------

    def step(self):
        """Enqueue one step on the pool's next batch; returns the loss (a
        device scalar that is ready when the step has run)."""
        x, y = self.pool[self.cursor % len(self.pool)]
        self.cursor += 1
        self.params, self.opt_state, loss = self.compiled(
            self.params, self.opt_state, x, y)
        return loss

    # -- what the check reads ---------------------------------------------

    def first_gradient_norms(self) -> dict:
        """{leaf: norm} of the gradient the optimizer's inner
        transformation was handed in the step just run."""
        import jax

        norms = _find_clip_state(self.opt_state).grad_norms
        return {k: float(v) for k, v in
                reference_gpt2.path_dict(jax.device_get(norms)).items()}

    def delta_norms(self, seed: int) -> dict:
        import jax

        # The weights of step 0 made again from the seed, not kept.
        start = self._make_params(self._seed(seed))
        return {k: float(v) for k, v in jax.device_get(
            self._delta(self.params, start)).items()}

    def structure_checks(self) -> list:
        """Facts of the compiled program and the placed state that a fast
        wrong program would break: [(name, value, limit, ok)]."""
        import jax

        text = self.compiled.as_text()
        n = len(self.devices)
        rows = [("flash_kernel_calls_in_program",
                 text.count("tpu_custom_call"), ">=1",
                 self.devices[0].platform != "tpu"
                 or "tpu_custom_call" in text)]
        if n > 1:
            held = {d for leaf in jax.tree.leaves(
                (self.params, self.opt_state, self.pool))
                for d in leaf.devices()}
            rows.append(("devices_holding_state", len(held), f"=={n}",
                         held == set(self.devices)))
            shard_rows = {s.data.shape[0] for s in
                          self.pool[0][0].addressable_shards}
            rows.append(("batch_rows_per_device", max(shard_rows),
                         f"=={self.per_chip_batch}",
                         shard_rows == {self.per_chip_batch}))
            rows.append(("all_reduce_in_program",
                         text.count("all-reduce"), ">=1",
                         "all-reduce" in text))
        return rows

    def memory_analysis(self) -> dict:
        m = self.compiled.memory_analysis()
        out = {k: int(getattr(m, k + "_size_in_bytes")) for k in
               ("argument", "output", "temp", "alias", "generated_code")}
        out["total"] = (out["argument"] + out["output"] + out["temp"]
                        - out["alias"])
        return out

    def release(self) -> None:
        """Free the program's state before the reference runs."""
        import jax

        for leaf in jax.tree.leaves((self.params, self.opt_state, self.pool)):
            if not leaf.is_deleted():
                leaf.delete()
        self.params = self.opt_state = None
        self.pool = []

    # -- the plain reference ----------------------------------------------

    def reference(self, seed: int, steps: int,
                  precision: str = "float32") -> dict:
        """The plain reference over the first ``steps`` global batches, on
        one device: {"loss": [..], "grad_norm": {leaf: ..},
        "delta_norm": {leaf: ..}} as floats."""
        import jax

        toks = traffic.token_pool(self.job, seed=seed,
                                  global_batch=self.global_batch,
                                  vocab=self.sizes["vocab"])[:steps]
        fn = _reference_fn(_freeze(self.sizes), _freeze(self.opt),
                           self.config["reference"]["micro_rows"], precision)
        dev = self.devices[0]
        out = fn(jax.device_put(self._seed(seed), dev),
                 jax.device_put(toks, dev))
        return as_floats(jax.device_get(out))


def as_floats(out: dict) -> dict:
    """The reference's result as plain floats, the form lib/compare.py
    takes."""
    return {"loss": [float(v) for v in out["loss"]],
            "grad_norm": {k: float(v) for k, v in out["grad_norm"].items()},
            "delta_norm": {k: float(v) for k, v in out["delta_norm"].items()}}


def _freeze(d: dict) -> tuple:
    return tuple(sorted(d.items()))


@functools.lru_cache(maxsize=None)
def _reference_fn(sizes: tuple, opt: tuple, micro_rows: int, precision: str):
    import jax

    return jax.jit(functools.partial(
        reference_gpt2.train_steps, s=dict(sizes), opt=dict(opt),
        micro_rows=micro_rows, precision=precision))


def build(config: dict, job: dict, devices) -> Session:
    traffic.validate_job(job)
    return Session(config, job, devices)
