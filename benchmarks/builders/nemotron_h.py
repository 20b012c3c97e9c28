"""Builder for ``nemotron_h`` hybrid decoders (one sublayer a layer by
``hybrid_override_pattern``: Mamba-2 mixers through the chunked scan
``hvd.ssd_scan``, a grouped-KV attention layer with no position, sigmoid-
routed two-matrix ``relu2`` experts walked in a latent beside a full-width
shared expert, the routers' biases carried as state), trained on the share
of the model one chip holds.

The step is ``builders/afmoe.py``'s, entry point for entry point:
``hvd.value_and_grad(loss_fn, has_aux=True, reduce=False)``
+ ``hvd.DistributedOptimizer`` inside ``hvd.shard_map`` over ``hvd.mesh()``,
donated state, one AOT ``lower().compile()``, AdamW behind the recording
clip, a pool of seeded batches, the untied head in ``hvd.lm_head_loss``,
the routers' biases as a third donated tree that
``update_router_biases`` moves after the optimizer's update. What differs:
the model (``horovod_tpu.models.HybridMambaMoE`` built from the
configuration file's own keys), the weights (made from the seed by the
plain reference's own function, ``lib/reference_nemotron_h.py``), the
learning rate (the same AdamW under the configuration's ``warmup_steps``,
the reference's ``warmup_schedule`` handed to ``optax.adamw``) and what is
stated about it: the FLOPs a token needs (``lib/flops_nemotron_h.py``), the
calls a step makes (``kernel_shapes`` ``ssd_scan``, which
``lib/kernels_ssd.py`` takes, with ``calls`` the scans a step runs in each
direction; ``nope_attention``, which ``lib/kernels_window.py`` takes) and
what the compiled text has to hold.
"""

from __future__ import annotations

import functools
import re

from benchmarks.builders import afmoe, gpt_decoder
from benchmarks.builders.sdar_moe import whole_name_count
from benchmarks.lib import flops_nemotron_h, reference_nemotron_h, traffic
from horovod_tpu.monitor.hlo_owners import BACKWARD_MARK, REMAT_MARK

FULL = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
SCAN_SCOPE = "hvd.ssd_scan"
#: ``lax.ragged_dot``s of one expert layer's two walks: W1 and W2 forward;
#: W1 again, dy W2^T and the hidden rows' W1^T in the backward.
GROUPED_A_LAYER = 5


def scan_layers(text: str) -> dict:
    """{"forward" | "backward" | "remat": the layers ``h<i>`` that hold a
    matmul under ``hvd.ssd_scan`` in that direction} of a compiled step's
    text (the directions as monitor/hlo_owners.py tells them)."""
    found = {"forward": set(), "backward": set(), "remat": set()}
    for line in text.splitlines():
        if SCAN_SCOPE not in line or not re.search(
                r"\b(dot|convolution)\(", line):
            continue
        at = re.search(r"/h(\d+)/", line)
        if at is None:
            continue
        direction = ("remat" if REMAT_MARK in line else
                     "backward" if BACKWARD_MARK in line else "forward")
        found[direction].add(int(at.group(1)))
    return found


class Session(afmoe.Session):
    """``afmoe.Session`` with the hybrid model behind it: the feed, the
    compile, the step's call, the biases' row and what the check reads of
    the parameters are inherited."""

    def __init__(self, config: dict, job: dict, devices):
        import horovod_tpu as hvd
        # A tree from before the family fails here, at once and before a
        # device is touched (ImportError).
        from horovod_tpu.models import (HybridMambaMoE, HybridMambaMoEConfig,
                                        update_router_biases)

        self._update_biases = update_router_biases
        self.config, self.job, self.devices = config, job, list(devices)
        self.sizes = s = reference_nemotron_h.sizes_from_config(config)
        self.opt = dict(config["optimizer"],
                        lr=reference_nemotron_h.warmup_schedule(
                            config["optimizer"]))
        self.seq_len = job["seq_len"]
        if self.seq_len > config["max_position_embeddings"]:
            raise ValueError(f"job seq_len {self.seq_len} exceeds the "
                             f"configuration's positions")
        self.per_chip_batch = config["per_chip_batch"]
        self.global_batch = self.per_chip_batch * len(self.devices)
        self.tokens_per_step = self.global_batch * self.seq_len
        self.flops_per_token = flops_nemotron_h.train_flops_per_token(
            s, self.seq_len)
        kinds = s["kinds"]
        self.kernel_shapes = {}
        if reference_nemotron_h.MAMBA in kinds:
            self.kernel_shapes["ssd_scan"] = dict(
                batch=self.per_chip_batch, seq=self.seq_len,
                heads=s["mamba_heads"], head_dim=s["mamba_head_dim"],
                groups=s["groups"], d_state=s["d_state"], chunk=s["chunk"],
                act_bytes=2, calls=kinds.count(reference_nemotron_h.MAMBA))
        if reference_nemotron_h.ATTENTION in kinds:
            self.kernel_shapes["nope_attention"] = dict(
                batch=self.per_chip_batch, seq=self.seq_len,
                heads=s["heads"], kv_heads=s["kv_heads"],
                head_dim=s["head_dim"], act_bytes=2, window=None)

        hvd.shutdown()
        hvd.init(devices=self.devices, mesh_shape=(1, len(self.devices)))
        self.hvd, self.mesh = hvd, hvd.mesh()
        self.model_cfg = HybridMambaMoEConfig.from_dict(
            config, return_hidden=True, return_load=True)
        self.model = HybridMambaMoE(self.model_cfg)
        self.params = self.opt_state = self.biases = self.compiled = None
        self.pool, self.cursor = [], 0
        self._bias_row = ["router_bias_moved", float("nan"),
                          "not read yet", False]
        self._build()

    def _make(self):
        return functools.partial(reference_nemotron_h.make_params,
                                 s=self.sizes)

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
            functools.partial(reference_nemotron_h.zero_biases, self.sizes),
            out_shardings=self.replicated)
        self._init_opt = jax.jit(tx.init, out_shardings=self.replicated)
        self._delta = jax.jit(lambda p, p0: reference_nemotron_h.leaf_norms(
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
                    f"benchmarks/lib/reference_nemotron_h.py makes")

    def structure_checks(self) -> list:
        """The compiled program holds each full flash kernel once an
        attention layer at the least (whole names), the grouped matmuls of
        one walk a direction in every expert layer, matmuls under
        ``hvd.ssd_scan`` in every Mamba layer's forward AND backward and in
        no layer's recomputed forward (the scan runs once a direction), and
        no array of a state a token (on a TPU; the interpreter inlines a
        kernel's body). The last row is filled after the checked steps
        (``delta_norms``): every router's bias has moved by the rule's
        step."""
        text = self.compiled.as_text()
        on_tpu = self.devices[0].platform == "tpu"
        kinds, s = self.sizes["kinds"], self.sizes
        mamba = {i for i, k in enumerate(kinds)
                 if k == reference_nemotron_h.MAMBA}
        attention = kinds.count(reference_nemotron_h.ATTENTION)
        routed = kinds.count(reference_nemotron_h.EXPERTS)
        rows = []
        for name in FULL:
            n = whole_name_count(text, name)
            rows.append((f"{name}_in_program", n, f">={attention}",
                         not on_tpu or n >= attention))
        n = text.count("ragged-dot") + text.count("ragged_dot")
        rows.append(("grouped_matmuls_in_program", n,
                     f">={GROUPED_A_LAYER * routed}",
                     not on_tpu or n >= GROUPED_A_LAYER * routed))
        found = scan_layers(text)
        for direction in ("forward", "backward"):
            rows.append((f"ssd_scan_{direction}_layers",
                         len(found[direction] & mamba), f"=={len(mamba)}",
                         not on_tpu or found[direction] >= mamba))
        rows.append(("ssd_scan_recomputed_layers", len(found["remat"]),
                     "==0", not on_tpu or not found["remat"]))
        state = (f"{self.seq_len},{s['mamba_heads']},{s['mamba_head_dim']},"
                 f"{s['d_state']}]")
        n = text.count(state)
        rows.append(("state_a_token_arrays_in_program", n, "==0",
                     not on_tpu or n == 0))
        rows.append(self._bias_row)
        return rows

    def reference(self, seed: int, steps: int,
                  precision: str = "float32") -> dict:
        import jax

        toks = traffic.token_pool(self.job, seed=seed,
                                  global_batch=self.global_batch,
                                  vocab=self.sizes["vocab"])[:steps]
        ref = self.config["reference"]
        fn = _reference_fn(gpt_decoder._freeze(self.sizes),
                           gpt_decoder._freeze(self.config["optimizer"]),
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
        reference_nemotron_h.train_steps, s=dict(sizes), opt=dict(opt),
        micro_rows=micro_rows, q_block=q_block, precision=precision))


def build(config: dict, job: dict, devices) -> Session:
    traffic.validate_job(job)
    return Session(config, job, devices)
