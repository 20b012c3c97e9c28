"""Builder for SmallThinker decoders (a NoPE full-attention layer and
sliding-window layers at 7 query heads a KV head; on every layer ReLU-gated
experts whose router reads the block's input, before attention), trained on
the share of the model one chip holds.

The step is ``builders/sparse_moe_decoder.py``'s, entry point for entry
point: ``hvd.value_and_grad(loss_fn, reduce=False)``
+ ``hvd.DistributedOptimizer`` inside ``hvd.shard_map`` over
``hvd.mesh()``, donated state, one AOT ``lower().compile()``, AdamW behind
the recording clip, a pool of seeded batches, the untied head in
``hvd.lm_head_loss``. What differs: the model
(``horovod_tpu.models.SparseMoEDecoder`` built from the configuration
file's own SmallThinker keys), the weights (made from the seed by the plain
reference's own function, ``lib/reference_smallthinker.py``, so that the
reference can make them again), the learning rate (the same AdamW under
the configuration's ``warmup_steps``: the reference's ``warmup_schedule``,
handed to ``optax.adamw`` through the inherited ``_build``) and what is
stated about it: the FLOPs a
token needs (``lib/flops_smallthinker.py``), the two kinds of flash call a
step makes (``kernel_shapes`` ``swa_attention`` and ``nope_attention``,
which ``lib/kernels_window.py`` takes) and what the compiled text has to
hold.
"""

from __future__ import annotations

import functools
import re
import sys

from benchmarks.builders import gpt_decoder, sparse_moe_decoder
# Whole names: ``hvd_flash_fwd`` is a prefix of ``hvd_flash_fwd_win``.
from benchmarks.builders.sdar_moe import whole_name_count
from benchmarks.lib import (flops_smallthinker, reference_smallthinker,
                            traffic)

WINDOWED = ("hvd_flash_fwd_win", "hvd_flash_bwd_dq_win",
            "hvd_flash_bwd_dkv_win")
FULL = ("hvd_flash_fwd", "hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")
ROUTE_SCOPE = "hvd.moe_route"


def layers_routed_ahead(text: str, layers: int) -> int:
    """Layers of whose forward pass the compiled text holds an instruction
    under ``hvd.moe_route`` AHEAD of the layer's first flash forward kernel,
    in the entry computation: a scheduled module's instructions stand in
    the order they run, and one that reads what attention made cannot stand
    before it. A plan made from the post-attention stream reads 0 here."""
    route, flash = {}, {}
    entry = text.find("\nENTRY ")
    for n, line in enumerate(text[max(entry, 0):].splitlines()):
        at = re.search(r"/h(\d+)/", line)
        if at is None or "transpose(" in line:
            continue
        if ROUTE_SCOPE in line:
            route.setdefault(int(at.group(1)), n)
        if "hvd_flash_fwd" in line:
            flash.setdefault(int(at.group(1)), n)
    return sum(i in route and i in flash and route[i] < flash[i]
               for i in range(layers))


class Session(sparse_moe_decoder.Session):
    """``sparse_moe_decoder.Session`` with the SmallThinker model behind
    it: the step, the feed, the compile, the memory reckoning and what the
    check reads of the parameters are inherited."""

    def __init__(self, config: dict, job: dict, devices):
        import horovod_tpu as hvd
        from horovod_tpu.models import SparseMoEConfig, SparseMoEDecoder
        # A tree from before the family fails here, at once and before a
        # device is touched (ImportError).
        from horovod_tpu.moe import moe_apply, moe_route  # noqa: F401

        self.config, self.job, self.devices = config, job, list(devices)
        self.sizes = s = reference_smallthinker.sizes_from_config(config)
        # The inherited ``_build`` hands ``opt["lr"]`` to ``optax.adamw``,
        # which takes a schedule as it takes a number; the reference is
        # given the configuration's own numbers.
        self.opt = dict(config["optimizer"],
                        lr=reference_smallthinker.warmup_schedule(
                            config["optimizer"]))
        self.seq_len = job["seq_len"]
        if self.seq_len > config["max_position_embeddings"]:
            raise ValueError(f"job seq_len {self.seq_len} exceeds the "
                             f"configuration's positions")
        self.per_chip_batch = config["per_chip_batch"]
        self.global_batch = self.per_chip_batch * len(self.devices)
        self.tokens_per_step = self.global_batch * self.seq_len
        self.flops_per_token = flops_smallthinker.train_flops_per_token(
            s, self.seq_len)
        # What one flash call of each kind sees on a chip.
        call = dict(batch=self.per_chip_batch, seq=self.seq_len,
                    heads=s["heads"], kv_heads=s["kv_heads"],
                    head_dim=s["head_dim"], act_bytes=2)
        self.kernel_shapes = {}
        if any(s["sliding"]):
            self.kernel_shapes["swa_attention"] = dict(
                call, window=s["window"])
        if not all(s["sliding"]):
            self.kernel_shapes["nope_attention"] = dict(call, window=None)

        hvd.shutdown()
        hvd.init(devices=self.devices, mesh_shape=(1, len(self.devices)))
        self.hvd, self.mesh = hvd, hvd.mesh()
        self.model_cfg = SparseMoEConfig.from_dict(config, return_hidden=True)
        self.model = SparseMoEDecoder(self.model_cfg)
        self.params = self.opt_state = self.compiled = None
        self.pool, self.cursor = [], 0
        self._build()

    def _make(self):
        return functools.partial(reference_smallthinker.make_params,
                                 s=self.sizes)

    def structure_checks(self) -> list:
        """The compiled program holds each windowed kernel once a sliding
        layer and each full one once a full layer at the least (whole
        names), the grouped matmuls of every layer, and in every layer
        routing that stands ahead of attention (on a TPU; the interpreter
        inlines a kernel's body)."""
        text = self.compiled.as_text()
        on_tpu = self.devices[0].platform == "tpu"
        layers = self.sizes["layers"]
        sliding = sum(self.sizes["sliding"])
        rows = []
        for names, least in ((WINDOWED, sliding), (FULL, layers - sliding)):
            for name in names:
                n = whole_name_count(text, name)
                rows.append((f"{name}_in_program", n, f">={least}",
                             not on_tpu or n >= least))
        n = text.count("ragged-dot") + text.count("ragged_dot")
        rows.append(("grouped_matmuls_in_program", n, f">={3 * layers}",
                     not on_tpu or n >= 3 * layers))
        n = layers_routed_ahead(text, layers)
        rows.append(("layers_routed_ahead_of_attention", n, f"=={layers}",
                     not on_tpu or n == layers))
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
        args = (jax.device_put(self._seed(seed), dev),
                jax.device_put(toks, dev))
        if precision == "float32":
            return gpt_decoder.as_floats(jax.device_get(fn(*args)))
        # The control: e4m3 cotangents overflow under the sum of a 16k
        # sequence's losses, so it trains under a loss scale.
        out, scale = reference_smallthinker.finite_under_scale(
            lambda scale: jax.device_get(fn(*args, loss_scale=scale)))
        print(f"[control] seed {seed}: {precision} under loss scale {scale}",
              file=sys.stderr, flush=True)
        return gpt_decoder.as_floats(out)


@functools.lru_cache(maxsize=None)
def _reference_fn(sizes: tuple, opt: tuple, micro_rows: int, q_block: int,
                  precision: str):
    import jax

    return jax.jit(functools.partial(
        reference_smallthinker.train_steps, s=dict(sizes), opt=dict(opt),
        micro_rows=micro_rows, q_block=q_block, precision=precision))


def build(config: dict, job: dict, devices) -> Session:
    traffic.validate_job(job)
    return Session(config, job, devices)
