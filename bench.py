#!/usr/bin/env python
"""Synthetic training benchmark — the reference's headline harness.

Default mode mirrors ``examples/tensorflow2/tensorflow2_synthetic_benchmark
.py`` from the reference (docs/benchmarks.rst:66-80): ResNet-50, synthetic
ImageNet-shaped data, SGD-momentum, DistributedOptimizer gradient
averaging, reporting images/sec. ``--model gpt`` swaps in a GPT-124M (or
``--gpt-scale 350m``) language model over the identical training step,
reporting tokens/sec — the matmul-dominated counterpoint to ResNet's
HBM-bound profile. Runs on every visible chip via the Horovod mesh.

Prints ONE JSON line:
  {"metric": "resnet50_images_per_sec_per_chip" |
             "gpt{124m,350m}_tokens_per_sec_per_chip",
   "value": <items/s/chip>, "unit": "images/sec/chip"|"tokens/sec/chip",
   "vs_baseline": <ratio, resnet50 only — null for gpt>, "mfu": <frac>,
   "platform": "tpu", ...}

Scaling mode (the north-star metric, docs/benchmarks.rst:13-43): pass
``--scaling 1,2,4,8`` to run the SAME weak-scaling step (fixed per-chip
batch, global batch = B*N) over growing device-subset meshes and report
per-chip throughput plus efficiency vs the smallest world. The JSON line
then carries ``{model}_scaling_efficiency_{maxN}chip`` with the full
per-world table, and ``vs_baseline`` compares against the reference's
published 90% 512-GPU scaling figure (docs/benchmarks.rst:13-14). The
sweep runs unchanged on a v5e pod the day one is attached; today it is
smoke-tested on the 8-device virtual CPU mesh
(``--platform cpu --cpu-devices 8 --model resnet18 ...`` — MFU is omitted
on CPU automatically). ``--chips N`` restricts any single run to the
first N visible chips.

Methodology (round 3): per-chip batch 128, median-step throughput/MFU,
timing blocks on every step output, donated state buffers, optional
``--profile`` device-trace capture with a category/bytes roofline summary,
optional ``--steps-per-call`` host-loop offload. See README.md
"Benchmark methodology" for the profile-backed roofline analysis.

``vs_baseline`` (single-run mode) compares against 103.55 images/sec/device
— the only absolute per-device throughput published in the reference:
tf_cnn_benchmarks ResNet-101, batch 64, 1656.82 images/sec on 16 Pascal
GPUs (docs/benchmarks.rst:27-43) → 103.55/GPU. BASELINE.json publishes no
chip-level numbers (`published: {}`), so that figure is the anchor. Because a
2017-Pascal anchor says little about a modern TPU chip, the JSON also carries
**MFU** (model FLOPs utilization): compiled-step FLOPs (XLA cost analysis)
divided by measured step time and the chip's peak bf16 FLOP/s.

``--platform auto`` (the default) means the chip or nothing: where JAX
finds no accelerator the run exits non-zero and prints no number.
``--platform cpu`` is the virtual CPU mesh for tests and smoke scripts; its
JSON says ``"platform": "cpu"`` and carries no MFU.
"""

import argparse
import json
import os
import sys
import time


def log(msg):
    print(msg, file=sys.stderr, flush=True)


def parse_mesh_shape(spec: str):
    """Parse ``--mesh-shape``: ``CROSSxLOCAL`` → (cross, local), or
    ``CROSSxLOCALxPODS`` → (cross, local, pods) — the 3-level
    ``(hvd_pod, hvd_cross, hvd_local)`` mesh (docs/wire-plan.md)."""
    try:
        parts = tuple(int(v) for v in
                      spec.lower().replace(",", "x").split("x"))
    except ValueError:
        parts = ()
    if len(parts) not in (2, 3):
        raise ValueError(f"--mesh-shape expects CROSSxLOCAL or "
                         f"CROSSxLOCALxPODS ints, got {spec!r}")
    if any(v < 1 for v in parts):
        raise ValueError("--mesh-shape sizes must be >= 1")
    return parts


def mesh_shape_str(mesh_shape):
    return ("x".join(str(v) for v in mesh_shape)
            if mesh_shape else None)


def dump_plan(args, mesh_shape):
    """``--dump-plan``: print the resolved wire plan as a table and exit
    — no devices needed (the cost model prices the emulated mesh). The
    ``model ms``/``pred ms`` columns are the predicted-vs-measured pair
    (docs/cost-model.md): modeled bytes-at-bandwidth vs the full
    calibrated-when-available cost model."""
    from horovod_tpu import plan as hvd_plan

    if mesh_shape is None:
        n = args.chips or args.cpu_devices
        mesh_shape = (2, n // 2) if n % 2 == 0 and n >= 2 else (1, n)
        log(f"--dump-plan: no --mesh-shape given, pricing the emulated "
            f"{mesh_shape_str(mesh_shape)} mesh")
    step_plan = hvd_plan.describe_plan(
        quantized=args.quantized or None,
        zero_stage=(args.zero_stage if args.zero_stage
                    else (2 if args.zero else None)),
        overlap=args.overlap or None,
        fused=args.fused or None,
        quantized_pod=args.quantized_pod or None,
        hierarchical=args.quantized_pod or None,
        mesh_shape=mesh_shape,
        pp_stages=args.pp or None,
        pp_microbatches=args.pp_microbatches if args.pp else None,
        pp_interleave=args.pp_interleave if args.pp else None,
        pp_schedule=args.pp_schedule if args.pp else None,
        pp_quantized=(args.quantized or None) if args.pp else None,
        moe_experts=args.moe or None,
        moe_topk=args.moe_topk if args.moe else None,
        moe_capacity=args.moe_capacity if args.moe else None,
        moe_quantized=(args.quantized or None) if args.moe else None,
    )
    model = hvd_plan.get_cost_model(mesh_shape=mesh_shape)
    if model.source != "static":
        log(f"--dump-plan: pricing with the calibrated link model "
            f"({model.geometry})")
    print(step_plan.table(payload_bytes=args.dump_plan_bytes,
                          model=model))


def metrics_snapshot(prefixes=("comm.", "step.", "optimizer.",
                               "straggler.", "link.", "compile.")):
    """Registry snapshot filtered to the bench-relevant metric families —
    the ``metrics_snapshot`` field every A/B leg embeds in its JSON line
    (docs/observability.md). Also flushes the configured sinks, so a run
    with HOROVOD_METRICS_JSONL set leaves a joinable artifact for
    scripts/obs_report.py."""
    from horovod_tpu import monitor

    monitor.flush()
    snap = monitor.snapshot()

    def _filt(d):
        return {k: v for k, v in sorted(d.items())
                if k.startswith(tuple(prefixes))}

    return {"counters": _filt(snap["counters"]),
            "gauges": _filt(snap["gauges"]),
            "histograms": _filt(snap["histograms"])}


# ---------------------------------------------------------------------------
# Compile-once plumbing (docs/compile.md): every measured leg routes its
# lower+compile through the executable cache, so a warm rerun performs
# ZERO XLA compiles (the perf gate's hard assertion) — and since a warm
# leg never traces, the wire-byte accounting is persisted as the cache
# entry's aux payload and replayed on hits.


def wire_stats_aux(ws):
    """JSON-serializable snapshot of a traced program's WireStats."""
    return {k: v for k, v in vars(ws).items()
            if isinstance(v, (int, float))}


def restore_wire_stats(aux):
    from horovod_tpu.plan.accounting import WireStats

    ws = WireStats()
    for k, v in (aux or {}).items():
        if hasattr(ws, k):
            setattr(ws, k, v)
    return ws


def compile_snapshot():
    """Executable-cache counters at leg start (compile_fields deltas)."""
    from horovod_tpu import compile as xc

    return dict(xc.stats())


def compile_fields(snap0, ttfs_ms=None):
    """The compile-cost block of one measured leg's JSON: executable-
    cache hit/miss deltas across the leg (``compile_count`` counts true
    XLA compiles — a warm rerun must report 0), total compile wall time,
    and time from leg start to the first step's results being ready."""
    from horovod_tpu import compile as xc

    s = xc.stats()
    misses = int(s["misses"] - snap0["misses"])
    return {
        "time_to_first_step_ms": (round(ttfs_ms, 3)
                                  if ttfs_ms is not None else None),
        "compile_count": misses,
        "compile_ms_total": round(s["compile_ms"] - snap0["compile_ms"], 3),
        "compile_cache": {"hits": int(s["hits"] - snap0["hits"]),
                          "misses": misses},
    }


def cached_lower_compile(tag, jitted, lower_args, *, mesh=None,
                         plan=None, extra=None):
    """Lower+compile one leg's step through the executable cache.

    Cold: traces under ``record_wire_stats`` and stores the byte
    accounting as the entry's aux. Warm (memory or a prior process's
    disk entry): no lowering happens at all, so the traced wire profile
    is replayed from the aux recorded at cold-compile time and
    re-published to the registry. Returns
    ``(compiled, wire_stats, CompileResult)``."""
    from horovod_tpu import compile as xc
    from horovod_tpu.plan import accounting as _acct

    box = {}

    def _lower():
        with _acct.record_wire_stats() as w:
            lowered = jitted.lower(*lower_args)
        box["wire"] = wire_stats_aux(w)
        return lowered

    res = xc.get_or_compile(tag, _lower, plan=plan, mesh=mesh,
                            shapes=lower_args, extra=extra,
                            aux_fn=lambda lowered: box.get("wire") or {})
    wire = restore_wire_stats(box.get("wire") or res.aux)
    if res.cache_hit:
        _acct._publish_wire_stats(wire)
    return res.compiled, wire, res


# Peak dense bf16 FLOP/s per chip, keyed by substrings of
# jax.Device.device_kind (public TPU spec sheet numbers).
_PEAK_BF16_TFLOPS = [
    ("v6e", 918.0), ("v6 lite", 918.0),
    ("v5p", 459.0),
    ("v5e", 197.0), ("v5 lite", 197.0), ("v5litepod", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]


def peak_flops_per_chip(device) -> float:
    """Peak bf16 FLOP/s for this chip; a chip that is not in the table
    is an error, not a default (the CPU mesh reports no MFU at all)."""
    if device.platform == "cpu":
        return 0.0
    kind = device.device_kind.lower()
    for marker, tflops in _PEAK_BF16_TFLOPS:
        if marker in kind:
            return tflops * 1e12
    raise SystemExit(f"no peak FLOP/s on record for device kind "
                     f"{device.device_kind!r}: add it to "
                     f"_PEAK_BF16_TFLOPS with its source")


def step_flops_per_chip(compiled, global_items, n_chips,
                        analytic_flops_per_item) -> float:
    """Per-chip FLOPs of one compiled train step. XLA's cost_analysis on an
    SPMD executable reports the per-device partitioned module, so it is
    already per-chip; the analytic per-item fallback (model-specific:
    fwd+bwd FLOPs per image/token) is global and gets divided down."""
    try:
        ca = compiled.cost_analysis()
        flops = float(ca.get("flops", 0.0))
        if flops > 0:
            return flops
    except Exception as e:
        log(f"cost_analysis unavailable ({e}); using analytic FLOPs")
    return analytic_flops_per_item * global_items / n_chips


def init_backend():
    """The accelerator, or no run: ``--platform auto`` never measures on
    the CPU. One process per chip — no probing child, no retry."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform == "cpu":
        raise SystemExit(
            "bench.py: JAX found no accelerator (platform 'cpu'). "
            "--platform auto runs on the chip or not at all; pass "
            "--platform cpu for the virtual CPU mesh.")
    return devices, platform


def force_cpu_backend(n_devices: int):
    """Deterministic CPU bring-up for smoke tests: n virtual CPU devices,
    never touching (or waiting on) an accelerator backend."""
    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", n_devices)
    devices = jax.devices()
    if len(devices) < n_devices:
        raise SystemExit(
            f"--platform cpu asked for {n_devices} devices, got "
            f"{len(devices)}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_devices}")
    return devices, "cpu"


BASELINE_IMG_PER_SEC_PER_DEVICE = 1656.82 / 16  # docs/benchmarks.rst:27-43
BASELINE_SCALING_EFFICIENCY = 0.90  # docs/benchmarks.rst:13-14 (512 GPUs)


def summarize_profile(log_dir: str, top: int = 15) -> None:
    """Parse the perfetto trace the profiler dropped under ``log_dir`` and
    print where the step time goes: per-HLO-category busy time + bytes
    accessed (roofline evidence), then the top individual ops."""
    import collections
    import glob
    import gzip

    traces = sorted(glob.glob(
        os.path.join(log_dir, "plugins", "profile", "*", "*.trace.json.gz")))
    if not traces:
        log(f"no trace found under {log_dir}")
        return
    with gzip.open(traces[-1], "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    device_pids = {e["pid"] for e in events if e.get("ph") == "M"
                   and e.get("name") == "process_name" and "args" in e
                   and "/device:" in e["args"].get("name", "")}
    # Each device pid carries several mirrored lanes (steps / modules /
    # XLA ops); the op lane is the one whose events have an hlo_category.
    by_op = collections.Counter()
    by_cat_us = collections.Counter()
    by_cat_bytes = collections.Counter()
    total = 0.0
    for e in events:
        if (e.get("ph") != "X" or "dur" not in e
                or e.get("pid") not in device_pids):
            continue
        cat = e.get("args", {}).get("hlo_category")
        if not cat:
            continue
        by_op[e.get("name", "?")] += e["dur"]
        by_cat_us[cat] += e["dur"]
        by_cat_bytes[cat] += int(e["args"].get("bytes_accessed", 0))
        total += e["dur"]
    log(f"-- profile ({traces[-1].split('/')[-1]}): device busy "
        f"{total / 1e3:.2f} ms, bytes accessed "
        f"{sum(by_cat_bytes.values()) / 1e9:.1f} GB, effective "
        f"{sum(by_cat_bytes.values()) / 1e3 / max(total, 1):.0f} GB/s --")
    for cat, us in by_cat_us.most_common():
        log(f"  {us / 1e3:9.2f} ms  {100 * us / max(total, 1):5.1f}%  "
            f"{by_cat_bytes[cat] / 1e9:6.2f} GB  {cat}")
    log(f"-- top {top} ops --")
    for name, us in by_op.most_common(top):
        log(f"  {us / 1e3:9.2f} ms  {100 * us / max(total, 1):5.1f}%  {name}")


def build_workload(args, global_batch):
    """Model, synthetic data, and loss for one measurement leg — shared
    between :func:`run_once` and the ``--autotune`` tuning session (every
    autotune trial recompiles the SAME workload, so tuned params transfer
    to the measured legs by construction). Returns a dict with ``params``,
    ``batch_stats``, ``images``, ``labels``, ``loss_fn`` and, for GPT,
    the model ``gpt_cfg`` (analytic-FLOPs inputs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    rng = jax.random.PRNGKey(0)
    gpt_cfg = None
    if args.model == "gpt":
        from horovod_tpu.models import GPT, GPTConfig

        shape = (dict(num_layers=12, num_heads=12, d_model=768, d_ff=3072)
                 if args.gpt_scale == "124m" else
                 dict(num_layers=24, num_heads=16, d_model=1024, d_ff=4096))
        cfg = GPTConfig(vocab_size=args.vocab_size, max_seq_len=args.seq_len,
                        attention=args.attention, fused_ln=args.fused_ln,
                        remat=args.remat, **shape)
        model = GPT(cfg)
        variables = model.init(rng, jnp.zeros((1, args.seq_len), jnp.int32))
        params, batch_stats = variables["params"], {}
        images = jnp.asarray(np.random.randint(
            0, cfg.vocab_size, (global_batch, args.seq_len)))
        labels = jnp.asarray(np.random.randint(
            0, cfg.vocab_size, (global_batch, args.seq_len)))

        if args.lm_loss in ("fused", "auto"):
            import dataclasses

            from horovod_tpu.ops.softmax_xent import lm_head_loss

            hidden_model = GPT(dataclasses.replace(cfg, return_hidden=True))
            head_mode = args.lm_loss

            def loss_fn(p, bs, xb, yb):
                h = hidden_model.apply({"params": p}, xb)
                loss = lm_head_loss(h, p["wte"].astype(cfg.dtype), yb,
                                    mode=head_mode).mean()
                return loss, bs
        else:
            def loss_fn(p, bs, xb, yb):
                logits = model.apply({"params": p}, xb)
                loss = optax.softmax_cross_entropy_with_integer_labels(
                    logits, yb).mean()
                return loss, bs
    else:
        from horovod_tpu.models import ResNet18, ResNet50

        resnet_cls = ResNet50 if args.model == "resnet50" else ResNet18
        kw = ({"space_to_depth": args.space_to_depth}
              if args.model == "resnet50" else {})
        side = args.image_size
        model = resnet_cls(num_classes=1000, dtype=jnp.bfloat16, **kw)
        variables = model.init(
            rng, jnp.zeros((1, side, side, 3), jnp.bfloat16), train=False)
        params, batch_stats = variables["params"], variables["batch_stats"]
        images = jnp.asarray(np.random.randn(global_batch, side, side, 3),
                             jnp.bfloat16)
        labels = jnp.asarray(np.random.randint(0, 1000, global_batch))

        def loss_fn(p, bs, xb, yb):
            logits, new_vars = model.apply(
                {"params": p, "batch_stats": bs}, xb, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, yb).mean()
            return loss, new_vars["batch_stats"]

    if args.model == "gpt":
        gpt_cfg = cfg
    return {"params": params, "batch_stats": batch_stats,
            "images": images, "labels": labels, "loss_fn": loss_fn,
            "gpt_cfg": gpt_cfg}


def run_once(args, devices, platform, *, quantized=False, zero=False,
             overlap=False, mesh_shape=None, tuned_params=None,
             zero_stage=None, ckpt_probe=False):
    """One full measurement on ``devices``: init the world, build the
    model + DistributedOptimizer step, compile, warm up, time, and return
    the result row (no JSON printing — the caller owns the one-line
    contract). Calls ``hvd.shutdown()`` first so scaling sweeps can re-init
    over growing device subsets.

    ``quantized`` selects the int8 DCN wire with error feedback in the
    DistributedOptimizer; ``zero`` the ZeRO-1 sharded optimizer update
    (reduce-scatter grads → per-rank optax update on 1/world shards →
    all-gather, docs/zero.md); ``zero_stage`` (1/2/3) the explicit ZeRO
    stage — stage 3 restructures the loop: the params live as flat
    bucket shards and the forward runs on ``hvd.zero3_gather_params``
    output; ``mesh_shape=(cross, local)`` emulates a multi-host topology
    (a real DCN hop) on a single host. Under ``--quantized``/``--zero``/
    ``--zero-stage`` both A/B legs run the reduce-in-optimizer step
    structure so the comparison is like-for-like. ``tuned_params`` (the
    frozen winner of an autotune session) overrides the collective
    tunables for this leg — the ``--autotune`` A/B measures its value.
    ``ckpt_probe`` saves an async rank-sharded checkpoint twice during
    the timed window (docs/checkpoint.md) and reports the save stall."""
    import jax
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.shutdown()  # no-op unless a previous sweep world is up
    hvd.init(devices=devices, mesh_shape=mesh_shape)
    n_chips = hvd.size()
    global_batch = args.batch_size * n_chips
    log(f"world={n_chips} global_batch={global_batch} platform={platform}")

    stage = int(zero_stage) if zero_stage else (2 if zero else 0)
    zero = stage in (1, 2)
    zero3 = stage == 3

    wl = build_workload(args, global_batch)
    params, batch_stats = wl["params"], wl["batch_stats"]
    images, labels = wl["images"], wl["labels"]
    loss_fn, cfg = wl["loss_fn"], wl["gpt_cfg"]

    compression = (hvd.Compression.bf16 if args.fp16_allreduce
                   else hvd.Compression.none)
    tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                  compression=compression,
                                  quantized=quantized,
                                  zero=None if stage else False,
                                  zero_stage=stage if stage else None,
                                  overlap=overlap,
                                  tuned_params=tuned_params)
    opt_state = tx.init(params)

    mesh = hvd.mesh()
    rep = NamedSharding(mesh, P())
    data_sh = hvd.data_sharding()

    # Pin shardings up front so step 2 doesn't recompile on resharded args.
    params = jax.device_put(params, rep)
    batch_stats = jax.device_put(batch_stats, rep)
    pshards = pshard_spec = params_tpl = None
    if zero3:
        # Stage 3: the loop owns 1/world flat bucket shards; the full
        # params exist only transiently inside the step (per-bucket JIT
        # gather, docs/zero.md).
        params_tpl = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
        pshards = hvd.zero3_shard_params(jax.device_get(params))
        pshard_spec = hvd.zero3_param_pspecs(pshards)
        pshards = jax.device_put(
            pshards,
            jax.tree.map(lambda s: NamedSharding(mesh, s), pshard_spec))
    if zero or zero3:
        # ZeRO state: flat bucket moments (and EF residuals) shard
        # rank-major over the mesh; scalars replicate
        # (hvd.zero_state_pspecs docstring).
        state_spec = hvd.zero_state_pspecs(opt_state)
        opt_state = jax.device_put(
            opt_state,
            jax.tree.map(lambda s: NamedSharding(mesh, s), state_spec))
    elif quantized:
        # Error-feedback residuals are per-rank state: leaves carry a
        # leading world axis sharded over the mesh; the inner optimizer
        # state stays replicated (hvd.QuantizedEFState docstring).
        opt_state = hvd.QuantizedEFState(
            inner=jax.device_put(opt_state.inner, rep),
            residual=jax.device_put(opt_state.residual, data_sh))
        state_spec = hvd.QuantizedEFState(P(), hvd.data_pspec())
    else:
        opt_state = jax.device_put(opt_state, rep)
        state_spec = P()
    # Optimizer-state bytes this rank actually holds: on the ZeRO legs
    # every non-scalar leaf shards 1/world over the mesh (the
    # zero_state_pspecs contract), so per-rank bytes shrink world× — the
    # memory metric the A/B reports.
    if zero or zero3:
        opt_state_bytes_per_rank = float(sum(
            (l.nbytes / n_chips if getattr(l, "ndim", 0) >= 1 else l.nbytes)
            for l in jax.tree.leaves(opt_state)))
    else:
        opt_state_bytes_per_rank = float(sum(
            getattr(l, "nbytes", 0) for l in jax.tree.leaves(opt_state)))
    # Parameter bytes: replicated params cost their full size on every
    # rank; stage-3 shards cost 1/world persistent (+ the per-bucket
    # transient the JIT gather materializes during the step, reported
    # separately — docs/zero.md memory math).
    model_bytes = float(sum(
        getattr(l, "nbytes", 0) for l in jax.tree.leaves(params)))
    if zero3:
        param_bytes_per_rank = float(sum(
            s.nbytes for s in jax.tree.leaves(pshards))) / n_chips
        param_bytes_transient = model_bytes
    else:
        param_bytes_per_rank = model_bytes
        param_bytes_transient = 0.0
    # Persistent gradient-accumulation state (backward_passes_per_step >
    # 1 only; stage 1 keeps the full classic accumulator, stage 2/3 the
    # 1/world shard — zero for k == 1, where gradients are transients).
    grad_accum_bytes_per_rank = 0.0
    if (zero or zero3) and isinstance(opt_state, hvd.ZeroState):
        inner = opt_state.inner
        if isinstance(inner, hvd.ZeroFullMultiStepsState):
            grad_accum_bytes_per_rank = float(sum(
                l.nbytes / n_chips for l in jax.tree.leaves(inner.acc)))
        elif hasattr(inner, "acc_grads"):
            grad_accum_bytes_per_rank = float(sum(
                l.nbytes / n_chips
                for l in jax.tree.leaves(inner.acc_grads)))
    bytes_per_rank_total = (opt_state_bytes_per_rank + param_bytes_per_rank
                            + grad_accum_bytes_per_rank)
    log(f"bytes/rank: params {param_bytes_per_rank / 1e6:.3f} MB"
        + (f" (+{param_bytes_transient / 1e6:.3f} MB gather transient)"
           if zero3 else "")
        + f", opt state {opt_state_bytes_per_rank / 1e6:.3f} MB, "
        f"grad accum {grad_accum_bytes_per_rank / 1e6:.3f} MB"
        + (f" (ZeRO stage {stage})" if stage else " (replicated)"))
    images = jax.device_put(images, data_sh)
    labels = jax.device_put(labels, data_sh)

    # The optimizer owns the gradient reduction on every leg:
    # reduce=False keeps the raw gradients per-rank locals so the fused
    # (and, on the quantized leg, int8+error-feedback; on the zero leg,
    # reduce-scattered) bucket wire inside tx.update is the one and only
    # gradient collective — the wire the autotuner's fusion/hierarchical
    # knobs actually steer. A tape that reduced too would hand tx.update
    # replicated AVERAGES, which it reads as autodiff's cross-rank SUM
    # and divides by the world size again (found by chip_smoke.py's
    # four-device rehearsal, PR 21; invisible on one chip).
    def spmd(p, bs, s, xb, yb):
        if zero3:
            # p is the shard tuple; the full params exist only between
            # here and the end of the backward (per-bucket JIT gather,
            # forward order, overlapping deeper buckets under compute).
            pfull = hvd.zero3_gather_params(p, params_tpl, overlap=overlap)
        else:
            pfull = p
        (loss, nbs), grads = hvd.value_and_grad(
            loss_fn, has_aux=True, reduce=False)(pfull, bs, xb, yb)
        nbs = hvd.allreduce_pytree(nbs, op=hvd.Average)
        updates, ns = tx.update(grads, s, p)
        return optax.apply_updates(p, updates), nbs, ns, hvd.allreduce(loss)

    if args.steps_per_call > 1:
        # Host-loop offload: K steps per device call via lax.scan, the
        # standard TPU recipe for hiding per-dispatch latency (the synthetic
        # batch is reused, exactly as the reference harness reuses its fixed
        # batch across timing steps).
        import jax.lax as lax

        def spmd_k(p, bs, s, xb, yb):
            def body(carry, _):
                p, bs, s = carry
                p, bs, s, loss = spmd(p, bs, s, xb, yb)
                return (p, bs, s), loss

            (p, bs, s), losses = lax.scan(
                body, (p, bs, s), None, length=args.steps_per_call)
            return p, bs, s, losses[-1]

        step_body = spmd_k
    else:
        step_body = spmd

    # Donate params/batch_stats/opt_state: the step overwrites them, so XLA
    # can update in place instead of allocating fresh HBM buffers — on a
    # bandwidth-bound chip the avoided copy is measurable.
    param_spec = pshard_spec if zero3 else P()
    param_arg = pshards if zero3 else params
    train_step = jax.jit(hvd.shard_map(
        step_body, mesh=mesh,
        in_specs=(param_spec, P(), state_spec, hvd.data_pspec(),
                  hvd.data_pspec()),
        out_specs=(param_spec, P(), state_spec, P())),
        donate_argnums=(0, 1, 2))

    compile_snap0 = compile_snapshot()
    t_leg0 = time.perf_counter()
    knobs = (f"{args.model}|q{int(quantized)}|z{stage}|ov{int(overlap)}"
             f"|spc{args.steps_per_call}")
    compiled, wire, cres = cached_lower_compile(
        "bench.train_step", train_step,
        (param_arg, batch_stats, opt_state, images, labels),
        mesh=mesh, plan=knobs)
    log(f"compile: {time.perf_counter() - t_leg0:.1f}s"
        + (f" ({cres.source} hit, saved ~{cres.compile_ms:.0f}ms)"
           if cres.cache_hit else ""))
    log(f"wire bytes/step/device: ICI {wire.ici_bytes / 1e6:.2f} MB, "
        f"DCN {wire.dcn_bytes / 1e6:.3f} MB"
        + (f" (fp-equiv {wire.dcn_bytes_fp / 1e6:.3f} MB, "
           f"{wire.dcn_reduction:.2f}x reduction)"
           if wire.dcn_reduction else ""))

    # Cost-model drift pair (docs/cost-model.md): the analytic planner's
    # predicted wire time for this leg's knob set vs what the traced
    # program's accounting actually charged at the modeled bandwidths —
    # scripts/perf_gate.sh's cost leg checks |predicted - measured|.
    from horovod_tpu import plan as hvd_plan
    from horovod_tpu.plan.accounting import modeled_wire_ms

    wire_ms_modeled = modeled_wire_ms(wire.ici_bytes, wire.dcn_bytes,
                                      wire.pod_bytes)
    cost_fields = {"wire_ms_modeled": wire_ms_modeled,
                   "wire_ms_predicted": None,
                   "wire_ms_predicted_total": None,
                   "cost_model": None}
    try:
        payload_elems = sum(int(np.prod(l.shape))
                            for l in jax.tree.leaves(params))
        cost_model = hvd_plan.get_cost_model()
        step_plan = hvd_plan.describe_plan(
            quantized=quantized, zero_stage=stage, overlap=overlap,
            tuned_params=tuned_params)
        step_cost = hvd_plan.price_step(
            step_plan, model_bytes,
            itemsize=model_bytes / max(1, payload_elems),
            model=cost_model)
        cost_fields.update(
            wire_ms_predicted=step_cost.wire_ms,
            wire_ms_predicted_total=step_cost.predicted_ms,
            cost_model=step_cost.source)
        log(f"wire ms/step/device: predicted {step_cost.wire_ms:.4f} "
            f"(total {step_cost.predicted_ms:.4f} with latency+quant"
            f"{'-overlap' if step_plan.overlap else ''}) vs modeled "
            f"{wire_ms_modeled:.4f} [{step_cost.source} model]")
    except Exception as e:  # pricing must never fail a measurement
        log(f"cost-model prediction unavailable for this leg: {e}")
    # Model FLOPs for MFU. ResNets: XLA cost analysis on the compiled
    # step (analytic fallback ~4.09 GFLOP fwd/image x 3 for fwd+bwd). GPT:
    # ALWAYS the standard analytic count — 6*N matmul FLOPs/token plus the
    # causal attention term 6*L*T*d (the causal-halved convention, as in
    # FlashAttention/Chinchilla accounting; PaLM Appendix B's unhalved
    # form would be 12*L*T*d) — because XLA's cost analysis cannot see
    # inside the Pallas flash-attention custom call and would under-credit
    # the flash path for the very FLOPs it executes (MFU is defined on
    # model FLOPs, not implementation ops).
    if args.model == "gpt":
        n_params = sum(int(np.prod(x.shape))
                       for x in jax.tree.leaves(params))
        analytic_per_item = (6.0 * n_params
                             + 6.0 * cfg.num_layers * args.seq_len
                             * cfg.d_model)
        items_per_step = global_batch * args.seq_len
        flops = analytic_per_item * items_per_step / n_chips
    else:
        # fwd-pass GFLOP/image at 224x224, x3 for fwd+bwd, scaled by the
        # conv-dominated quadratic dependence on image side.
        base = 4.089e9 if args.model == "resnet50" else 1.82e9
        analytic_per_item = 3.0 * base * (args.image_size / 224.0) ** 2
        items_per_step = global_batch
        flops = step_flops_per_chip(
            compiled, items_per_step * args.steps_per_call,
            n_chips, analytic_per_item) / args.steps_per_call
    item_unit = "tok" if args.model == "gpt" else "img"
    # Drive the AOT executable directly so the jit dispatch path doesn't
    # trigger a second identical XLA compile.
    train_step = compiled

    t0 = time.perf_counter()
    pstate = param_arg
    ttfs_ms = None
    for wi in range(args.num_warmup):
        pstate, batch_stats, opt_state, loss = train_step(
            pstate, batch_stats, opt_state, images, labels)
        if wi == 0:
            # Time-to-first-step: leg start (pre-lower) → the first
            # step's results ready — the latency the compile cache is
            # in the business of cutting (docs/compile.md).
            jax.block_until_ready((pstate, batch_stats, opt_state, loss))
            ttfs_ms = (time.perf_counter() - t_leg0) * 1e3
    # Block on EVERY output, not just the loss: the loss allreduce completes
    # early in the step, so blocking on it alone under-times the tail of the
    # parameter update and flattered iter 0 in round 2's numbers.
    jax.block_until_ready((pstate, batch_stats, opt_state, loss))
    log(f"warmup ({args.num_warmup} steps): "
        f"{time.perf_counter() - t0:.1f}s  loss={float(loss):.3f}"
        f"  first step ready {0.0 if ttfs_ms is None else ttfs_ms:.0f}ms "
        f"after leg start")

    # Async checkpoint probe: save the sharded training state mid-window
    # (each rank's 1/world shards, background write) and measure the
    # trainer-visible stall — the docs/checkpoint.md A/B contract is
    # stall ≤ 10% of the step budget it interrupts.
    ckpt_mgr = ckpt_dir = None
    ckpt_stalls = []
    if ckpt_probe:
        import tempfile

        from horovod_tpu import checkpoint as hvd_ckpt

        ckpt_dir = os.environ.get("HOROVOD_BENCH_CKPT_DIR") or \
            tempfile.mkdtemp(prefix="bench_ckpt_")
        ckpt_mgr = hvd_ckpt.CheckpointManager(ckpt_dir, keep=2)
        from horovod_tpu import monitor as _monitor

        ckpt_commits0 = _monitor.metrics().counter("ckpt.commits").value

    def _ckpt_save(step_no):
        t = time.perf_counter()
        ckpt_mgr.save(step_no, {"params": pstate, "opt_state": opt_state},
                      mesh_shape=mesh_shape)
        ckpt_stalls.append((time.perf_counter() - t) * 1e3)

    profile_iter = min(1, args.num_iters - 1) if args.profile else None
    save_iters = ({max(0, args.num_iters // 3),
                   max(0, 2 * args.num_iters // 3)} if ckpt_probe else set())
    img_secs = []
    step_times = []
    for i in range(args.num_iters):
        if i == profile_iter:
            jax.profiler.start_trace(args.profile)
        if i in save_iters:
            _ckpt_save(i)
        t0 = time.perf_counter()
        for _ in range(args.num_batches_per_iter):
            pstate, batch_stats, opt_state, loss = train_step(
                pstate, batch_stats, opt_state, images, labels)
        jax.block_until_ready((pstate, batch_stats, opt_state, loss))
        dt = time.perf_counter() - t0
        steps = args.num_batches_per_iter * args.steps_per_call
        rate = items_per_step * steps / dt
        if i == profile_iter:
            jax.profiler.stop_trace()
            # Tracing inflates the iter; keep it out of the reported stats.
            log(f"iter {i}: {rate:.1f} {item_unit}/s total "
                f"(profiled; excluded)")
            continue
        step_times.append(dt / steps)
        img_secs.append(rate)
        log(f"iter {i}: {rate:.1f} {item_unit}/s total")

    if args.profile:
        try:
            summarize_profile(args.profile)
        except Exception as e:  # profile is diagnostics, never fail the run
            log(f"profile summary failed: {e}")

    # Report from the MEDIAN step: robust to the occasional slow host-side
    # hiccup and immune to a single anomalously fast iteration (round-2
    # methodology flaw: MFU from min(step_times)).
    median_step = float(np.median(step_times))
    per_chip = items_per_step / median_step / n_chips
    unit = "tokens/sec/chip" if args.model == "gpt" else "images/sec/chip"
    peak = peak_flops_per_chip(devices[0])
    mfu = (flops / median_step / peak) if peak > 0 else None
    log(f"Median {unit.split('/')[0]}/sec on {n_chips} chip(s): "
        f"{items_per_step / median_step:.1f} "
        f"(mean {float(np.mean(img_secs)):.1f} "
        f"± {float(np.std(img_secs)):.1f});  per chip: {per_chip:.1f}")
    if mfu is not None:
        log(f"MFU: {mfu:.3f} (step {flops / 1e9:.1f} GFLOP/chip, median step "
            f"{median_step * 1e3:.2f} ms, min {min(step_times) * 1e3:.2f} ms, "
            f"peak {peak / 1e12:.0f} TFLOP/s/chip)")

    # Unified observability: the measured step times feed the registry's
    # log2 latency histogram, and the leg's result row carries a metrics
    # snapshot (wire bytes per hop from the traced program, per-bucket
    # histograms, hidden fraction) for the JSON artifact.
    from horovod_tpu import monitor

    step_hist = monitor.metrics().histogram("step.time_ms")
    for st in step_times:
        step_hist.observe(st * 1e3)

    # Straggler attribution (monitor/straggler.py): every timed step
    # records its phase breakdown — per-hop wire at the modeled
    # bandwidths (the only per-step wire time that exists on the
    # compiled path), the checkpoint stall when the probe ran, and the
    # compute remainder — then one detection pass closes the loop (a
    # clean run must flag nothing; zero false positives is the
    # acceptance contract). Link health scores each hop's wire against
    # the resolved cost model's prediction for this rank's traffic.
    from horovod_tpu.plan.accounting import bench_gbps

    det = monitor.straggler_detector()
    _gbps = dict(zip(("ici", "dcn", "pod"), bench_gbps()))
    hop_ms = {hop: getattr(wire, f"{hop}_bytes")
              / (_gbps[hop] * 1e9) * 1e3 for hop in ("ici", "dcn", "pod")}
    ckpt_ms_per_save = (float(np.median(ckpt_stalls))
                        if ckpt_stalls else 0.0)
    for i, st in enumerate(step_times):
        step_ms = st * 1e3
        for hop, ms in hop_ms.items():
            if ms > 0:
                det.record_phase(f"wire.{hop}", min(ms, step_ms))
        if ckpt_probe and i == 0 and ckpt_ms_per_save > 0:
            det.record_phase("ckpt", ckpt_ms_per_save)
        det.record_phase(
            "compute", max(0.0, step_ms - sum(hop_ms.values())))
        det.end_step(i)
    for hop, ms in hop_ms.items():
        nbytes = getattr(wire, f"{hop}_bytes")
        if nbytes > 0:
            det.observe_wire(hop, nbytes, ms)
    stragglers = det.detect()
    if stragglers:
        log(f"stragglers detected: {stragglers}")

    ckpt_fields = {}
    if ckpt_probe and ckpt_mgr is not None:
        ok = ckpt_mgr.wait(120)
        commits = (monitor.metrics().counter("ckpt.commits").value
                   - ckpt_commits0)
        stall_ms = float(np.median(ckpt_stalls)) if ckpt_stalls else 0.0
        median_ms = float(np.median(step_times)) * 1e3
        ckpt_fields = {
            "ckpt_commits": int(commits),
            "ckpt_save_stall_ms": round(stall_ms, 3),
            "ckpt_stall_frac": round(stall_ms / max(1e-9, median_ms), 4),
            "ckpt_dir": ckpt_dir,
            "ckpt_drained": bool(ok),
        }
        log(f"ckpt probe: {len(ckpt_stalls)} async saves, stall "
            f"{stall_ms:.2f} ms vs step {median_ms:.2f} ms "
            f"({100 * stall_ms / max(1e-9, median_ms):.1f}% of a step), "
            f"{int(commits)} commits in {ckpt_dir}")
        ckpt_mgr.close()

    return {
        "param_bytes_per_rank": param_bytes_per_rank,
        "param_bytes_transient": param_bytes_transient,
        "grad_accum_bytes_per_rank": grad_accum_bytes_per_rank,
        "bytes_per_rank_total": bytes_per_rank_total,
        **ckpt_fields,
        "per_chip": per_chip,
        "unit": unit,
        "mfu": mfu,
        "step_ms_median": median_step * 1e3,
        "step_ms_min": min(step_times) * 1e3,
        "chips": n_chips,
        "global_batch": global_batch,
        "wire_bytes_ici": wire.ici_bytes,
        "wire_bytes_dcn": wire.dcn_bytes,
        "wire_bytes_dcn_fp": wire.dcn_bytes_fp,
        "wire_bytes_pod": wire.pod_bytes,
        "wire_reduction_dcn": wire.dcn_reduction,
        "wire_bytes_overlap": wire.overlap_bytes,
        "comm_hidden_fraction": wire.hidden_fraction,
        "opt_state_bytes_per_rank": opt_state_bytes_per_rank,
        **cost_fields,
        **compile_fields(compile_snap0, ttfs_ms),
        "metrics": metrics_snapshot(),
    }


def leg_compile_fields(res):
    """Lift the measured leg's compile-once fields (docs/compile.md) out
    of a run_once result into the top-level JSON line — every leg
    reports TTFS and how many executables it actually compiled vs
    pulled from the cache."""
    return {k: res.get(k) for k in (
        "time_to_first_step_ms", "compile_count", "compile_ms_total",
        "compile_cache")}


def wire_ms_fields(res):
    """The ``wire_ms`` JSON block of one measured leg: the cost-model
    prediction vs the trace-accounted bytes at modeled bandwidths —
    the drift pair scripts/perf_gate.sh's cost leg checks
    (docs/cost-model.md)."""
    rnd = lambda v: round(v, 4) if v is not None else None  # noqa: E731
    return {"wire_ms": {
        "predicted": rnd(res.get("wire_ms_predicted")),
        "predicted_total": rnd(res.get("wire_ms_predicted_total")),
        "modeled": rnd(res.get("wire_ms_modeled")),
        "model": res.get("cost_model"),
    }}


def run_stage_parity_probe(devices, mesh_shape, steps=3):
    """Stage 1/2/3 parity on a tiny model: all three updates run
    side-by-side in ONE compiled step (the repo's established bitwise
    methodology, tests/test_zero.py::test_sgd_update_bit_identical...),
    sharing a single gradient computation, over ``steps`` training
    steps. Returns the probe dict for the JSON line; raises on parity
    loss. Stage 1 vs 2 must be BIT-identical across the whole
    trajectory; stage 3 is bit-identical per update (same gshards, same
    shard updates) and tracked at ≤1e-5 over the trajectory — across
    structurally different apply paths XLA's fusion choices (FMA
    formation) round the final ulp differently, which is compiler noise,
    not decomposition error (docs/zero.md)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init(devices=devices, mesh_shape=mesh_shape)
    mesh = hvd.mesh()
    world = hvd.size()

    params0 = {"w": jnp.zeros((37, 4)), "b": jnp.zeros((4,))}
    tpl = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params0)
    rng = np.random.RandomState(0)
    x = rng.randn(world * 4 * steps, 37).astype(np.float32)
    y = (x[:, :4] * 0.3 + 0.1).astype(np.float32)

    def loss_fn(p, batch):
        xb, yb = batch
        return jnp.mean((xb @ p["w"] + p["b"] - yb) ** 2)

    txs = [hvd.DistributedOptimizer(optax.sgd(0.1, momentum=0.9),
                                    zero_stage=s) for s in (1, 2, 3)]
    states = [tx.init(params0) for tx in txs]
    sspecs = [hvd.zero_state_pspecs(s) for s in states]
    put = lambda t, sp: jax.device_put(  # noqa: E731
        t, jax.tree.map(lambda q: NamedSharding(mesh, q), sp))
    states = [put(s, sp) for s, sp in zip(states, sspecs)]
    psh = hvd.zero3_shard_params(params0)
    pspec = hvd.zero3_param_pspecs(psh)
    psh = put(psh, pspec)

    @jax.jit
    def step(p, psh, s1, s2, s3, xb, yb):
        def spmd(p, psh, s1, s2, s3, xb, yb):
            pg = hvd.zero3_gather_params(psh, tpl)
            _, g = hvd.value_and_grad(loss_fn, zero=True)(pg, (xb, yb))
            u1, ns1 = txs[0].update(g, s1, p)
            u2, ns2 = txs[1].update(g, s2, p)
            u3, ns3 = txs[2].update(g, s3, psh)
            return (optax.apply_updates(p, u1), optax.apply_updates(p, u2),
                    optax.apply_updates(psh, u3), ns1, ns2, ns3)

        return hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), pspec, *sspecs, hvd.data_pspec(),
                      hvd.data_pspec()),
            out_specs=(P(), P(), pspec, *sspecs))(
            p, psh, s1, s2, s3, xb, yb)

    p = params0
    bs = world * 4
    max_rel3 = 0.0
    for i in range(steps):
        xb = jnp.asarray(x[i * bs:(i + 1) * bs])
        yb = jnp.asarray(y[i * bs:(i + 1) * bs])
        p1, p2, psh, *states = step(p, psh, *states, xb, yb)
        p3 = hvd.zero3_gather_params(jax.device_get(psh), params0)
        for k in p1:
            a1, a2 = np.asarray(p1[k]), np.asarray(p2[k])
            if not np.array_equal(a1, a2):
                raise AssertionError(
                    f"stage 1 vs 2 diverged at step {i} on {k!r}")
            a3 = np.asarray(p3[k])
            denom = np.maximum(np.abs(a1), 1e-12)
            max_rel3 = max(max_rel3,
                           float(np.max(np.abs(a1 - a3) / denom)))
            np.testing.assert_allclose(a1, a3, rtol=1e-5, atol=1e-7)
        p = p1
    log(f"stage parity probe: stage1==stage2 bit-identical over {steps} "
        f"steps; stage3 max rel err {max_rel3:.2e} (<=1e-5)")
    return {"steps": steps, "stage12_bit_identical": True,
            "stage3_max_rel_err": max_rel3}


def run_fused(args, devices, platform, mesh_shape):
    """The ``--fused`` leg: fused compute-collective Pallas kernels A/B
    (docs/fused-kernels.md).

    A synthetic fusion-pair workload — an L-layer linear chain whose
    weights live in the ZeRO-3 rank-major shard layout
    (``--zero-stage 3``, the default here) — runs twice with identical
    math:

    * **unfused**: plan-compiled wire (``hvd.all_gather`` each layer's
      weight, matmul, then ``hvd.reduce_scatter`` the full weight-grad
      product; ``--quantized`` puts int8 on the grad wire's DCN leg,
      ``--overlap`` issues through the stream entry points);
    * **fused**: the same pairs through
      :func:`hvd.fused_all_gather_matmul` (ring-gathered shards feed
      the matmul prologue) and :func:`hvd.fused_matmul_reduce_scatter`
      (each output tile accumulates into the traveling partial sum) —
      or, on the quantized grad wire, the plan-compiled legs with the
      Pallas quantize/dequant kernels (``fused=True``).

    Reports measured steps/sec for both legs plus the MODELED step-time
    saving from the avoided HBM round-trip (trace-time
    ``fused_hbm_saved_bytes`` at ``HOROVOD_BENCH_HBM_GBPS``, default
    819 GB/s — v5e spec) — on the emulated CPU mesh the interpreter-mode
    kernels measure nothing real, so the HBM-traffic reduction is the
    asserted contract there; on a TPU the measured delta is the
    headline. A parity probe (fused vs unfused, one step, identical
    inputs) hard-fails on divergence beyond float/ulp tolerance."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu.ops.collective_ops import record_wire_stats

    hvd.shutdown()
    hvd.init(devices=devices, mesh_shape=mesh_shape)
    n = hvd.size()
    mesh = hvd.mesh()
    stage = args.zero_stage or 3
    zero3 = stage == 3
    quantized = bool(args.quantized)
    overlap = bool(args.overlap)
    D = int(os.environ.get("HOROVOD_BENCH_FUSED_DIM", "256"))
    L = int(os.environ.get("HOROVOD_BENCH_FUSED_LAYERS", "4"))
    B = args.batch_size * n
    log(f"fused A/B: world={n} layers={L} dim={D} global_batch={B} "
        f"zero_stage={stage} quantized={quantized} overlap={overlap}")

    rng = np.random.RandomState(0)
    ws_full = np.stack([rng.randn(D, D).astype(np.float32) / np.sqrt(D)
                        for _ in range(L)])                  # [L, D, D]
    x = rng.randn(B, D).astype(np.float32)
    y = rng.randn(B, D).astype(np.float32)
    if zero3:
        # rank-major row shards, stacked [n, L, D/n, D] for P(HVD_AXES)
        w_arg = np.stack([ws_full[:, r * (D // n):(r + 1) * (D // n), :]
                          for r in range(n)])
        w_spec = P(hvd.HVD_AXES)
    else:
        w_arg = ws_full
        w_spec = P()

    def make_step(fused):
        def spmd(wsh, xb, yb):
            w = wsh[0] if zero3 else wsh                      # [L, ...]
            h = xb
            acts = []
            for li in range(L):
                acts.append(h)
                if zero3:
                    if fused:
                        h = hvd.fused_all_gather_matmul(h, w[li])
                    else:
                        wfull = hvd.all_gather(
                            w[li].reshape(-1)).reshape(D, D)
                        h = h @ wfull
                else:
                    h = h @ w[li]
            # Per-rank local cotangent; each layer's weight grad is the
            # canonical matmul → reduce-scatter pair (the activations
            # differ per layer, the cotangent is shared — a synthetic
            # but fixed compute pattern, identical across both legs).
            dh = (h - yb) * (2.0 / float(B * D))
            gs = []
            for li in reversed(range(L)):
                a = acts[li]
                if quantized:
                    # int8 grad wire: the quantize/dequant rides the
                    # plan-compiled DCN leg — Pallas-backed when fused.
                    flat = (a.T @ dh).reshape(-1)
                    if overlap:
                        g = hvd.reduce_scatter_stream(
                            flat, bucket_id=li, op=hvd.Sum,
                            quantized=True, fused=fused)
                    else:
                        g = hvd.reduce_scatter(flat, op=hvd.Sum,
                                               quantized=True,
                                               fused=fused)
                    g = g.reshape(D // n, D)
                elif fused:
                    g = hvd.fused_matmul_reduce_scatter(a.T, dh)
                elif overlap:
                    g = hvd.reduce_scatter_stream(
                        (a.T @ dh).reshape(-1), bucket_id=li,
                        op=hvd.Sum).reshape(D // n, D)
                else:
                    g = hvd.reduce_scatter(
                        (a.T @ dh).reshape(-1),
                        op=hvd.Sum).reshape(D // n, D)
                gs.append(g)
            gstack = jnp.stack(gs[::-1])                     # [L, D/n, D]
            loss = hvd.allreduce(jnp.mean((h - yb) ** 2))
            if zero3:
                new_w = wsh - 0.01 * gstack[None]
            else:
                # replicated weights: gather the shard grads back (the
                # stage-1/2 update tail) and apply
                gfull = jnp.stack([
                    hvd.all_gather(gstack[li].reshape(-1)).reshape(D, D)
                    for li in range(L)])
                new_w = wsh - 0.01 * gfull
            return new_w, gstack[None], loss

        return jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(w_spec, hvd.data_pspec(), hvd.data_pspec()),
            out_specs=(w_spec, P(hvd.HVD_AXES), P())))

    data_sh = hvd.data_sharding()
    xb = jax.device_put(jnp.asarray(x), data_sh)
    yb = jax.device_put(jnp.asarray(y), data_sh)
    w0 = jax.device_put(jnp.asarray(w_arg),
                        NamedSharding(mesh, w_spec))

    fn_snap0 = compile_snapshot()
    legs = {}
    for name, fused in (("unfused", False), ("fused", True)):
        log(f"=== A/B leg: {name} ===")
        step = make_step(fused)
        t_leg0 = time.perf_counter()
        compiled, wire, _ = cached_lower_compile(
            f"bench.fused.{name}", step, (w0, xb, yb), mesh=mesh,
            plan=f"q{int(quantized)}|z{stage}|ov{int(overlap)}|L{L}|D{D}")
        wcur, g1, loss = compiled(w0, xb, yb)
        jax.block_until_ready((wcur, g1, loss))
        ttfs_ms = (time.perf_counter() - t_leg0) * 1e3
        times = []
        for _ in range(args.num_iters):
            t0 = time.perf_counter()
            for _ in range(args.num_batches_per_iter):
                wcur, gl, loss = compiled(wcur, xb, yb)
            jax.block_until_ready((wcur, gl, loss))
            times.append((time.perf_counter() - t0)
                         / args.num_batches_per_iter)
        legs[name] = {
            "step_ms_median": float(np.median(times)) * 1e3,
            "wire": wire,
            "grad": np.asarray(g1),
            "loss": float(loss),
            "ttfs_ms": ttfs_ms,
        }
        log(f"{name}: step {legs[name]['step_ms_median']:.3f} ms, "
            f"wire ici {wire.ici_bytes / 1e3:.1f} kB dcn "
            f"{wire.dcn_bytes / 1e3:.1f} kB, fused kernel calls "
            f"{wire.fused_calls}, hbm saved "
            f"{wire.fused_hbm_saved_bytes / 1e3:.1f} kB")

    # Parity: identical inputs, one step — fused vs unfused gradients.
    ga, gb = legs["unfused"]["grad"], legs["fused"]["grad"]
    denom = max(1e-12, float(np.abs(ga).max()))
    max_rel = float(np.abs(ga - gb).max()) / denom
    # Unquantized: pure float-association noise of the ring accumulate.
    # Quantized: the fused forward's float-assoc noise can flip a value
    # across an int8 rounding boundary — one whole quantization step,
    # scale = block absmax / 127 — so the bound is a couple of quanta
    # (~2/127), not float ulps.
    tol = 2e-2 if quantized else 1e-4
    parity_ok = max_rel <= tol
    log(f"parity probe: max rel diff {max_rel:.2e} (tol {tol}) "
        f"{'OK' if parity_ok else 'FAILED'}")
    if not parity_ok:
        raise SystemExit(
            f"--fused parity FAILED: fused grads diverge from unfused "
            f"by {max_rel:.2e} > {tol}")

    hbm_saved = legs["fused"]["wire"].fused_hbm_saved_bytes
    if hbm_saved <= 0:
        raise SystemExit(
            "--fused: fused leg recorded zero saved HBM bytes — the "
            "kernels never engaged (check HOROVOD_FUSED_KERNELS "
            "routing)")
    hbm_gbps = float(os.environ.get("HOROVOD_BENCH_HBM_GBPS", "819"))
    modeled_saving_ms = hbm_saved / (hbm_gbps * 1e9) * 1e3
    unf_ms = legs["unfused"]["step_ms_median"]
    fus_ms = legs["fused"]["step_ms_median"]
    measured_delta = unf_ms / fus_ms - 1.0
    modeled_fused_ms = max(1e-6, unf_ms - modeled_saving_ms)
    log(f"A/B: unfused {unf_ms:.3f} ms vs fused {fus_ms:.3f} ms "
        f"measured ({100 * measured_delta:+.1f}%); modeled HBM "
        f"round-trip saved {hbm_saved / 1e3:.1f} kB/step/dev = "
        f"{modeled_saving_ms:.4f} ms at {hbm_gbps:.0f} GB/s"
        + ("" if platform == "tpu" else
           " [CPU interpret mode: the modeled saving is the contract; "
           "measured kernel time is interpreter overhead]"))

    from horovod_tpu import plan as hvd_plan

    if quantized:
        # Kernel-backed int8 legs on the plan-compiled wire.
        plan_enc = hvd_plan.describe_plan(
            quantized=True, zero_stage=stage,
            overlap=overlap or None, fused=True).encode()
    else:
        # The matmul⇄collective ring pair (docs/fused-kernels.md).
        parts = [hvd_plan.fused_matmul_rs_plan(overlap=overlap).encode()]
        if zero3:
            parts.append(
                "fwd@" + hvd_plan.fused_ag_matmul_plan(
                    overlap=overlap).encode())
        plan_enc = " + ".join(parts)
    print(json.dumps({
        "metric": "fused_matmul_collective_step_ms",
        "value": round(fus_ms, 4),
        "unit": "ms/step (lower is better)",
        "vs_baseline": None,
        "platform": platform,
        "device_kind": getattr(devices[0], "device_kind", "unknown"),
        "chips": n,
        "fused": True,
        "zero_stage": stage,
        "quantized": quantized,
        "overlap": overlap,
        "layers": L,
        "dim": D,
        "plan": plan_enc,
        "mesh_shape": (mesh_shape_str(mesh_shape)
                       if mesh_shape else None),
        "unfused_step_ms": round(unf_ms, 4),
        "throughput_delta_measured": round(measured_delta, 4),
        "hbm_saved_bytes_per_step": round(hbm_saved, 1),
        "fused_kernel_calls": legs["fused"]["wire"].fused_calls,
        "modeled": {
            "hbm_gbps": hbm_gbps,
            "saving_ms": round(modeled_saving_ms, 6),
            "fused_step_ms": round(modeled_fused_ms, 4),
            "improvement_frac": round(
                modeled_saving_ms / max(1e-9, unf_ms), 6),
        },
        "parity": {"max_rel_err": max_rel, "tol": tol, "ok": parity_ok},
        "wire_bytes_ici": round(legs["fused"]["wire"].ici_bytes, 1),
        "wire_bytes_dcn": round(legs["fused"]["wire"].dcn_bytes, 1),
        "wire_bytes_ici_unfused": round(
            legs["unfused"]["wire"].ici_bytes, 1),
        "wire_bytes_dcn_unfused": round(
            legs["unfused"]["wire"].dcn_bytes, 1),
        **compile_fields(fn_snap0, legs["fused"]["ttfs_ms"]),
        "metrics_snapshot": metrics_snapshot(),
    }), flush=True)


def run_pp(args, devices, platform, mesh_shape):
    """The ``--pp`` leg: interleaved-1F1B pipeline parallelism A/B
    (docs/pipeline.md).

    * **dense leg** — the same GPT trained pure-data-parallel over ALL
      devices (same global batch, same optimizer math): the throughput
      baseline and the parity reference.
    * **pipelined leg** — a dedicated ``hvd_pp`` mesh of ``--pp`` stages
      over the remaining data axes; the model splits into
      ``stages x --pp-interleave`` round-robin chunks and trains under
      the ``--pp-schedule`` schedule with the inter-stage hops lowered
      as wire-plan ``send`` legs. Composes ``--zero-stage`` (the
      per-stage sharded optimizer), ``--quantized`` (int8+EF on BOTH
      the gradient wire and, when the hop is DCN/pod-class, the
      activation sends), and ``--overlap`` (stream-scheduled bucket
      collectives filling the bubble T3-style) into ONE compiled step.

    When the requested schedule is in the interleaved table family,
    BOTH ``interleaved_1f1b`` and the zero-bubble ``zb1`` table run on
    the same geometry (schedule A/B) and the zb1 measured bubble must
    land strictly below the 1F1B one; under ``--zero-stage 3`` the
    forward's bucket all-gathers stream against the schedule's
    idle-tick table and the leg hard-gates predicted == accounted
    ``bubble_hidden_bytes`` (docs/pipeline.md).

    The JSON line carries the measured ``bubble_fraction`` (derived
    from the schedule's ``PP:F``/``PP:B``/``PP:W`` spans), the
    no-overlap GPipe analytic bound ``(S-1)/(M+S-1)`` it must stay
    strictly under, ``bubble_hidden_fraction`` + the fill byte pair,
    the per-hop wire bytes, and the send-leg predicted-vs-modeled
    wire-ms drift pair the perf gate checks (scripts/perf_gate.sh
    pp)."""
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import plan as hvd_plan
    from horovod_tpu.models import GPT, gpt_tiny
    from horovod_tpu.monitor import span_audit
    from horovod_tpu.ops.collective_ops import record_wire_stats
    from horovod_tpu.parallel.pipeline import (
        _send_plan_for_axis, build_interleaved_schedule, pp_split_chunks,
        pipelined_gpt_train)
    from horovod_tpu.plan.accounting import bench_gbps

    S = args.pp
    v = max(1, args.pp_interleave)
    sched_name = args.pp_schedule
    if sched_name not in ("interleaved_1f1b", "zb1") and v > 1:
        raise SystemExit(f"--pp-interleave {v} needs "
                         f"--pp-schedule interleaved_1f1b or zb1")
    ndev = len(devices)
    if ndev % S:
        raise SystemExit(f"--pp {S} does not divide {ndev} devices")
    if mesh_shape is not None:
        if len(mesh_shape) != 2:
            raise SystemExit("--pp takes a 2-D --mesh-shape (the DATA "
                             "mesh; the pp axis is the leading dim)")
        dmesh = tuple(mesh_shape)
    else:
        dp0 = ndev // S
        dmesh = (2, dp0 // 2) if dp0 % 2 == 0 and dp0 >= 2 else (1, dp0)
    dp = dmesh[0] * dmesh[1]
    if S * dp != ndev:
        raise SystemExit(f"--pp {S} x mesh {dmesh} != {ndev} devices")
    M = args.pp_microbatches
    if M % S and sched_name in ("interleaved_1f1b", "zb1") and v > 1:
        raise SystemExit(f"--pp-microbatches {M} must divide by --pp {S}")
    stage = args.zero_stage or 0
    quantized = bool(args.quantized)
    overlap = bool(args.overlap)
    lr = 0.05

    chunks_v = v if sched_name in ("interleaved_1f1b", "zb1") else 1
    L = S * max(chunks_v, v)
    seq = 16
    cfg = gpt_tiny(dtype=jnp.float32, num_layers=L)
    rs = np.random.RandomState(0)
    B = M * dp
    tokens = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, seq)))
    targets = jnp.asarray(rs.randint(0, cfg.vocab_size, (B, seq)))
    params0 = GPT(cfg).init(jax.random.PRNGKey(0), tokens)["params"]
    log(f"pp A/B: stages={S} interleave={v} microbatches={M} "
        f"schedule={sched_name} data_mesh={dmesh} layers={L} "
        f"global_batch={B} zero_stage={stage} quantized={quantized} "
        f"overlap={overlap}")

    def dense_loss_fn(p, tok, tgt):
        logits = GPT(cfg).apply({"params": p}, tok)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, tgt).mean()

    iters = max(2, args.num_iters)
    spc = max(1, args.num_batches_per_iter)

    # ---- dense leg: pure DP over all devices -------------------------
    hvd.shutdown()
    dense_mesh_shape = ((2, ndev // 2) if ndev % 2 == 0 and ndev >= 2
                        else (1, ndev))
    hvd.init(devices=devices, mesh_shape=dense_mesh_shape)
    mesh = hvd.mesh()

    def dense_spmd(p, tok, tgt):
        loss, g = hvd.value_and_grad(dense_loss_fn)(p, tok, tgt)
        loss = hvd.allreduce(loss, op=hvd.Average)
        return loss, jax.tree.map(lambda a, b: a - lr * b, p, g)

    dense_step = jax.jit(hvd.shard_map(
        dense_spmd, mesh=mesh,
        in_specs=(P(), hvd.data_pspec(), hvd.data_pspec()),
        out_specs=(P(), P())))
    p = params0
    fn_snap0 = compile_snapshot()
    t_fn0 = time.perf_counter()
    dense_loss0, p = jax.block_until_ready(dense_step(p, tokens, targets))
    # The pp legs re-trace per run on purpose — the bubble audit reads
    # PP:F/B/W spans emitted at trace time — so only the XLA-level
    # persistent cache (not the executable registry) accelerates them;
    # compile_count in this leg's JSON counts registry-routed compiles.
    ttfs_ms = (time.perf_counter() - t_fn0) * 1e3
    t0 = time.perf_counter()
    for _ in range(iters * spc):
        loss_d, p = dense_step(p, tokens, targets)
    jax.block_until_ready(loss_d)
    dense_sps = iters * spc / (time.perf_counter() - t0)
    dense_tps = dense_sps * B * seq
    log(f"dense leg: loss0={float(dense_loss0):.4f} "
        f"{dense_tps:.0f} tok/s ({dense_sps:.2f} steps/s)")

    # ---- pipelined leg(s) -------------------------------------------
    # Schedule A/B (docs/pipeline.md): when the requested schedule is in
    # the interleaved table family, BOTH interleaved-1F1B and the
    # zero-bubble zb1 table run on the same (S, M, v) geometry and land
    # in ONE JSON line — the zb1 measured bubble must come out strictly
    # below the 1F1B one, and each leg parity-gates against dense.
    from horovod_tpu import monitor as _monitor
    from horovod_tpu.ops import fusion as _fusion

    def pp_leg(leg_sched):
        family = "zb1" if leg_sched == "zb1" else "1f1b"
        hvd.shutdown()
        tl_path = os.path.join(tempfile.mkdtemp(prefix="bench_pp_"),
                               "pp_timeline.json")
        os.environ["HOROVOD_TIMELINE"] = tl_path
        try:
            hvd.init(devices=devices, mesh_shape=dmesh, pp_stages=S)
        finally:
            del os.environ["HOROVOD_TIMELINE"]
        mesh = hvd.mesh()
        assert hvd.pp_size() == S
        chunks, rest = pp_split_chunks(params0, S, chunks_v)
        splan = _send_plan_for_axis(hvd.PP_AXIS, quantized=quantized,
                                    block=256, error_feedback=quantized)
        sched = (build_interleaved_schedule(M, S, v, family=family)
                 if leg_sched != "gpipe" and S > 1 else None)
        # T3-style bubble fill (docs/pipeline.md): under ZeRO-3 the
        # forward's bucket all-gathers stream against the schedule's
        # idle-tick table, so up to idle_ticks_per_rank flights price as
        # bubble-hidden instead of exposed wire.
        fill_on = stage == 3 and sched is not None
        PPALL = (hvd.PP_AXIS,) + hvd.HVD_AXES
        data_spec = P(hvd.HVD_AXES)

        tx = hvd.DistributedOptimizer(
            optax.sgd(lr, momentum=0.9), zero_stage=stage,
            quantized=quantized, overlap=overlap,
            pp_stages=S, pp_microbatches=M, pp_schedule=leg_sched,
            pp_interleave=v) if stage else None

        def pp_grads(cp_local, rest_local, tok, tgt):
            return pipelined_gpt_train(
                cfg, cp_local, rest_local, tok, tgt, axis=hvd.PP_AXIS,
                num_microbatches=M, schedule=leg_sched, interleave=v,
                send_plan=splan if S > 1 else None)

        def state_specs(state):
            return jax.tree.map(
                lambda l: P(PPALL) if getattr(l, "ndim", 0) >= 1 else P(),
                state)

        if stage == 3:
            tpl = {"chunks": jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                chunks),
                "rest": jax.tree.map(
                    lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    rest)}
            psh_rows = []
            for r in range(S):
                ptree_r = {"chunks": jax.tree.map(lambda a: a[r], chunks),
                           "rest": rest}
                psh_rows.append(hvd.zero3_shard_params(ptree_r))
            psh = tuple(jnp.stack([row[i] for row in psh_rows])
                        for i in range(len(psh_rows[0])))
            psh_spec = jax.tree.map(
                lambda _: P(hvd.PP_AXIS, hvd.HVD_AXES), psh)
            psh = jax.device_put(psh, jax.tree.map(
                lambda q: NamedSharding(mesh, q), psh_spec))

            def init_spmd(psh):
                local = tuple(b[0] for b in psh)
                ptree = hvd.zero3_gather_params(local, tpl)
                return tx.init(ptree)

            # Host-side init of ONE stage's tree gives the state
            # STRUCTURE (leaf ranks match the in-trace form); the values
            # come from the in-trace init below, sharded per stage x
            # data rank.
            state_tpl = tx.init({"chunks": jax.tree.map(lambda a: a[0],
                                                        chunks),
                                 "rest": rest})
            state = jax.jit(hvd.shard_map(
                init_spmd, mesh=mesh, in_specs=(psh_spec,),
                out_specs=state_specs(state_tpl)))(psh)

            def step_spmd(psh, state, tok, tgt):
                local = tuple(b[0] for b in psh)
                ptree = hvd.zero3_gather_params(
                    local, tpl, overlap=True if fill_on else None,
                    fill_sched=sched if fill_on else None)
                loss, g_cp, g_rest = pp_grads(ptree["chunks"],
                                              ptree["rest"], tok, tgt)
                grads = {"chunks": g_cp, "rest": g_rest}
                upd, new_state = tx.update(grads, state, local)
                new_local = optax.apply_updates(local, upd)
                loss = hvd.allreduce(loss, op=hvd.Average)
                return (loss, tuple(u[None] for u in new_local),
                        new_state)

            sspec = state_specs(state)
            step = jax.jit(hvd.shard_map(
                step_spmd, mesh=mesh,
                in_specs=(psh_spec, sspec, data_spec, data_spec),
                out_specs=(P(), psh_spec, sspec)))
            carry = (psh, state)

            def drive(tok, tgt):
                nonlocal carry
                psh, state = carry
                loss, psh, state = step(psh, state, tok, tgt)
                carry = (psh, state)
                return loss
        elif stage:
            ptree = {"chunks": chunks, "rest": rest}
            pspec = {"chunks": jax.tree.map(lambda _: P(hvd.PP_AXIS),
                                            chunks),
                     "rest": jax.tree.map(lambda _: P(), rest)}

            def init_spmd(pt):
                local = {"chunks": jax.tree.map(lambda a: a[0],
                                                pt["chunks"]),
                         "rest": pt["rest"]}
                return tx.init(local)

            state_tpl = tx.init({"chunks": jax.tree.map(lambda a: a[0],
                                                        chunks),
                                 "rest": rest})
            state = jax.jit(hvd.shard_map(
                init_spmd, mesh=mesh, in_specs=(pspec,),
                out_specs=state_specs(state_tpl)))(ptree)

            def step_spmd(pt, state, tok, tgt):
                local_c = jax.tree.map(lambda a: a[0], pt["chunks"])
                loss, g_cp, g_rest = pp_grads(local_c, pt["rest"], tok,
                                              tgt)
                grads = {"chunks": g_cp, "rest": g_rest}
                local = {"chunks": local_c, "rest": pt["rest"]}
                upd, new_state = tx.update(grads, state, local)
                new_local = optax.apply_updates(local, upd)
                loss = hvd.allreduce(loss, op=hvd.Average)
                # The optimizer's buckets mix pp-varying chunk leaves
                # with pp-invariant rest leaves, so the updated rest
                # comes back typed pp-varying although every stage
                # computed the same value — re-establish the replication
                # by construction (stage 0's copy, masked psum) so the
                # P() out-spec holds.
                from jax import lax as _lax

                rpp = _lax.axis_index(hvd.PP_AXIS)
                new_rest = jax.tree.map(
                    lambda a: _lax.psum(
                        jnp.where(rpp == 0, a, jnp.zeros_like(a)),
                        hvd.PP_AXIS), new_local["rest"])
                new_pt = {"chunks": jax.tree.map(lambda a: a[None],
                                                 new_local["chunks"]),
                          "rest": new_rest}
                return loss, new_pt, new_state

            sspec = state_specs(state)
            step = jax.jit(hvd.shard_map(
                step_spmd, mesh=mesh,
                in_specs=(pspec, sspec, data_spec, data_spec),
                out_specs=(P(), pspec, sspec)))
            carry = (ptree, state)

            def drive(tok, tgt):
                nonlocal carry
                pt, state = carry
                loss, pt, state = step(pt, state, tok, tgt)
                carry = (pt, state)
                return loss
        else:
            ptree = {"chunks": chunks, "rest": rest}
            pspec = {"chunks": jax.tree.map(lambda _: P(hvd.PP_AXIS),
                                            chunks),
                     "rest": jax.tree.map(lambda _: P(), rest)}

            def step_spmd(pt, tok, tgt):
                local_c = jax.tree.map(lambda a: a[0], pt["chunks"])
                loss, g_cp, g_rest = pp_grads(local_c, pt["rest"], tok,
                                              tgt)
                # Chunk grads are pp-VARYING (per stage), rest grads
                # pp-invariant — reduce them in separate bucket sets so
                # the rest wire keeps its provable pp replication.
                g_cp = hvd.allreduce_pytree(g_cp, op=hvd.Average,
                                            quantized=quantized or None,
                                            overlap=overlap or None)
                g_rest = hvd.allreduce_pytree(
                    g_rest, op=hvd.Average, quantized=quantized or None,
                    overlap=overlap or None)
                new_c = jax.tree.map(lambda a, b: a - lr * b, local_c,
                                     g_cp)
                new_rest = jax.tree.map(lambda a, b: a - lr * b,
                                        pt["rest"], g_rest)
                loss = hvd.allreduce(loss, op=hvd.Average)
                return loss, {"chunks": jax.tree.map(lambda a: a[None],
                                                     new_c),
                              "rest": new_rest}

            step = jax.jit(hvd.shard_map(
                step_spmd, mesh=mesh,
                in_specs=(pspec, data_spec, data_spec),
                out_specs=(P(), pspec)))
            carry = [ptree]

            def drive(tok, tgt):
                loss, carry[0] = step(carry[0], tok, tgt)
                return loss

        with record_wire_stats() as wire:
            pp_loss0 = jax.block_until_ready(drive(tokens, targets))
        parity_rel = abs(float(pp_loss0) - float(dense_loss0)) / max(
            1e-9, abs(float(dense_loss0)))
        tol = 1e-2 if quantized else 1e-4
        log(f"pp[{leg_sched}] leg: loss0={float(pp_loss0):.4f} vs dense "
            f"{float(dense_loss0):.4f} (rel {parity_rel:.2e}, tol {tol})")
        if parity_rel > tol:
            raise SystemExit(
                f"pp parity FAILED ({leg_sched}): pipelined loss "
                f"{float(pp_loss0)} vs dense {float(dense_loss0)} "
                f"(rel {parity_rel:.2e} > {tol})")

        # Bubble-fill contract hard-gate (docs/pipeline.md): the cost
        # model's predicted flat all-gather bytes for the first
        # min(buckets, idle ticks) forward-order flights must equal the
        # trace-accounted bubble_hidden_bytes exactly.
        fill = {"capacity_ticks": (sched.idle_ticks_per_rank
                                   if sched is not None else 0),
                "filled_ticks": wire.filled_ticks,
                "bubble_hidden_bytes": wire.bubble_hidden_bytes,
                "predicted_bytes": 0.0,
                "bubble_hidden_fraction": 0.0}
        if fill_on:
            planb = hvd.zero3_plan(tpl)
            cap = sched.idle_ticks_per_rank
            exp_filled = min(len(planb), cap)
            pred = 0.0
            for i in _fusion.gather_order(planb)[:exp_filled]:
                rows = hvd_plan.predict_leg_bytes(
                    hvd_plan.flat_plan("all_gather"),
                    planb[i].padded_size, 4, dmesh)
                pred += sum(r["bytes"] for r in rows)
            fill["predicted_bytes"] = pred
            fill["bubble_hidden_fraction"] = exp_filled / max(1, cap)
            fdrift = abs(pred - wire.bubble_hidden_bytes) / max(1.0, pred)
            log(f"bubble fill[{leg_sched}]: {wire.filled_ticks}/{cap} "
                f"idle ticks filled, accounted "
                f"{wire.bubble_hidden_bytes:.0f} B vs predicted "
                f"{pred:.0f} B")
            if wire.filled_ticks != exp_filled or fdrift > 1e-6:
                raise SystemExit(
                    f"pp bubble-fill drift FAILED ({leg_sched}): filled "
                    f"{wire.filled_ticks} ticks vs {exp_filled} "
                    f"expected; accounted {wire.bubble_hidden_bytes:.0f}"
                    f" B vs predicted {pred:.0f} B")

        t0 = time.perf_counter()
        for _ in range(iters * spc):
            loss_p = drive(tokens, targets)
        jax.block_until_ready(loss_p)
        pp_sps = iters * spc / (time.perf_counter() - t0)
        pp_tps = pp_sps * B * seq

        # Bubble measured from the schedule's PP:F/PP:B/PP:W spans (the
        # zb1 table emits the deferred W units as first-class spans).
        bound = hvd_plan.pp_bubble_bound(S, M)
        if sched is not None:
            hvd.shutdown()  # flush + close the timeline
            audit = span_audit.audit_spans(tl_path, prefix="PP:",
                                           require_spans=True)
            busy = (audit.count.get("PP:F", 0)
                    + audit.count.get("PP:B", 0)
                    + audit.count.get("PP:W", 0))
            # One trace per compiled step; the schedule emits once.
            per_trace = sched.unit_count()
            traces = max(1, busy // per_trace)
            bubble = 1.0 - (busy / traces) / float(S * sched.ticks)
            ticks = sched.ticks
        else:
            bubble = bound  # gpipe baseline: the analytic bound itself
            ticks = M + S - 1
        log(f"bubble_fraction[{leg_sched}]={bubble:.4f} "
            f"(gpipe bound {bound:.4f}, {ticks} ticks)")

        # Straggler attribution: the measured idle ticks feed the
        # pp_bubble phase NET of the fill credit (monitor/straggler.py);
        # the compute remainder gets the rest.
        pp_step_ms = 1e3 / max(1e-9, pp_sps)
        det = _monitor.straggler_detector()
        if sched is not None:
            _monitor.record_pp_bubble(
                sched.idle_ticks_per_rank, sched.ticks, pp_step_ms,
                filled_ticks=wire.filled_ticks, detector=det)
        else:
            det.record_phase("pp_bubble", bubble * pp_step_ms)
        det.record_phase("compute", max(0.0, (1.0 - bubble) * pp_step_ms))
        det.end_step()

        # Send-leg drift pair: predicted (cost model) vs the
        # trace-accounted bytes at the modeled bandwidths.
        act_bytes = (B // (M * dp)) * seq * cfg.d_model * 4.0
        issues = 2 * ticks if sched is not None else (M + S - 1)
        priced = hvd_plan.price_send(
            splan, act_bytes, issues=issues, mesh_shape=dmesh,
            model=hvd_plan.get_cost_model(mesh_shape=dmesh))
        ici_g, dcn_g, pod_g = bench_gbps()
        hop = splan.legs[0].level
        hop_gbps = {"ici": ici_g, "dcn": dcn_g, "pod": pod_g}[hop]
        pp_wire_ms_modeled = wire.pp_bytes / (hop_gbps * 1e9) * 1e3
        drift = (abs(priced["modeled_ms"] - pp_wire_ms_modeled)
                 / max(1e-9, pp_wire_ms_modeled))
        log(f"send wire[{leg_sched}]: accounted {wire.pp_bytes:.0f} B "
            f"({pp_wire_ms_modeled:.4f} ms modeled) vs predicted "
            f"{priced['wire_bytes']:.0f} B ({priced['modeled_ms']:.4f} "
            f"ms); drift {drift:.4f}")

        return {
            "schedule": leg_sched, "family": family,
            "parity_rel_err": parity_rel, "parity_tol": tol,
            "tokens_per_sec": pp_tps, "steps_per_sec": pp_sps,
            "bubble_fraction": bubble, "bubble_bound": bound,
            "ticks": ticks, "send_plan": splan.encode(),
            "wire": wire, "priced": priced,
            "pp_wire_ms_modeled": pp_wire_ms_modeled, "drift": drift,
            "fill": fill,
        }

    ab = sched_name in ("interleaved_1f1b", "zb1") and S > 1
    leg_names = ["interleaved_1f1b", "zb1"] if ab else [sched_name]
    legs = {name: pp_leg(name) for name in leg_names}
    prim = legs[sched_name]
    if ab:
        b1 = legs["interleaved_1f1b"]["bubble_fraction"]
        bz = legs["zb1"]["bubble_fraction"]
        log(f"schedule A/B: interleaved-1F1B bubble {b1:.4f} vs zb1 "
            f"{bz:.4f}")
        if not bz < b1:
            raise SystemExit(
                f"zb1 bubble FAILED: {bz:.4f} not strictly below the "
                f"interleaved-1F1B bubble {b1:.4f} on the same geometry "
                f"(S={S}, M={M}, v={v})")

    wire = prim["wire"]
    priced = prim["priced"]
    result = {
        "metric": f"pp{S}_tokens_per_sec",
        "value": round(prim["tokens_per_sec"], 1),
        "unit": "tokens/sec",
        "platform": platform,
        "pp": {
            "stages": S, "interleave": v, "microbatches": M,
            "schedule": sched_name, "data_mesh": mesh_shape_str(dmesh),
            "zero_stage": stage, "quantized": quantized,
            "overlap": overlap, "send_plan": prim["send_plan"],
            "ticks": prim["ticks"],
        },
        "bubble_fraction": round(prim["bubble_fraction"], 6),
        "bubble_bound_gpipe": round(prim["bubble_bound"], 6),
        "parity_rel_err": prim["parity_rel_err"],
        "parity_tol": prim["parity_tol"],
        "dense_tokens_per_sec": round(dense_tps, 1),
        "throughput_delta": round(
            prim["tokens_per_sec"] / max(1e-9, dense_tps), 4),
        "wire_bytes_ici": wire.ici_bytes,
        "wire_bytes_dcn": wire.dcn_bytes,
        "wire_bytes_pod": wire.pod_bytes,
        "pp_send_bytes": wire.pp_bytes,
        "pp_sends": wire.pp_sends,
        "bubble_hidden_fraction": round(
            prim["fill"]["bubble_hidden_fraction"], 6),
        "bubble_hidden_bytes": prim["fill"]["bubble_hidden_bytes"],
        "filled_ticks": prim["fill"]["filled_ticks"],
        "fill_capacity_ticks": prim["fill"]["capacity_ticks"],
        "fill_predicted_bytes": round(
            prim["fill"]["predicted_bytes"], 1),
        "wire_ms": {
            "predicted": round(priced["modeled_ms"], 4),
            "predicted_total": round(priced["predicted_ms"], 4),
            "modeled": round(prim["pp_wire_ms_modeled"], 4),
            "model": priced["model"],
        },
        **compile_fields(fn_snap0, ttfs_ms),
        "metrics_snapshot": metrics_snapshot(),
    }
    if ab:
        result["bubble_fraction_1f1b"] = round(
            legs["interleaved_1f1b"]["bubble_fraction"], 6)
        result["bubble_fraction_zb1"] = round(
            legs["zb1"]["bubble_fraction"], 6)
        result["schedules"] = {
            name: {
                "bubble_fraction": round(r["bubble_fraction"], 6),
                "tokens_per_sec": round(r["tokens_per_sec"], 1),
                "parity_rel_err": r["parity_rel_err"],
                "bubble_hidden_fraction": round(
                    r["fill"]["bubble_hidden_fraction"], 6),
            } for name, r in legs.items()}
    print(json.dumps(result))
    return result


def run_pp4d(args, devices, platform, mesh_shape):
    """The combined ``--pp S --moe E --zero-stage 3`` leg: the 4-D
    composed mesh ``(hvd_pp, hvd_ep, hvd_cross, hvd_local)``
    (docs/parallelism.md).

    One residual top-k MoE FFN stage per hvd_pp rank, expert groups on
    the stage-LOCAL hvd_ep axis (the dispatch/combine exchanges lowered
    as wire-plan ``a2a`` legs; ``--quantized`` rides them
    blockwise-int8), ZeRO-3 parameter shards per (stage, expert-group)
    cell over the trailing data mesh, and the forward's bucket
    all-gathers streamed against the pipeline schedule's idle-tick
    table (the T3-style bubble fill; ``--pp-schedule zb1`` runs the
    zero-bubble table). Hard gates: one-step loss parity vs the dense
    single-device reference, and predicted == accounted bubble-fill
    bytes. The JSON line carries the composed plan encodings, the
    ``ppS.epE`` geometry fingerprint, per-hop + a2a + pp-send wire
    bytes, the fill pair, and the a2a predicted-vs-modeled drift the
    perf gate checks (scripts/perf_gate.sh pp4d)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import monitor as _monitor
    from horovod_tpu import plan as hvd_plan
    from horovod_tpu.common import basics as _basics
    from horovod_tpu.moe import (EXPERT_LEAVES, default_a2a_plan,
                                 ep_mean_dense_grads, ep_stack_params,
                                 moe_capacity, moe_ffn)
    from horovod_tpu.ops import fusion as _fusion
    from horovod_tpu.ops.collective_ops import record_wire_stats
    from horovod_tpu.parallel.pipeline import (
        build_interleaved_schedule, interleaved_1f1b)
    from horovod_tpu.plan.accounting import bench_gbps

    S, E = args.pp, args.moe
    K = min(args.moe_topk, E)
    sched_name = args.pp_schedule
    if sched_name == "gpipe":
        raise SystemExit("--pp --moe needs a table-family schedule "
                         "(interleaved_1f1b or zb1), not gpipe")
    family = "zb1" if sched_name == "zb1" else "1f1b"
    if (args.zero_stage or 0) != 3:
        raise SystemExit("--pp --moe is the combined 4-D ZeRO-3 leg: "
                         "pass --zero-stage 3 (the EPxPP stage<=2 "
                         "matrix is covered by tests/test_pp4d.py)")
    quantized = bool(args.quantized)
    overlap = bool(args.overlap)
    ndev = len(devices)
    if ndev % (S * E):
        raise SystemExit(f"--pp {S} x --moe {E} does not divide {ndev} "
                         f"devices")
    if mesh_shape is not None:
        if len(mesh_shape) != 2:
            raise SystemExit("--pp --moe takes a 2-D --mesh-shape (the "
                             "per-cell DATA mesh)")
        dmesh = tuple(mesh_shape)
    else:
        dp0 = ndev // (S * E)
        dmesh = (2, dp0 // 2) if dp0 % 2 == 0 and dp0 >= 2 else (1, dp0)
    dp = dmesh[0] * dmesh[1]
    if S * E * dp != ndev:
        raise SystemExit(f"--pp {S} x --moe {E} x mesh {dmesh} != "
                         f"{ndev} devices")
    M = args.pp_microbatches
    C, F = 32, 64
    NL = 16                        # tokens per device per microbatch
    Nb = NL * E * dp               # tokens per microbatch (pp-replicated)
    lr = 0.05
    blk = 64
    cf = float(E)                  # lossless capacity: parity is exact
    iters = max(2, args.num_iters)
    spc = max(1, args.num_batches_per_iter)
    log(f"pp4d leg: stages={S} experts={E} topk={K} microbatches={M} "
        f"schedule={sched_name} data_mesh={dmesh} zero_stage=3 "
        f"quantized={quantized} overlap={overlap} "
        f"tokens_per_step={M * Nb}")

    def init_stage(seed):
        r = np.random.RandomState(seed)
        return {
            "router": jnp.asarray(r.randn(C, E) * 0.1, jnp.float32),
            "w1": jnp.asarray(r.randn(E, C, F) * 0.1, jnp.float32),
            "b1": jnp.zeros((E, F), jnp.float32),
            "w2": jnp.asarray(r.randn(E, F, C) * 0.1, jnp.float32),
            "b2": jnp.zeros((E, C), jnp.float32),
        }

    stage_params = [init_stage(11 + s) for s in range(S)]
    rs = np.random.RandomState(5)
    hp = {"wh": jnp.asarray(rs.randn(C, C) * 0.1, jnp.float32)}
    x = jnp.asarray(rs.randn(M, Nb, C), jnp.float32)
    tgt = jnp.asarray(rs.randn(M, Nb, C), jnp.float32)

    # Dense single-device reference (eager, no mesh): the same routing
    # math on the full batch — lossless capacity keeps it exact.
    h_ref = x.reshape(-1, C)
    for p in stage_params:
        y_ref, _, _ = moe_ffn(h_ref, p, topk=K, capacity_factor=cf)
        h_ref = h_ref + y_ref
    dense_loss = float(jnp.mean((h_ref @ hp["wh"]
                                 - tgt.reshape(-1, C)) ** 2))

    hvd.shutdown()
    hvd.init(devices=devices, mesh_shape=dmesh, ep_size=E, pp_stages=S)
    mesh = hvd.mesh()
    assert hvd.pp_size() == S and hvd.ep_size() == E
    geometry = _basics.mesh_geometry()
    EPALL = (hvd.EP_AXIS,) + hvd.HVD_AXES
    SALL = (hvd.PP_AXIS, hvd.EP_AXIS) + hvd.HVD_AXES
    splan = default_a2a_plan(hvd.EP_AXIS, quantized=quantized,
                             block=blk, error_feedback=False)
    sched = build_interleaved_schedule(M, S, 1, family=family)
    log(f"a2a plan: {splan.encode()} geometry: {geometry}")

    stacked = [ep_stack_params(p, E) for p in stage_params]
    chunks = jax.tree.map(lambda *ls: jnp.stack(ls), *stacked)

    def leaf_name(path):
        return (path[-1].key if hasattr(path[-1], "key")
                else str(path[-1]))

    def cell_local(s, g):
        """Cell (stage s, expert-group g)'s LOCAL tree — the form the
        in-trace ``b[0, 0]`` slices reproduce (expert leaves keep the
        ep-singleton lead that doubles as the schedule's v dim)."""
        def pick(path, a):
            if leaf_name(path) in EXPERT_LEAVES:
                return a[s, g][None]
            return a[s][None]

        return {"chunks": jax.tree_util.tree_map_with_path(pick, chunks),
                "head": hp}

    lc_tpl = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        cell_local(0, 0))
    cells = [[hvd.zero3_shard_params(cell_local(s, g)) for g in range(E)]
             for s in range(S)]
    nb = len(cells[0][0])
    psh = tuple(jnp.stack([jnp.stack([cells[s][g][i] for g in range(E)])
                           for s in range(S)]) for i in range(nb))
    psh_spec = jax.tree.map(
        lambda _: P(hvd.PP_AXIS, hvd.EP_AXIS, hvd.HVD_AXES), psh)
    psh = jax.device_put(psh, jax.tree.map(
        lambda q: NamedSharding(mesh, q), psh_spec))

    tx = hvd.DistributedOptimizer(
        optax.sgd(lr, momentum=0.9), zero_stage=3, quantized=quantized,
        overlap=overlap, pp_stages=S, pp_microbatches=M,
        pp_schedule=sched_name, moe_experts=E, moe_capacity_factor=cf)

    def stage_fn(p, xx):
        y, _, _ = moe_ffn(xx, p, topk=K, capacity_factor=cf,
                          ep_axis=hvd.EP_AXIS, a2a_plan=splan)
        return xx + y

    def loss_fn(hp_, y, tg):
        return jnp.mean((y @ hp_["wh"] - tg) ** 2)

    def state_specs(state):
        return jax.tree.map(
            lambda l: P(SALL) if getattr(l, "ndim", 0) >= 1 else P(),
            state)

    def init_spmd(psh):
        local = tuple(b[0, 0] for b in psh)
        lc = hvd.zero3_gather_params(local, lc_tpl)
        return tx.init(lc)

    state_tpl = tx.init(cell_local(0, 0))
    state = jax.jit(hvd.shard_map(
        init_spmd, mesh=mesh, in_specs=(psh_spec,),
        out_specs=state_specs(state_tpl)))(psh)
    sspec = state_specs(state)

    def step_spmd(psh, state, xb, tg):
        local = tuple(b[0, 0] for b in psh)
        lc = hvd.zero3_gather_params(local, lc_tpl, overlap=True,
                                     fill_sched=sched)
        loss, g_cp, g_hp, _ = interleaved_1f1b(
            stage_fn, loss_fn, lc["chunks"], lc["head"], xb, tg,
            axis=hvd.PP_AXIS, interleave=1, family=family)
        # Global-mean gradient shares (docs/moe.md): router/head pmean
        # over hvd_ep, expert leaves 1/ep — never a reduction over
        # hvd_pp; the stage-3 update then averages over the data axes.
        g = ep_mean_dense_grads({"chunks": g_cp, "head": g_hp})
        upd, new_state = tx.update(g, state, local)
        new_local = optax.apply_updates(local, upd)
        loss = hvd.allreduce(loss, op=hvd.Average, axes=EPALL)
        return (loss, tuple(u[None, None] for u in new_local), new_state)

    data_spec = P(None, EPALL)
    step = jax.jit(hvd.shard_map(
        step_spmd, mesh=mesh,
        in_specs=(psh_spec, sspec, data_spec, data_spec),
        out_specs=(P(), psh_spec, sspec)))
    carry = [psh, state]

    def drive(xb, tg):
        loss, carry[0], carry[1] = step(carry[0], carry[1], xb, tg)
        return loss

    fn_snap0 = compile_snapshot()
    t_fn0 = time.perf_counter()
    with record_wire_stats() as wire:
        loss0 = jax.block_until_ready(drive(x, tgt))
    # Re-traced per run on purpose (the fill audit reads trace-time
    # spans); the XLA persistent cache still absorbs the XLA compile.
    ttfs_ms = (time.perf_counter() - t_fn0) * 1e3
    parity_rel = abs(float(loss0) - dense_loss) / max(1e-9,
                                                      abs(dense_loss))
    tol = 5e-2 if quantized else 1e-4
    log(f"pp4d parity: loss0={float(loss0):.5f} vs dense "
        f"{dense_loss:.5f} (rel {parity_rel:.2e}, tol {tol})")
    if parity_rel > tol:
        raise SystemExit(
            f"pp4d parity FAILED: pipelined MoE ZeRO-3 loss "
            f"{float(loss0)} vs dense {dense_loss} "
            f"(rel {parity_rel:.2e} > {tol})")

    # Bubble-fill contract hard-gate, same as the --pp leg.
    planb = hvd.zero3_plan(lc_tpl)
    cap = sched.idle_ticks_per_rank
    exp_filled = min(len(planb), cap)
    pred = 0.0
    for i in _fusion.gather_order(planb)[:exp_filled]:
        rows = hvd_plan.predict_leg_bytes(
            hvd_plan.flat_plan("all_gather"), planb[i].padded_size, 4,
            dmesh)
        pred += sum(r["bytes"] for r in rows)
    fdrift = abs(pred - wire.bubble_hidden_bytes) / max(1.0, pred)
    log(f"bubble fill: {wire.filled_ticks}/{cap} idle ticks filled, "
        f"accounted {wire.bubble_hidden_bytes:.0f} B vs predicted "
        f"{pred:.0f} B")
    if wire.filled_ticks != exp_filled or fdrift > 1e-6:
        raise SystemExit(
            f"pp4d bubble-fill drift FAILED: filled {wire.filled_ticks} "
            f"ticks vs {exp_filled} expected; accounted "
            f"{wire.bubble_hidden_bytes:.0f} B vs predicted {pred:.0f} B")

    t0 = time.perf_counter()
    for _ in range(iters * spc):
        loss_p = drive(x, tgt)
    jax.block_until_ready(loss_p)
    sps = iters * spc / (time.perf_counter() - t0)
    tps = sps * M * Nb

    # a2a drift pair (run_moe's formula on the stage-local plan) +
    # straggler attribution with the fill credit.
    a2a_cap = moe_capacity(NL, E, cf, K)
    buf_bytes = E * a2a_cap * C * 4.0
    priced = hvd_plan.price_a2a(
        splan, buf_bytes, ep=E, issues=max(1, wire.a2a_calls),
        mesh_shape=dmesh, model=hvd_plan.get_cost_model(mesh_shape=dmesh))
    ici_g, dcn_g, pod_g = bench_gbps()
    hop = splan.legs[0].level
    hop_gbps = {"ici": ici_g, "dcn": dcn_g, "pod": pod_g}[hop]
    a2a_ms_modeled = wire.a2a_bytes / (hop_gbps * 1e9) * 1e3
    drift = (abs(priced["modeled_ms"] - a2a_ms_modeled)
             / max(1e-9, a2a_ms_modeled))
    log(f"a2a wire: accounted {wire.a2a_bytes:.0f} B "
        f"({a2a_ms_modeled:.4f} ms modeled, {wire.a2a_calls} exchanges) "
        f"vs predicted {priced['wire_bytes']:.0f} B "
        f"({priced['modeled_ms']:.4f} ms); drift {drift:.4f}")

    step_ms = 1e3 / max(1e-9, sps)
    det = _monitor.straggler_detector()
    _monitor.record_pp_bubble(sched.idle_ticks_per_rank, sched.ticks,
                              step_ms, filled_ticks=wire.filled_ticks,
                              detector=det)
    det.record_phase("wire.a2a", min(step_ms, a2a_ms_modeled))
    det.record_phase("compute", max(0.0, step_ms - a2a_ms_modeled))
    det.end_step()

    result = {
        "metric": f"pp{S}ep{E}_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "platform": platform,
        "chips": ndev,
        "pp4d": {
            "stages": S, "experts": E, "topk": K, "microbatches": M,
            "schedule": sched_name, "family": family,
            "data_mesh": mesh_shape_str(dmesh), "geometry": geometry,
            "zero_stage": 3, "quantized": quantized, "overlap": overlap,
            "a2a_plan": splan.encode(), "ticks": sched.ticks,
        },
        "parity_rel_err": parity_rel,
        "parity_tol": tol,
        "bubble_fraction": round(sched.bubble_fraction, 6),
        "bubble_hidden_fraction": round(exp_filled / max(1, cap), 6),
        "bubble_hidden_bytes": wire.bubble_hidden_bytes,
        "filled_ticks": wire.filled_ticks,
        "fill_capacity_ticks": cap,
        "fill_predicted_bytes": round(pred, 1),
        "wire_bytes_ici": wire.ici_bytes,
        "wire_bytes_dcn": wire.dcn_bytes,
        "wire_bytes_pod": wire.pod_bytes,
        "a2a_bytes": wire.a2a_bytes,
        "a2a_calls": wire.a2a_calls,
        "pp_send_bytes": wire.pp_bytes,
        "pp_sends": wire.pp_sends,
        "wire_ms": {
            "predicted": round(priced["modeled_ms"], 4),
            "predicted_total": round(priced["predicted_ms"], 4),
            "modeled": round(a2a_ms_modeled, 4),
            "model": priced["model"],
        },
        **compile_fields(fn_snap0, ttfs_ms),
        "metrics_snapshot": metrics_snapshot(
            prefixes=("comm.", "step.", "moe.", "straggler.", "link.",
                      "compile.")),
    }
    print(json.dumps(result))
    return result


def run_moe(args, devices, platform, mesh_shape):
    """The ``--moe`` leg: expert-parallel MoE vs iso-FLOP dense A/B
    (docs/moe.md).

    * **dense leg** — an L-layer residual FFN stack with
      ``d_ff = topk x expert_d_ff`` (the same per-token FLOPs a top-k
      MoE spends) trained pure-data-parallel over ALL devices: the
      throughput baseline.
    * **moe leg** — the same token budget on a dedicated ``hvd_ep``
      mesh of ``--moe`` expert groups (one expert per group,
      ``hvd.init(ep_size=E)``): per-layer top-k routing with
      capacity-factor dispatch, the dispatch/combine exchanges lowered
      as wire-plan ``a2a`` legs (``--quantized`` = blockwise-int8 with
      error feedback on the DCN-class hvd_ep hop). Expert grads reduce
      only within their expert's data group (the dedicated-axis
      contract); router grads take their explicit ep-mean.

    Before timing, a forced-routing parity probe hard-checks the wire:
    every token routed to expert 0 with identity gating must reproduce
    the dense expert-0 FFN (int8 wire within its documented error
    bound). The JSON line carries tokens/sec for both legs, per-hop +
    a2a wire bytes, the per-expert load histogram, the dropped-token
    fraction, and the a2a predicted-vs-modeled wire-ms drift pair the
    perf gate checks (scripts/perf_gate.sh moe)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import PartitionSpec as P

    import horovod_tpu as hvd
    from horovod_tpu import plan as hvd_plan
    from horovod_tpu.moe import (default_a2a_plan, ep_mean_dense_grads,
                                 ep_param_pspecs, ep_stack_params,
                                 moe_capacity, moe_ef_residuals, moe_ffn)
    from horovod_tpu.ops.collective_ops import record_wire_stats
    from horovod_tpu.plan.accounting import bench_gbps

    E = args.moe
    K = args.moe_topk
    cf = args.moe_capacity
    L = args.moe_layers
    quantized = bool(args.quantized)
    ndev = len(devices)
    if ndev % E:
        raise SystemExit(f"--moe {E} does not divide {ndev} devices")
    if mesh_shape is not None:
        if len(mesh_shape) != 2:
            raise SystemExit("--moe takes a 2-D --mesh-shape (the DATA "
                             "mesh; the hvd_ep axis is the leading dim)")
        dmesh = tuple(mesh_shape)
    else:
        dp0 = ndev // E
        # Cross-major default: the hvd_ep hop should cross a DCN-class
        # link (that is what --quantized compresses), so the data mesh
        # keeps a cross dim whenever it can.
        dmesh = ((2, dp0 // 2) if dp0 % 2 == 0 and dp0 >= 4
                 else (dp0, 1))
    dp = dmesh[0] * dmesh[1]
    if E * dp != ndev:
        raise SystemExit(f"--moe {E} x mesh {dmesh} != {ndev} devices")
    C, F = 32, 64
    Nd = 64                       # tokens per device
    Ng = Nd * ndev                # global tokens per step
    lr = 0.05
    blk = 64
    iters = max(2, args.num_iters)
    spc = max(1, args.num_batches_per_iter)
    rs = np.random.RandomState(0)
    log(f"moe A/B: experts={E} topk={K} capacity_factor={cf} layers={L} "
        f"data_mesh={dmesh} quantized={quantized} global_tokens={Ng}")

    def init_layer(seed):
        r = np.random.RandomState(seed)
        return {
            "router": jnp.asarray(r.randn(C, E) * 0.1, jnp.float32),
            "w1": jnp.asarray(r.randn(E, C, F) * 0.1, jnp.float32),
            "b1": jnp.zeros((E, F), jnp.float32),
            "w2": jnp.asarray(r.randn(E, F, C) * 0.1, jnp.float32),
            "b2": jnp.zeros((E, C), jnp.float32),
        }

    layers = [init_layer(7 + i) for i in range(L)]
    x_global = jnp.asarray(rs.randn(Ng, C), jnp.float32)
    y_global = jnp.asarray(rs.randn(Ng, C), jnp.float32)

    # ---- dense iso-FLOP leg: pure DP over all devices ----------------
    hvd.shutdown()
    dense_mesh = ((2, ndev // 2) if ndev % 2 == 0 and ndev >= 2
                  else (1, ndev))
    hvd.init(devices=devices, mesh_shape=dense_mesh)
    mesh = hvd.mesh()
    Fd = K * F                    # iso-FLOP dense width
    dl = [{"w1": jnp.asarray(np.random.RandomState(70 + i)
                             .randn(C, Fd) * 0.1, jnp.float32),
           "b1": jnp.zeros((Fd,), jnp.float32),
           "w2": jnp.asarray(np.random.RandomState(80 + i)
                             .randn(Fd, C) * 0.1, jnp.float32),
           "b2": jnp.zeros((C,), jnp.float32)} for i in range(L)]

    def dense_stack(p, h):
        import flax.linen as fnn

        for lyr in p:
            h = h + (fnn.gelu(h @ lyr["w1"] + lyr["b1"]) @ lyr["w2"]
                     + lyr["b2"])
        return h

    def dense_spmd(p, xb, yb):
        def loss_fn(pp):
            return jnp.mean((dense_stack(pp, xb) - yb) ** 2)

        loss, g = hvd.value_and_grad(loss_fn)(p)
        loss = hvd.allreduce(loss, op=hvd.Average)
        return loss, jax.tree.map(lambda a, b: a - lr * b, p, g)

    dense_step = jax.jit(hvd.shard_map(
        dense_spmd, mesh=mesh,
        in_specs=(P(), hvd.data_pspec(), hvd.data_pspec()),
        out_specs=(P(), P())))
    dstate = dl
    loss_d, dstate = jax.block_until_ready(
        dense_step(dstate, x_global, y_global))
    t0 = time.perf_counter()
    for _ in range(iters * spc):
        loss_d, dstate = dense_step(dstate, x_global, y_global)
    jax.block_until_ready(loss_d)
    dense_sps = iters * spc / (time.perf_counter() - t0)
    dense_tps = dense_sps * Ng
    log(f"dense leg (d_ff={Fd}): {dense_tps:.0f} tok/s "
        f"({dense_sps:.2f} steps/s), final loss {float(loss_d):.4f}")

    # ---- moe leg on the hvd_ep mesh ----------------------------------
    hvd.shutdown()
    hvd.init(devices=devices, mesh_shape=dmesh, ep_size=E)
    mesh = hvd.mesh()
    assert hvd.ep_size() == E
    stacked = [ep_stack_params(lyr, E) for lyr in layers]
    pspec = [ep_param_pspecs(s) for s in stacked]
    EPALL = (hvd.EP_AXIS,) + hvd.HVD_AXES
    data_spec = P(EPALL)
    splan = default_a2a_plan(hvd.EP_AXIS, quantized=quantized, block=blk,
                             error_feedback=quantized)
    log(f"a2a plan: {splan.encode()}")
    cap = moe_capacity(Nd, E, cf, K)
    if quantized:
        res0 = [moe_ef_residuals(Nd, C, E, cf, K) for _ in range(L)]
        res0 = jax.tree.map(lambda a: jnp.broadcast_to(
            a[None], (ndev,) + a.shape), res0)
        res_spec = jax.tree.map(lambda _: P(EPALL), res0)
    else:
        res0, res_spec = None, None

    def local_params(pt):
        return [{k: (v[0] if k in ("w1", "b1", "w2", "b2") else v)
                 for k, v in lyr.items()} for lyr in pt]

    def moe_forward(lp, xb, res, router_logits=None,
                    capacity_factor=cf):
        h = xb
        new_res = []
        total_load = jnp.zeros((E,), jnp.float32)
        total_drop = 0.0
        for i, lyr in enumerate(lp):
            r = None if res is None else tuple(
                jnp.squeeze(b, 0) for b in res[i])
            y, aux, nr = moe_ffn(
                h, lyr, topk=K, capacity_factor=capacity_factor,
                ep_axis=hvd.EP_AXIS, a2a_plan=splan, residuals=r,
                router_logits=router_logits)
            h = h + y
            total_load = total_load + aux.load
            total_drop = total_drop + aux.dropped_fraction / L
            new_res.append(None if nr is None else tuple(
                b[None] for b in nr))
            aux_last = aux
        return h, (new_res if res is not None else None,
                   total_load, total_drop, aux_last)

    def moe_spmd(pt, xb, yb, res):
        lp = local_params(pt)

        def loss_fn(lpp):
            h, (new_res, load, drop, aux) = moe_forward(lpp, xb, res)
            mse = jnp.mean((h - yb) ** 2)
            loss = (mse + 0.01 * aux.load_balance_loss
                    + 0.001 * aux.z_loss)
            return loss, (new_res, load, drop)

        (loss, (new_res, load, drop)), g = jax.value_and_grad(
            loss_fn, has_aux=True)(lp)
        # Router grads take their explicit ep-mean, expert grads their
        # 1/ep share; BOTH then reduce over the DATA axes only — but in
        # separate bucket sets: expert grads are ep-VARYING (per group)
        # while the router's are ep-invariant, and a shared fused
        # bucket would destroy the router's provable ep replication.
        g = [ep_mean_dense_grads(gl) for gl in g]
        g_exp = [{k: v for k, v in gl.items() if k != "router"}
                 for gl in g]
        g_rt = [gl["router"] for gl in g]
        g_exp = hvd.allreduce_pytree(g_exp, op=hvd.Average,
                                     quantized=quantized or None)
        g_rt = hvd.allreduce_pytree(g_rt, op=hvd.Average,
                                    quantized=quantized or None)
        g = [dict(ge, router=gr) for ge, gr in zip(g_exp, g_rt)]
        new_lp = jax.tree.map(lambda a, b: a - lr * b, lp, g)
        new_pt = [{k: (v[None] if k in ("w1", "b1", "w2", "b2")
                       else v) for k, v in lyr.items()}
                  for lyr in new_lp]
        loss = lax.pmean(loss, EPALL)
        load = lax.psum(load, EPALL)
        drop = lax.pmean(drop, EPALL)
        outs = (loss[None], new_pt, load[None], drop[None])
        if res is not None:
            return outs + (new_res,)
        return outs

    stat_spec = P(EPALL)
    in_specs = (pspec, data_spec, data_spec)
    out_specs = (stat_spec, pspec, stat_spec, stat_spec)
    if quantized:
        in_specs = in_specs + (res_spec,)
        out_specs = out_specs + (res_spec,)
        moe_step = jax.jit(hvd.shard_map(
            moe_spmd, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs))
    else:
        moe_step = jax.jit(hvd.shard_map(
            lambda pt, xb, yb: moe_spmd(pt, xb, yb, None), mesh=mesh,
            in_specs=in_specs, out_specs=out_specs))

    # -- forced-routing parity probe (hard gate) -----------------------
    def parity_spmd(pt, xb):
        lp = local_params(pt)
        n_shard = xb.shape[0]
        forced = jnp.concatenate(
            [jnp.full((n_shard, 1), 1000.0, jnp.float32),
             jnp.zeros((n_shard, E - 1), jnp.float32)], axis=1)
        h, _ = moe_forward(lp, xb, None, router_logits=forced,
                           capacity_factor=float(E))
        return h

    parity_fn = jax.jit(hvd.shard_map(
        parity_spmd, mesh=mesh, in_specs=(pspec, data_spec),
        out_specs=data_spec))
    h_moe = np.asarray(parity_fn(stacked, x_global))
    h_ref = np.asarray(x_global)
    for lyr in layers:
        import flax.linen as fnn

        act = np.asarray(fnn.gelu(
            h_ref @ np.asarray(lyr["w1"][0]) + np.asarray(lyr["b1"][0])))
        h_ref = h_ref + act @ np.asarray(lyr["w2"][0]) \
            + np.asarray(lyr["b2"][0])
    denom = max(1e-9, float(np.abs(h_ref).max()))
    parity_err = float(np.abs(h_moe - h_ref).max()) / denom
    tol = 5e-2 if quantized else 1e-5
    log(f"parity probe (forced expert-0 routing): max rel err "
        f"{parity_err:.2e} (tol {tol})")
    if parity_err > tol:
        raise SystemExit(
            f"moe parity FAILED: forced-routing MoE vs dense expert-0 "
            f"rel err {parity_err:.2e} > {tol}")

    # -- timed run -----------------------------------------------------
    carry = [stacked, res0]

    def drive(xb, yb):
        if quantized:
            loss, pt, load, drop, res = moe_step(
                carry[0], xb, yb, carry[1])
            carry[0], carry[1] = pt, res
        else:
            loss, pt, load, drop = moe_step(carry[0], xb, yb)
            carry[0] = pt
        return loss, load, drop

    fn_snap0 = compile_snapshot()
    t_fn0 = time.perf_counter()
    with record_wire_stats() as wire:
        loss0, load, drop = jax.block_until_ready(
            drive(x_global, y_global))
    ttfs_ms = (time.perf_counter() - t_fn0) * 1e3
    expert_tokens = np.zeros((E,), np.float64)
    t0 = time.perf_counter()
    for _ in range(iters * spc):
        loss_m, load, drop = drive(x_global, y_global)
        expert_tokens += np.asarray(load).reshape(-1, E).sum(0) / ndev
    jax.block_until_ready(loss_m)
    moe_sps = iters * spc / (time.perf_counter() - t0)
    moe_tps = moe_sps * Ng
    dropped_frac = float(np.asarray(drop).reshape(-1)[0])
    from horovod_tpu.monitor import registry as _metrics

    for e in range(E):
        _metrics.counter("moe.expert_tokens", expert=str(e)).inc(
            float(expert_tokens[e]))
    log(f"moe leg: {moe_tps:.0f} tok/s ({moe_sps:.2f} steps/s), final "
        f"loss {float(np.asarray(loss_m).reshape(-1)[0]):.4f}, dropped "
        f"{dropped_frac:.4f}, expert load {expert_tokens.round(1)}")

    # -- a2a drift pair + straggler attribution ------------------------
    buf_bytes = E * cap * C * 4.0
    priced = hvd_plan.price_a2a(
        splan, buf_bytes, ep=E, issues=max(1, wire.a2a_calls),
        mesh_shape=dmesh,
        model=hvd_plan.get_cost_model(mesh_shape=dmesh))
    ici_g, dcn_g, pod_g = bench_gbps()
    hop = splan.legs[0].level
    hop_gbps = {"ici": ici_g, "dcn": dcn_g, "pod": pod_g}[hop]
    a2a_ms_modeled = wire.a2a_bytes / (hop_gbps * 1e9) * 1e3
    drift = (abs(priced["modeled_ms"] - a2a_ms_modeled)
             / max(1e-9, a2a_ms_modeled))
    log(f"a2a wire: accounted {wire.a2a_bytes:.0f} B "
        f"({a2a_ms_modeled:.4f} ms modeled, {wire.a2a_calls} exchanges) "
        f"vs predicted {priced['wire_bytes']:.0f} B "
        f"({priced['modeled_ms']:.4f} ms); drift {drift:.4f}")

    from horovod_tpu import monitor as _monitor

    moe_step_ms = 1e3 / max(1e-9, moe_sps)
    det = _monitor.straggler_detector()
    det.record_phase("wire.a2a", min(moe_step_ms, a2a_ms_modeled))
    det.record_phase("compute",
                     max(0.0, moe_step_ms - a2a_ms_modeled))
    det.end_step()

    result = {
        "metric": f"moe{E}_tokens_per_sec",
        "value": round(moe_tps, 1),
        "unit": "tokens/sec",
        "platform": platform,
        "chips": ndev,
        "moe": {
            "experts": E, "topk": K, "capacity_factor": cf,
            "capacity": cap, "layers": L,
            "data_mesh": mesh_shape_str(dmesh),
            "quantized": quantized, "a2a_plan": splan.encode(),
        },
        "parity_rel_err": parity_err,
        "parity_tol": tol,
        "dropped_token_fraction": round(dropped_frac, 6),
        "expert_load": {str(e): round(float(expert_tokens[e]), 1)
                        for e in range(E)},
        "dense_tokens_per_sec": round(dense_tps, 1),
        "throughput_delta": round(moe_tps / max(1e-9, dense_tps), 4),
        "wire_bytes_ici": wire.ici_bytes,
        "wire_bytes_dcn": wire.dcn_bytes,
        "wire_bytes_pod": wire.pod_bytes,
        "a2a_bytes": wire.a2a_bytes,
        "a2a_bytes_fp": wire.a2a_bytes_fp,
        "a2a_calls": wire.a2a_calls,
        "wire_ms": {
            "predicted": round(priced["modeled_ms"], 4),
            "predicted_total": round(priced["predicted_ms"], 4),
            "modeled": round(a2a_ms_modeled, 4),
            "model": priced["model"],
        },
        **compile_fields(fn_snap0, ttfs_ms),
        "metrics_snapshot": metrics_snapshot(
            prefixes=("comm.", "step.", "moe.", "straggler.", "link.",
                      "compile.")),
    }
    print(json.dumps(result))
    return result


def run_serve(args, devices, platform, mesh_shape):
    """The ``--serve`` leg: a continuous-batching generation trace.

    Opens the inference scenario family (docs/serving.md) on the same
    stack the training legs measure: a :class:`ReplicaSet` partitions the
    visible chips into tensor-parallel replica groups, a Poisson arrival
    trace feeds the shared queue, and mid-trace the set resizes (scale
    down, then back up) with in-flight requests drained into the queue —
    the acceptance bar is zero dropped requests. Emits ONE JSON line with
    tokens/sec (all prefill+decode work), goodput (generated tokens of
    COMPLETED requests per second — replayed work does not count), and
    p50/p99 request latency, plus a decode-vs-full-context logits parity
    probe so the number is backed by a correctness check."""
    import jax
    import numpy as np
    import jax.numpy as jnp

    import horovod_tpu as hvd
    from horovod_tpu.models import GPT, gpt_tiny
    from horovod_tpu.serve import (PageConfig, PoissonTrace, ReplicaSet,
                                   kv_cache as kvlib)

    hvd.shutdown()
    hvd.init(devices=devices, mesh_shape=mesh_shape)
    n_chips = hvd.size()

    # Serve-scale model: gpt_tiny with 8 heads so every even partition of
    # an 8-chip mesh gives a valid tp degree; fp32 on CPU meshes (bf16
    # emulation is slow there), bf16 on real accelerators.
    dtype = jnp.float32 if platform == "cpu" else jnp.bfloat16
    cfg = gpt_tiny(num_heads=8, dtype=dtype)
    params = GPT(cfg).init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, 8), jnp.int32))["params"]

    page_size = args.serve_page_size
    max_slots = args.serve_max_slots
    p_lo, p_hi = args.serve_prompt_len
    n_lo, n_hi = args.serve_max_new
    shared_len = getattr(args, "shared_prefix_len", 0) or 0
    spec_k = getattr(args, "spec_decode", 0) or 0
    disagg = getattr(args, "disagg", None)
    pages_per_slot = -(-(shared_len + p_hi + n_hi + 1) // page_size)
    # Pool sized for ~75% occupancy at full slots: admission pressure is
    # real (the scheduler's page-availability policy actually gates) but
    # a lone big request can always run.
    num_pages = 1 + max(pages_per_slot,
                        int(0.75 * max_slots * pages_per_slot))
    pc = PageConfig(num_pages=num_pages, page_size=page_size,
                    max_slots=max_slots, pages_per_slot=pages_per_slot,
                    num_layers=cfg.num_layers, num_heads=cfg.num_heads,
                    head_dim=cfg.d_model // cfg.num_heads)

    # Parity probe: one prompt decoded token-by-token through the cache
    # must reproduce the full-context logits (docs/serving.md "page
    # math"; tests/test_serve.py holds the tight tier-1 version).
    rs_np = np.random.RandomState(7)
    probe = rs_np.randint(2, cfg.vocab_size, size=12)
    pcache = kvlib.init_cache(pc)
    alloc = kvlib.PageAllocator(pc.num_pages)
    pages = alloc.alloc("probe", pc.pages_for(len(probe)))
    table = np.array(pcache.page_table)
    table[0, :len(pages)] = pages
    pcache = pcache._replace(page_table=jnp.asarray(table))
    pstep = jax.jit(lambda tok, c: GPT(cfg).apply(
        {"params": params}, tok, cache=c,
        active=jnp.asarray([True] + [False] * (max_slots - 1))))
    rows = []
    for t in probe:
        tok = jnp.asarray([int(t)] + [0] * (max_slots - 1))
        logits, pcache = pstep(tok, pcache)
        rows.append(np.asarray(logits[0], np.float32))
    full = np.asarray(GPT(cfg).apply(
        {"params": params}, jnp.asarray(probe)[None])[0], np.float32)
    parity_err = float(np.max(np.abs(np.stack(rows) - full)))
    tol = 2e-4 if dtype == jnp.float32 else 5e-2
    log(f"decode-vs-full parity: max |Δlogit| {parity_err:.2e} "
        f"(tol {tol:g})")
    if parity_err > tol:
        raise SystemExit(f"decode/full-context parity FAILED: "
                         f"{parity_err} > {tol}")

    n_replicas = (sum(disagg) if disagg else args.serve_replicas)
    if n_chips % max(1, n_replicas):
        what = (f"--disagg {disagg[0]}:{disagg[1]}" if disagg
                else f"--serve-replicas {n_replicas}")
        raise SystemExit(f"{what} does not partition {n_chips} chips")
    trace = PoissonTrace(rate=args.serve_rate,
                         num_requests=args.serve_requests,
                         seed=12345, prompt_len=(p_lo, p_hi),
                         max_new_tokens=(n_lo, n_hi),
                         vocab_size=cfg.vocab_size, eos_id=1)
    specs = [(list(r.prompt), r.max_new_tokens, r.arrival_time)
             for r in trace]
    total = len(specs)
    if shared_len:
        # Multi-tenant shared-prefix trace (docs/serving.md): a few
        # tenants each pin one fixed prefix; request i joins tenant
        # i % T, so every tenant's later arrivals can hit the prefix
        # pages its earlier requests registered.
        n_tenants = max(1, min(3, total // 4))
        rs_pre = np.random.RandomState(99)
        prefixes = []
        for _ in range(n_tenants):
            toks = rs_pre.randint(0, cfg.vocab_size, size=shared_len)
            toks = np.where(toks == 1, 2, toks)
            prefixes.append([int(t) for t in toks])
        specs = [(prefixes[i % n_tenants] + p, n, a)
                 for i, (p, n, a) in enumerate(specs)]

    from horovod_tpu.serve import Request

    def mkreqs():
        # Fresh Request objects per leg: engines mutate them in place.
        return [Request(req_id=i, prompt=list(p), max_new_tokens=n,
                        arrival_time=a)
                for i, (p, n, a) in enumerate(specs)]

    # Manual trace loop so the elastic resize triggers on PROGRESS (a
    # third / two-thirds of the trace complete), not a step count that
    # depends on machine speed.
    import time as _time

    def _drain(rset, *, resize=False):
        resize_down_at = max(1, total // 3)
        resize_up_at = max(2, (2 * total) // 3)
        did_down = did_up = False
        down_to = max(1, n_replicas // 2)
        t0 = _time.monotonic()
        steps = 0
        while rset.has_work:
            now = _time.monotonic() - t0
            done = (len(rset.stats.completed)
                    + sum(len(e.stats.completed) for e in rset.engines))
            # Background-precompiled resizes (docs/compile.md): the
            # request starts a host thread warming the TARGET geometry's
            # executables; serving keeps stepping and the drain only
            # happens — inside step_all — once they are ready.
            if resize and not did_down and done >= resize_down_at \
                    and n_replicas > 1:
                if rset.request_resize(down_to):
                    did_down = True
                    log(f"resize requested: {n_replicas} -> {down_to} "
                        f"replicas at {done}/{total} complete "
                        f"(precompiling target in the background)")
            if resize and did_down and not did_up \
                    and done >= resize_up_at and n_replicas > 1 \
                    and rset.resize_events:
                if rset.request_resize(n_replicas):
                    did_up = True
                    log(f"resize requested: back to {n_replicas} "
                        f"replicas at {done}/{total} complete")
            if rset.step_all(now) == 0:
                _time.sleep(1e-3)
            steps += 1
            if steps > 200_000:
                raise SystemExit("serve trace did not drain")
        # A resize requested near the end of the trace may still be
        # precompiling when the queue empties; land it so the A/B gate
        # always sees both background events.
        while resize and rset.resize_pending:
            if rset.maybe_finish_resize(_time.monotonic() - t0) is None:
                _time.sleep(1e-3)
        wall = _time.monotonic() - t0
        stats = rset.stats
        for eng in rset.engines:
            stats.merge(eng.stats)
        stats.wall_time = wall
        return stats, wall

    def _cold_resize_stall(rset):
        """Cold-rebuild baseline for the resize A/B gate: disable every
        cache layer — the framework executable registry (memory + disk,
        via HOROVOD_COMPILE_CACHE=0) and XLA's persistent cache (turned
        off, not re-pointed: the directory is part of what is cached) —
        then resize down and back up with warm=False so the drain window
        pays the full trace+compile, exactly what an elastic resize cost
        before background precompile existed."""
        from horovod_tpu import compile as xc

        down_to = max(1, n_replicas // 2)
        prev_env = os.environ.get("HOROVOD_COMPILE_CACHE")
        os.environ["HOROVOD_COMPILE_CACHE"] = "0"
        try:
            with xc.persistent_cache_disabled():
                xc.clear_memory()
                rset.resize(down_to, warm=False)
                xc.clear_memory()
                rset.resize(n_replicas, warm=False)
            return max(e["resize_stall_ms"]
                       for e in rset.resize_events[-2:])
        finally:
            if prev_env is None:
                os.environ.pop("HOROVOD_COMPILE_CACHE", None)
            else:
                os.environ["HOROVOD_COMPILE_CACHE"] = prev_env

    from horovod_tpu.serve.engine import ServeStats

    def _warm(rset, ttfs_box=None, t0_build=None):
        """Absorb every engine's compiles (the W=1 step and, with spec
        on, the W=spec_k+1 window; for decode replicas the migrated-KV
        admission path) before the timed trace, then zero the stats so
        both A/B legs measure steady state only. ``ttfs_box`` receives
        ``ttfs_ms``: ReplicaSet construction start → first generated
        token ready (the serve-side time-to-first-step)."""
        for i in range(2 * len(rset.engines)):
            rset.submit(Request(req_id=1_000_000 + i,
                                prompt=[2 + (i % 7)] * page_size,
                                max_new_tokens=2, arrival_time=0.0))
        steps = 0
        while rset.has_work:
            moved = rset.step_all(float(steps))
            if ttfs_box is not None and "ttfs_ms" not in ttfs_box \
                    and moved:
                ttfs_box["ttfs_ms"] = round(
                    (_time.perf_counter() - t0_build) * 1e3, 3)
            if moved == 0:
                _time.sleep(1e-3)
            steps += 1
            if steps > 50_000:
                raise SystemExit("serve warmup did not drain")
        rset.stats = ServeStats()
        for eng in rset.engines:
            eng.stats = ServeStats()
            eng._spec_proposed = eng._spec_accepted = 0
            cache = eng.prefix_cache
            if cache is not None:
                cache.lookups = cache.hits = cache.hit_tokens = 0
                cache.insertions = cache.evictions = 0
        if getattr(rset, "kv_migrations", 0):
            rset.kv_migrations = 0
            rset.kv_migration_bytes = 0.0
            rset.kv_migration_fp_bytes = 0.0
            rset.kv_stall_steps = 0
            rset.migration_events = []

    base_stats = base_out = None
    if disagg:
        # Symmetric baseline FIRST, over the very same trace — the
        # disagg leg's acceptance bar is goodput >= this and greedy
        # outputs bit-identical to it (no mid-trace resize on either
        # leg: a resize folds progress into prompts, which legitimately
        # changes the generated continuations).
        base = ReplicaSet(cfg, params, pc, devices=devices,
                          n_replicas=n_replicas, eos_id=1)
        _warm(base)
        for req in mkreqs():
            base.submit(req)
        base_stats, base_wall = _drain(base)
        base_out = {r.req_id: list(r.generated)
                    for r in base_stats.completed}
        blat = base_stats.latency_percentiles()
        log(f"baseline (symmetric x{n_replicas}): "
            f"goodput {base_stats.goodput_tokens_per_sec():.1f} tok/s | "
            f"p99 {blat['p99'] * 1e3:.0f} ms | "
            f"{len(base_stats.completed)}/{total} completed")
        # A DCN-class mesh shape for the migration hop: the prefill and
        # decode halves sit across the slower boundary, so the wire plan
        # legalizes the blockwise-int8(+EF) compressed leg.
        kv_shape = (max(1, n_chips // 2), 2) if n_chips > 1 else (1, 1)
        fn_snap0 = compile_snapshot()
        t0_build = _time.perf_counter()
        rset = ReplicaSet(cfg, params, pc, devices=devices,
                          n_replicas=n_replicas, eos_id=1,
                          disagg=disagg,
                          prefix_cache=shared_len > 0,
                          spec_k=spec_k,
                          kv_migrate_quantized=n_chips > 1,
                          kv_mesh_shape=kv_shape)
        log(f"disagg {disagg[0]}P:{disagg[1]}D | kv plan "
            f"{rset.kv_plan.encode()} | prefix_cache={shared_len > 0} "
            f"spec_k={spec_k}")
    else:
        fn_snap0 = compile_snapshot()
        t0_build = _time.perf_counter()
        rset = ReplicaSet(cfg, params, pc, devices=devices,
                          n_replicas=n_replicas, eos_id=1)
    # TTFS here is serve-flavoured: measured ReplicaSet construction
    # (which AOT-precompiles every engine's step from the executable
    # cache — docs/compile.md) through the first generated token.
    ttfs_box = {}
    _warm(rset, ttfs_box, t0_build)
    for req in mkreqs():
        rset.submit(req)
    stats, wall = _drain(rset, resize=bool(args.serve_resize)
                         and not disagg)

    completed = len(stats.completed)
    dropped = total - completed
    lat = stats.latency_percentiles()
    log(f"serve: {completed}/{total} requests in {wall:.2f}s | "
        f"{stats.tokens_per_sec():.1f} tok/s processed, "
        f"goodput {stats.goodput_tokens_per_sec():.1f} tok/s | "
        f"p50 {lat['p50'] * 1e3:.0f} ms p99 {lat['p99'] * 1e3:.0f} ms | "
        f"{stats.preemptions} preemptions, "
        f"{len(rset.resize_events)} resizes")
    if dropped:
        raise SystemExit(f"serve trace DROPPED {dropped} requests")
    if disagg and len(base_stats.completed) != total:
        raise SystemExit(
            f"baseline leg DROPPED "
            f"{total - len(base_stats.completed)} requests")
    spec_parity_ok = None
    if disagg:
        # Greedy bit-exactness: KV migration (int8+EF residual pass) and
        # speculative verification must not change a single token.
        dis_out = {r.req_id: list(r.generated) for r in stats.completed}
        spec_parity_ok = dis_out == base_out
        if not spec_parity_ok:
            bad = sorted(i for i in dis_out
                         if dis_out[i] != base_out.get(i))
            raise SystemExit(
                f"disagg outputs DIVERGED from the symmetric baseline "
                f"on request(s) {bad[:8]} — greedy spec decode + KV "
                f"migration must be bit-identical")
        log("parity: disagg outputs bit-identical to the symmetric "
            "baseline")
    # The resize A/B gate: every elastic resize in the measured trace was
    # background-precompiled, so its stall (drain -> rebuilt, serving
    # again) must beat a cold rebuild of the SAME geometry flip with all
    # compilation caches defeated. Snapshot the trace's events first —
    # the cold baseline appends two more.
    resize_events = [dict(e) for e in rset.resize_events]
    resize_cmp = {}
    if args.serve_resize and not disagg and n_replicas > 1:
        bg_events = [e for e in resize_events if e.get("background")]
        if not bg_events:
            raise SystemExit(
                "serve resize leg produced no background-precompiled "
                "resize events — request_resize never completed")
        bg_stall = max(e["resize_stall_ms"] for e in bg_events)
        cold_stall = _cold_resize_stall(rset)
        log(f"resize stall: background-precompiled "
            f"{bg_stall:.1f} ms (worst of {len(bg_events)}) vs "
            f"cold rebuild {cold_stall:.1f} ms")
        if not bg_stall < cold_stall:
            raise SystemExit(
                f"background-precompiled resize stall {bg_stall:.1f} ms "
                f"is NOT below the cold-rebuild baseline "
                f"{cold_stall:.1f} ms")
        resize_cmp = {
            "resize_stall_ms_bg": round(bg_stall, 3),
            "resize_stall_ms_cold": round(cold_stall, 3),
            "resize_stall_speedup": round(cold_stall / bg_stall, 3)
                if bg_stall else None,
        }
    # Unified observability: publish the trace-level gauges the engine
    # counters cannot derive (goodput is completed-requests-only), then
    # embed the serve+comm snapshot in the JSON artifact.
    from horovod_tpu import monitor

    monitor.metrics().gauge("serve.goodput_tokens_per_sec").set(
        stats.goodput_tokens_per_sec())
    monitor.metrics().gauge("serve.tokens_per_sec").set(
        stats.tokens_per_sec())
    extra = {}
    if disagg:
        blat = base_stats.latency_percentiles()
        base_goodput = base_stats.goodput_tokens_per_sec()
        predicted = sum(e["predicted_bytes"]
                        for e in rset.migration_events)
        pcaches = [e.prefix_cache for e in rset.prefill_engines
                   if e.prefix_cache is not None]
        lookups = sum(c.lookups for c in pcaches)
        hits = sum(c.hits for c in pcaches)
        proposed = sum(e._spec_proposed for e in rset.decode_engines)
        accepted = sum(e._spec_accepted for e in rset.decode_engines)
        extra = {
            "disagg": f"{disagg[0]}:{disagg[1]}",
            "prefill_replicas": disagg[0],
            "decode_replicas": disagg[1],
            "kv_plan": rset.kv_plan.encode(),
            "shared_prefix_len": shared_len,
            "spec_decode_k": spec_k,
            "baseline_goodput_tokens_per_sec": round(base_goodput, 2),
            "baseline_tokens_per_sec": round(
                base_stats.tokens_per_sec(), 2),
            "baseline_latency_p50_ms": round(blat["p50"] * 1e3, 2),
            "baseline_latency_p99_ms": round(blat["p99"] * 1e3, 2),
            "goodput_vs_baseline": round(
                stats.goodput_tokens_per_sec() / base_goodput, 4)
                if base_goodput else None,
            "kv_migrations": rset.kv_migrations,
            "kv_migration_bytes": rset.kv_migration_bytes,
            "kv_migration_fp_bytes": rset.kv_migration_fp_bytes,
            "kv_predicted_bytes": predicted,
            "kv_bytes_drift": rset.kv_migration_bytes - predicted,
            "kv_predicted_ms": round(sum(
                e["predicted_ms"] for e in rset.migration_events), 4),
            "kv_modeled_ms": round(sum(
                e["modeled_ms"] for e in rset.migration_events), 4),
            "kv_stall_steps": rset.kv_stall_steps,
            "prefix_lookups": lookups,
            "prefix_hits": hits,
            "prefix_hit_rate": round(hits / lookups, 4) if lookups
                else 0.0,
            "prefix_hit_tokens": sum(c.hit_tokens for c in pcaches),
            "spec_proposed": proposed,
            "spec_accepted": accepted,
            "spec_acceptance_rate": round(accepted / proposed, 4)
                if proposed else 0.0,
            "spec_parity_ok": spec_parity_ok,
        }
    print(json.dumps({
        "metric": "gpt_serve_goodput_tokens_per_sec",
        "value": round(stats.goodput_tokens_per_sec(), 2),
        "unit": "tokens/sec",
        "vs_baseline": (extra.get("goodput_vs_baseline")
                        if disagg else None),
        "platform": platform,
        "device_kind": getattr(devices[0], "device_kind", "unknown"),
        "chips": n_chips,
        "mesh_shape": (mesh_shape_str(mesh_shape)
                       if mesh_shape else None),
        "tokens_per_sec": round(stats.tokens_per_sec(), 2),
        "goodput_tokens_per_sec": round(stats.goodput_tokens_per_sec(), 2),
        "latency_p50_ms": round(lat["p50"] * 1e3, 2),
        "latency_p99_ms": round(lat["p99"] * 1e3, 2),
        "requests": total,
        "requests_completed": completed,
        "requests_dropped": dropped,
        "arrival_rate_per_sec": args.serve_rate,
        "replicas": n_replicas,
        "resize_events": resize_events,
        **resize_cmp,
        **compile_fields(fn_snap0, ttfs_box.get("ttfs_ms")),
        "engine_steps": stats.steps,
        "prefill_tokens": stats.prefill_tokens,
        "decode_tokens": stats.decode_tokens,
        "preemptions": stats.preemptions,
        "page_size": page_size,
        "num_pages": num_pages,
        "max_slots": max_slots,
        "decode_parity_max_err": parity_err,
        **extra,
        "metrics_snapshot": metrics_snapshot(
            prefixes=("serve.", "comm.", "compile.")),
    }), flush=True)


def run_autotune_session(args, devices, platform, mesh_shape):
    """Run the online Bayesian tuning session on the real bench workload
    (``hvd.autotune_session``; each trial recompiles the step with a
    candidate TunedParams and times a scoring window). Returns the
    AutotuneResult whose ``.params`` the tuned A/B leg measures."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    import horovod_tpu as hvd

    hvd.shutdown()
    hvd.init(devices=devices, mesh_shape=mesh_shape)
    n_chips = hvd.size()
    global_batch = args.batch_size * n_chips
    log(f"autotune session: world={n_chips} global_batch={global_batch}")
    wl = build_workload(args, global_batch)
    loss_fn = wl["loss_fn"]
    compression = (hvd.Compression.bf16 if args.fp16_allreduce
                   else hvd.Compression.none)
    mesh = hvd.mesh()
    rep = NamedSharding(mesh, P())
    data_sh = hvd.data_sharding()
    images = jax.device_put(wl["images"], data_sh)
    labels = jax.device_put(wl["labels"], data_sh)

    def make_step(tuned):
        tx = hvd.DistributedOptimizer(optax.sgd(0.01, momentum=0.9),
                                      compression=compression,
                                      tuned_params=tuned)
        state = {
            "params": jax.device_put(wl["params"], rep),
            "bs": jax.device_put(wl["batch_stats"], rep),
            "opt": jax.device_put(tx.init(wl["params"]), rep),
        }

        # Same reduce-in-optimizer structure as the measured legs: the
        # fused bucket wire inside tx.update is the gradient collective
        # the tunables steer.
        def spmd(p, bs, s, xb, yb):
            (loss, nbs), grads = hvd.value_and_grad(
                loss_fn, has_aux=True, reduce=False)(p, bs, xb, yb)
            nbs = hvd.allreduce_pytree(nbs, op=hvd.Average)
            updates, ns = tx.update(grads, s, p)
            return optax.apply_updates(p, updates), nbs, ns, \
                hvd.allreduce(loss)

        train = jax.jit(hvd.shard_map(
            spmd, mesh=mesh,
            in_specs=(P(), P(), P(), hvd.data_pspec(), hvd.data_pspec()),
            out_specs=(P(), P(), P(), P())))

        def step():
            state["params"], state["bs"], state["opt"], loss = train(
                state["params"], state["bs"], state["opt"], images, labels)
            return loss

        return step

    result = hvd.autotune_session(
        make_step, cache_key=wl["params"], enabled=True,
        warm_start=args.autotune_warm_start)
    if result.shortlist:
        log("cost-model shortlist (docs/cost-model.md):")
        for row in result.shortlist:
            log(f"  {row['predicted_ms']:9.4f} ms  {row['plan']}  "
                f"thr={row['params']['fusion_threshold_bytes'] >> 20}MiB")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["resnet50", "resnet18", "gpt"],
                    default="resnet50",
                    help="resnet50 = the reference's headline benchmark "
                         "(HBM-bound on TPU); resnet18 = small CNN for "
                         "CPU-mesh smoke runs; gpt = GPT-124M, matmul-"
                         "dominated, shows the framework's MFU ceiling "
                         "without ResNet's bandwidth wall")
    ap.add_argument("--batch-size", type=int, default=None,
                    help="per-chip batch size (default: 128 images for "
                         "resnet50/18 — reference convention is 64, "
                         "docs/benchmarks.rst:27-43, 128 keeps the MXU "
                         "fed on v5e; 8 sequences for gpt)")
    ap.add_argument("--image-size", type=int, default=224,
                    help="square image side for resnet models (small "
                         "values speed up CPU smoke runs)")
    ap.add_argument("--seq-len", type=int, default=1024,
                    help="sequence length for --model gpt "
                         "(default 1024)")
    ap.add_argument("--vocab-size", type=int, default=32000,
                    help="GPT vocabulary size (the fused-vs-dense LM loss "
                         "crossover depends on it)")
    ap.add_argument("--gpt-scale", choices=["124m", "350m"],
                    default="124m",
                    help="GPT size: 124m (12L/768d) or 350m (24L/1024d)")
    ap.add_argument("--attention", choices=["flash", "dense"],
                    default="flash",
                    help="GPT attention path: flash = Pallas kernel "
                         "(no [T,T] HBM round-trip), dense = reference "
                         "einsum attention")
    ap.add_argument("--fused-ln", action="store_true",
                    help="fused residual+LayerNorm Pallas kernel for each "
                         "block's second LN (GPT; MFU A/B lever)")
    ap.add_argument("--remat", action="store_true",
                    help="checkpoint each GPT block (trade FLOPs for HBM; "
                         "lets bigger --batch-size fit)")
    ap.add_argument("--lm-loss", choices=["auto", "fused", "dense"],
                    default="auto",
                    help="GPT LM-head loss. auto (default) = dense while "
                         "the step's fp32 logits fit the measured HBM "
                         "budget, fused beyond (lm_head_loss dispatch — "
                         "dense measured faster at EVERY vocab that "
                         "compiles on v5e, fused extends the envelope); "
                         "dense / fused force a path")
    ap.add_argument("--chips", type=int, default=None,
                    help="run on the first N visible chips only "
                         "(default: all visible chips)")
    ap.add_argument("--scaling", default=None, metavar="N1,N2,...",
                    help="weak-scaling sweep: run the same per-chip batch "
                         "over each world size (e.g. 1,2,4,8) and report "
                         "per-chip efficiency vs the smallest; the JSON "
                         "line becomes the scaling-efficiency metric")
    ap.add_argument("--platform", choices=["auto", "cpu"], default="auto",
                    help="auto = the accelerator JAX finds, or exit "
                         "non-zero when there is none; "
                         "cpu = force an N-virtual-device CPU mesh "
                         "(--cpu-devices) for smoke-testing the scaling "
                         "sweep without pod hardware")
    ap.add_argument("--cpu-devices", type=int, default=8,
                    help="virtual device count for --platform cpu")
    ap.add_argument("--num-warmup", type=int, default=5)
    ap.add_argument("--num-iters", type=int, default=10,
                    help="timing rounds (reference: 10)")
    ap.add_argument("--num-batches-per-iter", type=int, default=10)
    ap.add_argument("--fp16-allreduce", action="store_true",
                    help="bf16 wire compression (reference flag name kept)")
    ap.add_argument("--quantized", action="store_true",
                    help="A/B the blockwise-int8 quantized allreduce "
                         "(EQuARX-style int8+scales on the DCN hop, error "
                         "feedback in the optimizer): runs a baseline leg "
                         "and a quantized leg over the same step structure "
                         "and reports wire-bytes and throughput deltas")
    ap.add_argument("--zero", action="store_true",
                    help="A/B the ZeRO-1 sharded optimizer (reduce-scatter "
                         "grads, per-rank optax update on 1/world flat "
                         "shards, all-gather updates): runs a replicated "
                         "leg and a sharded leg over the same fused "
                         "reduce-in-optimizer step and reports "
                         "throughput_delta, opt_state_bytes_per_rank and "
                         "wire bytes (docs/zero.md)")
    ap.add_argument("--zero-stage", type=int, choices=(1, 2, 3),
                    default=None,
                    help="A/B one explicit ZeRO stage against the "
                         "replicated baseline (docs/zero.md): stage 1 = "
                         "optimizer-state sharding (classic full-grad "
                         "accumulator), 2 = + gradient-accumulation "
                         "shards, 3 = + parameter shards with just-in-"
                         "time per-bucket gather in the forward. "
                         "Reports param+grad+state bytes-per-rank, an "
                         "async-checkpoint stall probe "
                         "(docs/checkpoint.md), and a stage-parity "
                         "probe (1/2/3 side-by-side in one program, "
                         "bit-identical)")
    ap.add_argument("--fused", action="store_true",
                    help="A/B the fused compute-collective Pallas "
                         "kernels (docs/fused-kernels.md) against the "
                         "plan-compiled unfused wire on the synthetic "
                         "fusion-pair workload; composes with "
                         "--zero-stage (default 3 here), --quantized "
                         "(Pallas int8 legs) and --overlap")
    ap.add_argument("--quantized-pod", action="store_true",
                    help="--dump-plan only: show the 3-level tree plan "
                         "with the pod hop as the blockwise-int8 rs+ag "
                         "pair (implies hierarchical; "
                         "HOROVOD_QUANTIZED_POD at runtime)")
    ap.add_argument("--pp", type=int, default=0, metavar="STAGES",
                    help="pipeline-parallel A/B leg: dense DP vs a "
                         "dedicated hvd_pp mesh of STAGES stages under "
                         "the --pp-schedule schedule, inter-stage "
                         "activation hops as wire-plan send legs; "
                         "composes --zero-stage/--quantized/--overlap "
                         "(docs/pipeline.md)")
    ap.add_argument("--pp-microbatches", type=int, default=8,
                    help="microbatches per pipelined step (pow2; must "
                         "divide by --pp for the interleaved schedule)")
    ap.add_argument("--pp-interleave", type=int, default=2,
                    help="virtual stages per rank (interleaved-1F1B "
                         "degree; 1 = plain 1F1B chunking)")
    ap.add_argument("--pp-schedule", default="interleaved_1f1b",
                    choices=["gpipe", "1f1b", "interleaved_1f1b", "zb1"],
                    help="pipeline schedule family member "
                         "(docs/pipeline.md; zb1 = zero-bubble B/W "
                         "split — the leg then A/Bs it against "
                         "interleaved-1F1B on the same geometry)")
    ap.add_argument("--moe", type=int, default=0, metavar="EXPERTS",
                    help="MoE A/B leg (docs/moe.md): expert-parallel "
                         "top-k MoE over a dedicated hvd_ep mesh axis "
                         "of EXPERTS groups vs an iso-FLOP dense FFN "
                         "stack on the same devices; --quantized rides "
                         "the dispatch/combine a2a wire blockwise-int8 "
                         "with error feedback")
    ap.add_argument("--moe-topk", type=int, default=2,
                    help="experts per token (top-k gating; default 2)")
    ap.add_argument("--moe-capacity", type=float, default=1.25,
                    help="dispatch capacity factor (default 1.25)")
    ap.add_argument("--moe-layers", type=int, default=2,
                    help="MoE FFN layers in the bench stack (default 2)")
    ap.add_argument("--overlap", action="store_true",
                    help="A/B the overlapped gradient reduction "
                         "(HOROVOD_OVERLAP: reverse-layer bucket "
                         "streaming + async-collective/LHS flags, "
                         "docs/overlap.md): runs a synchronous leg and "
                         "an overlap leg over the same reduce-in-"
                         "optimizer step and reports throughput_delta, "
                         "comm_hidden_fraction, and a "
                         "step_time_breakdown")
    ap.add_argument("--autotune-warm-start", type=int, default=5,
                    metavar="K",
                    help="seed the tuning session's GP with the top-K "
                         "cost-model-priced plans from the analytic "
                         "shortlist (docs/cost-model.md) and shrink the "
                         "trial budget to K+4 windows; 0 = the cold "
                         "7-dim search")
    ap.add_argument("--autotune", action="store_true",
                    help="run the online Bayesian tuning session "
                         "(hvd.autotune_session: GP/EI over fusion "
                         "threshold + hierarchical allreduce, recompile "
                         "per trial, warm-start cache), then A/B the "
                         "frozen winner against the default knobs; the "
                         "JSON line carries tuned_params + the trial "
                         "history")
    ap.add_argument("--serve", action="store_true",
                    help="continuous-batching generation trace "
                         "(docs/serving.md): Poisson arrivals into "
                         "tensor-parallel replica groups with a paged "
                         "KV cache, one elastic resize down and back up "
                         "mid-trace; reports tokens/sec, goodput and "
                         "p50/p99 latency plus a decode-vs-full-context "
                         "logits parity probe")
    ap.add_argument("--serve-rate", type=float, default=20.0,
                    help="Poisson arrival rate, requests/sec")
    ap.add_argument("--serve-requests", type=int, default=40,
                    help="trace length in requests")
    ap.add_argument("--serve-replicas", type=int, default=2,
                    help="replica groups at trace start (must partition "
                         "the chip count; tp degree = chips/replicas)")
    ap.add_argument("--serve-prompt-len", default="4,16",
                    metavar="LO,HI", help="prompt length range")
    ap.add_argument("--serve-max-new", default="4,16",
                    metavar="LO,HI", help="generation budget range")
    ap.add_argument("--serve-page-size", type=int, default=4,
                    help="KV-cache page size in tokens")
    ap.add_argument("--serve-max-slots", type=int, default=8,
                    help="concurrent sequences per replica")
    ap.add_argument("--serve-resize", type=int, default=1,
                    help="1 (default) = one elastic resize down and back "
                         "up mid-trace; 0 = fixed replica count")
    ap.add_argument("--disagg", default=None, metavar="P:D",
                    help="disaggregated serving (docs/serving.md): split "
                         "the fleet into P prefill and D decode replicas "
                         "joined by the kv_migrate wire plan "
                         "(blockwise-int8+EF on the DCN-class hop), and "
                         "A/B against a symmetric (P+D)-replica baseline "
                         "over the SAME trace — greedy outputs must "
                         "match the baseline bit-identically")
    ap.add_argument("--shared-prefix-len", type=int, default=None,
                    metavar="N",
                    help="multi-tenant trace: requests join one of a few "
                         "tenants, each with a fixed N-token prompt "
                         "prefix, so later arrivals hit the copy-on-"
                         "write prefix cache (default 8 under --disagg, "
                         "else 0 = independent prompts)")
    ap.add_argument("--spec-decode", type=int, default=None, metavar="K",
                    help="speculative decoding on the decode replicas: "
                         "the n-gram drafter proposes K tokens per step, "
                         "all verified in ONE batched window step "
                         "(greedy = bit-identical outputs; default 3 "
                         "under --disagg, else 0 = off)")
    ap.add_argument("--mesh-shape", default=None,
                    metavar="CROSSxLOCAL[xPODS]",
                    help="emulate a multi-host (cross, local) topology, "
                         "e.g. 2x4 — gives the collectives a real DCN "
                         "(cross) hop on a single host; default for "
                         "--quantized on an even device count is 2x(N/2). "
                         "A third component (e.g. 2x2x2) adds a pods "
                         "axis: the 3-level (pod, cross, local) mesh the "
                         "wire-plan tree plans target (docs/wire-plan.md)")
    ap.add_argument("--dump-plan", action="store_true",
                    help="print the resolved wire plan for the current "
                         "knob set (--quantized/--zero-stage/--overlap/"
                         "HOROVOD_* env) as a table — legs, hops, wire "
                         "dtypes, streams, predicted wire bytes from the "
                         "trace-time cost model — and exit "
                         "(docs/wire-plan.md)")
    ap.add_argument("--dump-plan-bytes", type=int, default=4 * 1024 * 1024,
                    help="payload size (bytes) the --dump-plan cost "
                         "model prices, default 4 MiB")
    ap.add_argument("--space-to-depth", action="store_true",
                    help="resnet50: MLPerf-style folded stem (4x4/1 conv "
                         "on 2x2-blocked input instead of 7x7/2 on 3 "
                         "channels — full MXU channel utilization)")
    ap.add_argument("--profile", metavar="DIR", default=None,
                    help="capture a jax.profiler trace of one timing iter "
                         "into DIR and print the top device ops")
    ap.add_argument("--steps-per-call", type=int, default=1,
                    help="run K train steps per device call via lax.scan "
                         "(host-loop offload; hides per-dispatch latency)")
    args = ap.parse_args()
    if args.batch_size is None:
        args.batch_size = 8 if args.model == "gpt" else 128
    if args.steps_per_call < 1:
        ap.error("--steps-per-call must be >= 1")
    if args.profile and args.num_iters < 2:
        ap.error("--profile needs --num-iters >= 2 (the profiled iter is "
                 "excluded from the reported stats)")

    if args.serve:
        if args.scaling or args.quantized or args.zero or args.overlap \
                or args.autotune or args.profile or args.zero_stage:
            ap.error("--serve cannot combine with --scaling/--quantized/"
                     "--zero/--zero-stage/--overlap/--autotune/--profile "
                     "(the serve leg has its own trace structure)")
        for flag in ("serve_prompt_len", "serve_max_new"):
            try:
                lo, hi = (int(v) for v in getattr(args, flag).split(","))
            except ValueError:
                ap.error(f"--{flag.replace('_', '-')} expects LO,HI ints")
            if lo < 1 or hi < lo:
                ap.error(f"--{flag.replace('_', '-')}: need 1 <= LO <= HI")
            setattr(args, flag, (lo, hi))
        if args.serve_rate <= 0:
            ap.error("--serve-rate must be > 0")
        if args.serve_requests < 1 or args.serve_replicas < 1:
            ap.error("--serve-requests/--serve-replicas must be >= 1")
        if args.disagg is not None:
            try:
                p, d = (int(v) for v in args.disagg.split(":"))
            except ValueError:
                ap.error("--disagg expects P:D ints, e.g. 3:1")
            if p < 1 or d < 1:
                ap.error("--disagg: need P >= 1 and D >= 1")
            args.disagg = (p, d)
        # The disagg A/B defaults exercise the whole engine: a shared
        # prefix (so the cache has something to hit) and a spec window.
        if args.shared_prefix_len is None:
            args.shared_prefix_len = 8 if args.disagg else 0
        if args.spec_decode is None:
            args.spec_decode = 3 if args.disagg else 0
        if args.shared_prefix_len < 0 or args.spec_decode < 0:
            ap.error("--shared-prefix-len/--spec-decode must be >= 0")
    elif (args.disagg is not None or args.shared_prefix_len is not None
          or args.spec_decode is not None):
        ap.error("--disagg/--shared-prefix-len/--spec-decode require "
                 "--serve")

    if args.dump_plan:
        # Pure plan resolution + cost model — runs before the A/B
        # exclusivity checks (any knob combination is a valid plan to
        # inspect) and needs no devices.
        shape = None
        if args.mesh_shape:
            try:
                shape = parse_mesh_shape(args.mesh_shape)
            except ValueError as e:
                ap.error(str(e))
        dump_plan(args, shape)
        return

    sweep = None
    if args.scaling:
        try:
            sweep = sorted({int(x) for x in args.scaling.split(",")})
        except ValueError:
            ap.error(f"--scaling expects comma-separated ints, "
                     f"got {args.scaling!r}")
        if not sweep or sweep[0] < 1:
            ap.error("--scaling sizes must be >= 1")
        if args.quantized or args.mesh_shape or args.autotune or args.zero \
                or args.overlap or args.zero_stage:
            ap.error("--scaling cannot combine with --quantized/"
                     "--mesh-shape/--autotune/--zero/--zero-stage/"
                     "--overlap (the sweep re-shapes the world per size)")
    if args.fused and (args.scaling or args.autotune or args.serve
                       or args.zero or args.profile):
        ap.error("--fused cannot combine with --scaling/--autotune/"
                 "--serve/--zero/--profile (it is its own A/B "
                 "structure; --zero-stage N, --quantized and --overlap "
                 "compose as knobs of the fused workload)")
    if args.autotune and (args.quantized or args.profile or args.zero
                          or args.overlap or args.zero_stage):
        ap.error("--autotune cannot combine with --quantized/--profile/"
                 "--zero/--zero-stage/--overlap (one A/B structure per "
                 "run)")
    if args.zero and args.quantized:
        ap.error("--zero cannot combine with --quantized (one A/B "
                 "structure per run; the quantized ZeRO wire is covered "
                 "by DistributedOptimizer(zero=True, quantized=True) and "
                 "tests/test_zero.py)")
    if args.zero_stage and args.zero:
        ap.error("--zero-stage cannot combine with --zero (--zero is "
                 "the stage-2 alias). --zero-stage DOES compose with "
                 "--quantized/--overlap: the stage leg then runs the "
                 "combined plan-compiled wire (docs/wire-plan.md)")
    if args.overlap and not args.zero_stage and not args.pp \
            and (args.quantized or args.zero):
        ap.error("--overlap cannot combine with --quantized/--zero (one "
                 "A/B structure per run; the compose matrix is covered "
                 "by tests/test_overlap.py — or use --zero-stage N "
                 "--quantized --overlap for the combined plan leg)")

    if args.pp:
        if args.pp < 2:
            ap.error("--pp needs >= 2 stages")
        if args.serve or args.scaling or args.autotune or args.fused \
                or args.zero:
            ap.error("--pp composes with --moe/--zero-stage/--quantized/"
                     "--overlap only (one A/B structure per run)")
        if args.pp_microbatches < 1:
            ap.error("--pp-microbatches must be >= 1")
        if args.pp_interleave < 1:
            ap.error("--pp-interleave must be >= 1")

    if args.moe:
        if args.moe < 2:
            ap.error("--moe needs >= 2 experts")
        if args.serve or args.scaling or args.autotune or args.fused \
                or args.zero:
            ap.error("--moe composes with --quantized (and, with --pp, "
                     "the combined 4-D leg) only")
        if not args.pp and (args.zero_stage or args.overlap):
            ap.error("--moe composes with --quantized only (one A/B "
                     "structure per run; the EPxZeRO compose matrix is "
                     "covered by tests/test_moe.py — or use --pp S "
                     "--moe E --zero-stage 3 for the combined 4-D leg)")
        if args.moe_topk < 1 or args.moe_topk > args.moe:
            ap.error(f"--moe-topk must be in 1..{args.moe}")
        if args.moe_capacity <= 0:
            ap.error("--moe-capacity must be > 0")
        if args.moe_layers < 1:
            ap.error("--moe-layers must be >= 1")

    mesh_shape = None
    if args.mesh_shape:
        try:
            mesh_shape = parse_mesh_shape(args.mesh_shape)
        except ValueError as e:
            ap.error(str(e))

    if args.platform == "cpu":
        want = max(sweep) if sweep else (args.chips or args.cpu_devices)
        devices, platform = force_cpu_backend(max(want, args.cpu_devices))
    else:
        devices, platform = init_backend()
    if args.chips is not None:
        if args.chips < 1:
            ap.error("--chips must be >= 1")
        if args.chips > len(devices):
            raise SystemExit(f"--chips {args.chips} > {len(devices)} "
                             f"visible devices")
        devices = devices[:args.chips]

    mesh_world = 1
    for v in (mesh_shape or ()):
        mesh_world *= v
    # Under --pp the --mesh-shape names the DATA mesh; the hvd_pp axis
    # multiplies it to cover the devices (docs/pipeline.md) — and the
    # hvd_ep axis too on the combined 4-D leg (docs/parallelism.md).
    if args.pp:
        mesh_world *= args.pp
        if args.moe:
            mesh_world *= args.moe
    if mesh_shape is not None and mesh_world != len(devices):
        raise SystemExit(f"--mesh-shape {mesh_shape_str(mesh_shape)} "
                         f"does not cover {len(devices)} devices"
                         + (f" (x --pp {args.pp})" if args.pp else ""))
    if (args.quantized or args.autotune or args.zero or args.overlap
            or args.serve or args.zero_stage or args.fused) \
            and mesh_shape is None \
            and len(devices) % 2 == 0 and len(devices) >= 2:
        # A DCN (cross) hop is what quantization compresses, what the
        # hierarchical-allreduce knob decomposes, what splits the ZeRO
        # reduce-scatter into its ICI/DCN legs, and what the overlap
        # schedule hides under backward (and what a multi-host serve
        # replica spans); emulate a 2-host topology unless the user
        # pinned one.
        mesh_shape = (2, len(devices) // 2)
        which = ("quantized" if args.quantized else "zero" if args.zero
                 else "zero-stage" if args.zero_stage
                 else "overlap" if args.overlap
                 else "serve" if args.serve
                 else "fused" if args.fused else "autotune")
        log(f"--{which}: emulating mesh_shape {mesh_shape} so the "
            f"collectives have a cross (DCN) hop")

    if args.pp and args.moe:
        run_pp4d(args, devices, platform,
                 parse_mesh_shape(args.mesh_shape) if args.mesh_shape
                 else None)
        return

    if args.pp:
        run_pp(args, devices, platform,
               parse_mesh_shape(args.mesh_shape) if args.mesh_shape
               else None)
        return

    if args.moe:
        run_moe(args, devices, platform,
                parse_mesh_shape(args.mesh_shape) if args.mesh_shape
                else None)
        return

    if args.serve:
        run_serve(args, devices, platform, mesh_shape)
        return

    if args.fused:
        run_fused(args, devices, platform, mesh_shape)
        return

    metric_stem = (f"gpt{args.gpt_scale}" if args.model == "gpt"
                   else args.model)
    gpt_fields = ({"attention": args.attention, "seq_len": args.seq_len,
                   "lm_loss": args.lm_loss, "vocab_size": args.vocab_size}
                  if args.model == "gpt" else {})

    if sweep:
        if sweep[-1] > len(devices):
            raise SystemExit(f"--scaling max {sweep[-1]} > {len(devices)} "
                             f"visible devices")
        rows = []
        for n in sweep:
            log(f"=== scaling sweep: world {n} ===")
            rows.append(run_once(args, devices[:n], platform))
        base = rows[0]
        for row in rows:
            row["efficiency"] = row["per_chip"] / base["per_chip"]
        log(f"-- weak scaling ({metric_stem}, per-chip batch "
            f"{args.batch_size}, base world {base['chips']}) --")
        log(f"  {'chips':>6} {'per-chip':>12} {'total':>12} "
            f"{'efficiency':>10}")
        for row in rows:
            log(f"  {row['chips']:>6} {row['per_chip']:>12.1f} "
                f"{row['per_chip'] * row['chips']:>12.1f} "
                f"{row['efficiency']:>10.3f}")
        final = rows[-1]
        print(json.dumps({
            "metric": f"{metric_stem}_scaling_efficiency_"
                      f"{final['chips']}chip",
            "value": round(final["efficiency"], 4),
            "unit": "fraction",
            # Reference's published scaling anchor: 90% at 512 GPUs
            # (docs/benchmarks.rst:13-14).
            "vs_baseline": round(
                final["efficiency"] / BASELINE_SCALING_EFFICIENCY, 3),
            "per_chip_base": round(base["per_chip"], 2),
            "per_chip_final": round(final["per_chip"], 2),
            "throughput_unit": base["unit"],
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "per_chip_batch": args.batch_size,
            "table": [{"chips": r["chips"],
                       "per_chip": round(r["per_chip"], 2),
                       "efficiency": round(r["efficiency"], 4),
                       "mfu": (round(r["mfu"], 4)
                               if r["mfu"] is not None else None)}
                      for r in rows],
            "metrics_snapshot": final["metrics"],
            **gpt_fields,
        }), flush=True)
        return

    metric = (f"{metric_stem}_tokens_per_sec_per_chip" if args.model == "gpt"
              else f"{metric_stem}_images_per_sec_per_chip")

    if args.autotune:
        # Tuning session first, then A/B: default knobs vs the frozen
        # winner over the identical step structure. Baseline first so a
        # tuned-path failure still leaves a reference number in the log.
        result = run_autotune_session(args, devices, platform, mesh_shape)
        tuned = result.params
        log(f"=== A/B leg 1/2: default knobs ===")
        res_d = run_once(args, devices, platform, mesh_shape=mesh_shape)
        log(f"=== A/B leg 2/2: tuned {tuned.as_dict()} ===")
        res_t = run_once(args, devices, platform, mesh_shape=mesh_shape,
                         tuned_params=tuned)
        delta = res_t["per_chip"] / res_d["per_chip"] - 1.0
        log(f"A/B: default {res_d['per_chip']:.1f} vs tuned "
            f"{res_t['per_chip']:.1f} {res_d['unit']} "
            f"({100 * delta:+.1f}%)"
            + (" [warm-start cache hit: trials skipped]"
               if result.cache_hit else
               f" after {result.samples} scored trials"))
        print(json.dumps({
            "metric": metric,
            "value": round(res_t["per_chip"], 2),
            "unit": res_t["unit"],
            "vs_baseline": None,
            "mfu": (round(res_t["mfu"], 4)
                    if res_t["mfu"] is not None else None),
            "step_ms_median": round(res_t["step_ms_median"], 3),
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "chips": res_t["chips"],
            "per_chip_batch": args.batch_size,
            "autotune": True,
            "autotune_cache_hit": result.cache_hit,
            "autotune_samples": result.samples,
            "autotune_warm_start": result.warm_start,
            "shortlist": list(result.shortlist),
            **wire_ms_fields(res_t),
            **leg_compile_fields(res_t),
            "tuned_params": tuned.as_dict(),
            "trial_history": [
                {**p.as_dict(), "score_steps_per_sec": round(s, 4)}
                for p, s in result.history],
            "mesh_shape": (mesh_shape_str(mesh_shape)
                           if mesh_shape else None),
            "baseline_per_chip": round(res_d["per_chip"], 2),
            "throughput_delta": round(delta, 4),
            "metrics_snapshot": res_t["metrics"],
            **gpt_fields,
        }), flush=True)
        return

    if args.overlap and not args.zero_stage:
        # A/B: identical step structure (reduce-in-optimizer), identical
        # mesh, same fused bucket plan; only the schedule changes
        # (synchronous post-backward reduction vs reverse-layer bucket
        # streaming). Baseline first so an overlap-path failure still
        # leaves a reference number in the log. (--overlap WITH
        # --zero-stage rides the stage leg below as one combined
        # plan-compiled wire, docs/wire-plan.md.)
        log("=== A/B leg 1/2: baseline (synchronous reduction) ===")
        res_b = run_once(args, devices, platform, overlap=False,
                         mesh_shape=mesh_shape)
        log("=== A/B leg 2/2: overlapped bucket streaming ===")
        res_o = run_once(args, devices, platform, overlap=True,
                         mesh_shape=mesh_shape)
        delta = res_o["per_chip"] / res_b["per_chip"] - 1.0
        # comm_hidden_fraction: share of the step's per-device wire bytes
        # issued through the overlap stream schedule (record_wire_stats
        # trace-time accounting) — traffic positioned for the latency-
        # hiding scheduler to run under backward/update compute.
        hidden = res_o["comm_hidden_fraction"]
        # step_time_breakdown: compute_ms backs the model wire time out
        # of the synchronous leg (baseline = compute + fully exposed
        # comm); exposed_comm_ms is what the overlap leg still pays on
        # top of that compute. Bandwidths are modeled (env-overridable) —
        # on an emulated CPU mesh they are nominal, on a pod they are the
        # chip spec.
        # Per-level link model (HOROVOD_BENCH_{ICI,DCN,POD}_GBPS): the
        # pod knob defaults to the DCN value, so 2-level meshes price
        # exactly as before; a 3-level mesh can model its slower
        # cross-pod links separately (docs/wire-plan.md).
        from horovod_tpu.plan.accounting import bench_gbps

        ici_gbps, dcn_gbps, pod_gbps = bench_gbps()
        wire_ms = (res_b["wire_bytes_ici"] / (ici_gbps * 1e9)
                   + res_b["wire_bytes_dcn"] / (dcn_gbps * 1e9)
                   + res_b["wire_bytes_pod"] / (pod_gbps * 1e9)) * 1e3
        compute_ms = max(0.0, res_b["step_ms_median"] - wire_ms)
        exposed_ms = max(0.0, res_o["step_ms_median"] - compute_ms)
        log(f"A/B: sync {res_b['per_chip']:.1f} vs overlap "
            f"{res_o['per_chip']:.1f} {res_b['unit']} "
            f"({100 * delta:+.1f}%); comm hidden fraction {hidden:.3f} "
            f"({res_o['wire_bytes_overlap'] / 1e6:.3f} of "
            f"{(res_o['wire_bytes_ici'] + res_o['wire_bytes_dcn']) / 1e6:.3f}"
            f" MB/step/device streamed)")
        print(json.dumps({
            "metric": metric,
            "value": round(res_o["per_chip"], 2),
            "unit": res_o["unit"],
            "vs_baseline": None,
            "mfu": (round(res_o["mfu"], 4)
                    if res_o["mfu"] is not None else None),
            "step_ms_median": round(res_o["step_ms_median"], 3),
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "chips": res_o["chips"],
            "per_chip_batch": args.batch_size,
            "overlap": True,
            "mesh_shape": (mesh_shape_str(mesh_shape)
                           if mesh_shape else None),
            "baseline_per_chip": round(res_b["per_chip"], 2),
            "throughput_delta": round(delta, 4),
            "comm_hidden_fraction": round(hidden, 4),
            "step_time_breakdown": {
                "compute_ms": round(compute_ms, 3),
                "exposed_comm_ms": round(exposed_ms, 3),
            },
            "wire_bytes_overlap": round(res_o["wire_bytes_overlap"], 1),
            "wire_bytes_ici": round(res_o["wire_bytes_ici"], 1),
            "wire_bytes_dcn": round(res_o["wire_bytes_dcn"], 1),
            **leg_compile_fields(res_o),
            "metrics_snapshot": res_o["metrics"],
            **gpt_fields,
        }), flush=True)
        return

    if args.zero_stage:
        # A/B: replicated baseline vs ONE explicit ZeRO stage, identical
        # reduce-in-optimizer step structure and mesh. The stage leg also
        # runs the async-checkpoint stall probe (docs/checkpoint.md) and
        # the run finishes with the stage-1/2/3 parity probe (one
        # program, bit-identical — the acceptance contract).
        stage = args.zero_stage
        combo = "".join(
            (" +quantized" if args.quantized else "",
             " +overlap" if args.overlap else ""))
        log("=== A/B leg 1/2: baseline (replicated optimizer update) ===")
        res_b = run_once(args, devices, platform, mesh_shape=mesh_shape)
        log(f"=== A/B leg 2/2: ZeRO stage {stage}{combo} ===")
        res_z = run_once(args, devices, platform, zero_stage=stage,
                         quantized=args.quantized, overlap=args.overlap,
                         mesh_shape=mesh_shape, ckpt_probe=True)
        parity = run_stage_parity_probe(devices, mesh_shape)
        from horovod_tpu import plan as hvd_plan

        plan_enc = hvd_plan.describe_plan(
            quantized=args.quantized or None, zero_stage=stage,
            overlap=args.overlap or None).encode()
        delta = res_z["per_chip"] / res_b["per_chip"] - 1.0
        tot_b, tot_z = (res_b["bytes_per_rank_total"],
                        res_z["bytes_per_rank_total"])
        log(f"A/B: replicated {res_b['per_chip']:.1f} vs stage {stage} "
            f"{res_z['per_chip']:.1f} {res_b['unit']} "
            f"({100 * delta:+.1f}%); param+grad+state "
            f"{tot_b / 1e6:.3f} -> {tot_z / 1e6:.3f} MB/rank "
            f"({tot_b / max(1.0, tot_z):.2f}x)"
            + (f"; ckpt stall {res_z.get('ckpt_save_stall_ms', 0):.2f} ms "
               f"({100 * res_z.get('ckpt_stall_frac', 0):.1f}% of a step)"
               if "ckpt_save_stall_ms" in res_z else ""))
        print(json.dumps({
            "metric": metric,
            "value": round(res_z["per_chip"], 2),
            "unit": res_z["unit"],
            "vs_baseline": None,
            "mfu": (round(res_z["mfu"], 4)
                    if res_z["mfu"] is not None else None),
            "step_ms_median": round(res_z["step_ms_median"], 3),
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "chips": res_z["chips"],
            "per_chip_batch": args.batch_size,
            "zero_stage": stage,
            "quantized": bool(args.quantized),
            "overlap": bool(args.overlap),
            "plan": plan_enc,
            "mesh_shape": (mesh_shape_str(mesh_shape)
                           if mesh_shape else None),
            "baseline_per_chip": round(res_b["per_chip"], 2),
            "throughput_delta": round(delta, 4),
            "bytes_per_rank": {
                "params": round(res_z["param_bytes_per_rank"], 1),
                "param_gather_transient": round(
                    res_z["param_bytes_transient"], 1),
                "grad_accum": round(res_z["grad_accum_bytes_per_rank"], 1),
                "opt_state": round(res_z["opt_state_bytes_per_rank"], 1),
                "total": round(tot_z, 1),
            },
            "bytes_per_rank_baseline": {
                "params": round(res_b["param_bytes_per_rank"], 1),
                "grad_accum": round(res_b["grad_accum_bytes_per_rank"], 1),
                "opt_state": round(res_b["opt_state_bytes_per_rank"], 1),
                "total": round(tot_b, 1),
            },
            "bytes_per_rank_reduction": round(
                tot_b / max(1.0, tot_z), 3),
            "ckpt_commits": res_z.get("ckpt_commits", 0),
            "ckpt_save_stall_ms": res_z.get("ckpt_save_stall_ms"),
            "ckpt_stall_frac": res_z.get("ckpt_stall_frac"),
            "stage_parity": parity,
            "wire_bytes_ici": round(res_z["wire_bytes_ici"], 1),
            "wire_bytes_dcn": round(res_z["wire_bytes_dcn"], 1),
            "wire_bytes_ici_baseline": round(res_b["wire_bytes_ici"], 1),
            "wire_bytes_dcn_baseline": round(res_b["wire_bytes_dcn"], 1),
            **wire_ms_fields(res_z),
            **leg_compile_fields(res_z),
            "metrics_snapshot": res_z["metrics"],
            **gpt_fields,
        }), flush=True)
        return

    if args.zero:
        # A/B: identical step structure (reduce-in-optimizer), identical
        # mesh, same fused bucket schedule; only the update layout changes
        # (replicated full update vs reduce-scatter → 1/world shard update
        # → all-gather). Baseline first so a sharded-path failure still
        # leaves a reference number in the log.
        log("=== A/B leg 1/2: baseline (replicated optimizer update) ===")
        res_b = run_once(args, devices, platform, zero=False,
                         mesh_shape=mesh_shape)
        log("=== A/B leg 2/2: ZeRO-1 sharded optimizer update ===")
        res_z = run_once(args, devices, platform, zero=True,
                         mesh_shape=mesh_shape)
        delta = res_z["per_chip"] / res_b["per_chip"] - 1.0
        log(f"A/B: replicated {res_b['per_chip']:.1f} vs ZeRO "
            f"{res_z['per_chip']:.1f} {res_b['unit']} "
            f"({100 * delta:+.1f}%); opt state "
            f"{res_b['opt_state_bytes_per_rank'] / 1e6:.3f} -> "
            f"{res_z['opt_state_bytes_per_rank'] / 1e6:.3f} MB/rank "
            f"({res_b['opt_state_bytes_per_rank'] / max(1.0, res_z['opt_state_bytes_per_rank']):.2f}x)")
        print(json.dumps({
            "metric": metric,
            "value": round(res_z["per_chip"], 2),
            "unit": res_z["unit"],
            "vs_baseline": None,
            "mfu": (round(res_z["mfu"], 4)
                    if res_z["mfu"] is not None else None),
            "step_ms_median": round(res_z["step_ms_median"], 3),
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "chips": res_z["chips"],
            "per_chip_batch": args.batch_size,
            "zero": True,
            "mesh_shape": (mesh_shape_str(mesh_shape)
                           if mesh_shape else None),
            "baseline_per_chip": round(res_b["per_chip"], 2),
            "throughput_delta": round(delta, 4),
            "opt_state_bytes_per_rank": round(
                res_z["opt_state_bytes_per_rank"], 1),
            "opt_state_bytes_per_rank_baseline": round(
                res_b["opt_state_bytes_per_rank"], 1),
            "opt_state_reduction": round(
                res_b["opt_state_bytes_per_rank"]
                / max(1.0, res_z["opt_state_bytes_per_rank"]), 3),
            "wire_bytes_ici": round(res_z["wire_bytes_ici"], 1),
            "wire_bytes_dcn": round(res_z["wire_bytes_dcn"], 1),
            "wire_bytes_ici_baseline": round(res_b["wire_bytes_ici"], 1),
            "wire_bytes_dcn_baseline": round(res_b["wire_bytes_dcn"], 1),
            **leg_compile_fields(res_z),
            "metrics_snapshot": res_z["metrics"],
            **gpt_fields,
        }), flush=True)
        return

    if args.quantized:
        # A/B: identical step structure (reduce-in-optimizer), identical
        # mesh; only the wire changes. Baseline first so a quantized-path
        # failure still leaves a reference number in the log.
        log("=== A/B leg 1/2: baseline (unquantized) ===")
        res_b = run_once(args, devices, platform, quantized=False,
                         mesh_shape=mesh_shape)
        log("=== A/B leg 2/2: quantized int8 DCN wire + error feedback ===")
        res_q = run_once(args, devices, platform, quantized=True,
                         mesh_shape=mesh_shape)
        delta = res_q["per_chip"] / res_b["per_chip"] - 1.0
        log(f"A/B: baseline {res_b['per_chip']:.1f} vs quantized "
            f"{res_q['per_chip']:.1f} {res_b['unit']} "
            f"({100 * delta:+.1f}%); DCN wire "
            f"{res_b['wire_bytes_dcn'] / 1e6:.3f} -> "
            f"{res_q['wire_bytes_dcn'] / 1e6:.3f} MB/step/device")
        print(json.dumps({
            "metric": metric,
            "value": round(res_q["per_chip"], 2),
            "unit": res_q["unit"],
            "vs_baseline": None,
            "mfu": (round(res_q["mfu"], 4)
                    if res_q["mfu"] is not None else None),
            "step_ms_median": round(res_q["step_ms_median"], 3),
            "platform": platform,
            "device_kind": getattr(devices[0], "device_kind", "unknown"),
            "chips": res_q["chips"],
            "per_chip_batch": args.batch_size,
            "quantized": True,
            "mesh_shape": (mesh_shape_str(mesh_shape)
                           if mesh_shape else None),
            "baseline_per_chip": round(res_b["per_chip"], 2),
            "throughput_delta": round(delta, 4),
            "wire_bytes_dcn": round(res_q["wire_bytes_dcn"], 1),
            "wire_bytes_dcn_baseline": round(res_b["wire_bytes_dcn"], 1),
            "wire_bytes_ici": round(res_q["wire_bytes_ici"], 1),
            **wire_ms_fields(res_q),
            **leg_compile_fields(res_q),
            # Representation ratio on the DCN hop: the same quantized
            # traffic pattern at the payload dtype vs as int8+scales
            # (EQuARX's "~4x wire bytes" accounting).
            "wire_reduction_dcn": (round(res_q["wire_reduction_dcn"], 3)
                                   if res_q["wire_reduction_dcn"] else None),
            "metrics_snapshot": res_q["metrics"],
            **gpt_fields,
        }), flush=True)
        return

    res = run_once(args, devices, platform, mesh_shape=mesh_shape)
    print(json.dumps({
        "metric": metric,
        "value": round(res["per_chip"], 2),
        "unit": res["unit"],
        "vs_baseline": (
            round(res["per_chip"] / BASELINE_IMG_PER_SEC_PER_DEVICE, 3)
            if args.model == "resnet50" else None),
        "mfu": round(res["mfu"], 4) if res["mfu"] is not None else None,
        "step_ms_median": round(res["step_ms_median"], 3),
        "step_ms_min": round(res["step_ms_min"], 3),
        "platform": platform,
        "device_kind": getattr(devices[0], "device_kind", "unknown"),
        "chips": res["chips"],
        "per_chip_batch": args.batch_size,
        **wire_ms_fields(res),
        **leg_compile_fields(res),
        "metrics_snapshot": res["metrics"],
        **gpt_fields,
    }), flush=True)


if __name__ == "__main__":
    main()
