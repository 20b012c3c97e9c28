"""Environment-variable configuration knobs.

The reference converges three config layers (env vars, CLI flags, YAML) onto
environment variables consumed by the native core at init time
(operations.cc:416-518, knob names common.h:64-90, config_parser.py). We keep
the same knob names with a ``HOROVOD_`` prefix so reference users can carry
their tuning over, and read them once at :func:`horovod_tpu.init` into a
typed, immutable :class:`Config`.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return int(v)
    except ValueError:
        raise ValueError(f"{name} must be an integer, got {v!r}")


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    try:
        return float(v)
    except ValueError:
        raise ValueError(f"{name} must be a float, got {v!r}")


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None or v == "":
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def _env_str(name: str, default: Optional[str]) -> Optional[str]:
    v = os.environ.get(name)
    return default if v in (None, "") else v


@dataclasses.dataclass(frozen=True)
class Config:
    """Runtime knobs, mirroring the reference's env contract.

    Defaults match the reference where a reference default exists
    (fusion threshold 64 MiB and cycle time 5 ms: operations.cc:437,445;
    cache capacity 1024: operations.cc:452-461; stall warning 60 s:
    stall_inspector.h:36-66).
    """

    # --- tensor fusion (operations.cc:437; controller.cc:360-378) ---
    fusion_threshold_bytes: int = 64 * 1024 * 1024
    cycle_time_ms: float = 5.0

    # --- response cache (operations.cc:452-461) ---
    cache_capacity: int = 1024

    # --- hierarchical collectives (operations.cc:463-487) ---
    hierarchical_allreduce: bool = False
    hierarchical_allgather: bool = False

    # --- quantized allreduce (no reference analogue; EQuARX-style int8
    #     wire on the DCN hop of the hierarchical decomposition) ---
    quantized_allreduce: bool = False
    quant_block: int = 256  # elements per int8 scale block

    # --- ZeRO sharded optimizer (no reference analogue; reduce-scatter
    #     data parallelism with per-rank optax updates, docs/zero.md).
    #     zero_stage 0-3 wins; the PR-4 boolean maps to stage 2. ---
    zero_sharding: bool = False
    zero_stage: int = 0

    # --- overlapped gradient reduction (docs/overlap.md): stream fused
    #     buckets into collectives while backward compute still runs ---
    overlap: bool = False
    num_comm_streams: int = 1  # bucket collectives in flight (pow2 1-4)

    # 3-level tree plans: ride the pod hop as the blockwise-int8 rs+ag
    # pair instead of the exact psum (docs/wire-plan.md)
    quantized_pod: bool = False

    # --- pipeline parallelism (docs/pipeline.md): a dedicated hvd_pp
    #     mesh axis of pp_stages stages; the training schedule pumps
    #     pp_microbatches microbatches through it (gpipe | 1f1b |
    #     interleaved_1f1b with pp_interleave virtual stages per rank).
    #     pp_quantized rides the inter-stage activation sends as
    #     blockwise-int8 wire-plan legs with error feedback (DCN/pod
    #     hops only — the send leg inherits the EQuARX placement rule).
    pp_stages: int = 0          # 0/1 = pipeline off
    pp_microbatches: int = 0    # 0 = schedule default (max(stages, 2))
    pp_schedule: str = "interleaved_1f1b"
    pp_interleave: int = 1      # virtual stages per rank (>=1)
    pp_quantized: bool = False

    # --- expert parallelism / MoE (docs/moe.md): a dedicated hvd_ep
    #     mesh axis of ep_size expert groups; the MoE layer's
    #     dispatch/combine all-to-alls lower as wire-plan ``a2a`` legs.
    #     moe_quantized rides them blockwise-int8 with error feedback
    #     (DCN/pod hops only — the a2a leg inherits the EQuARX
    #     placement rule, exactly like the pipeline send leg).
    ep_size: int = 0            # 0/1 = expert parallelism off
    moe_experts: int = 0        # global expert count (0 = MoE off)
    moe_topk: int = 2           # experts per token (top-k gating)
    moe_capacity_factor: float = 1.25
    moe_quantized: bool = False

    # --- autotune (common.h:68-73) ---
    autotune: bool = False
    autotune_log: Optional[str] = None
    autotune_warmup_samples: int = 3
    autotune_steps_per_sample: int = 10
    autotune_bayes_opt_max_samples: int = 20
    autotune_gaussian_process_noise: float = 0.8
    # Cost-model warm start (docs/cost-model.md): seed the GP with the
    # top-K analytically priced plans (0 = cold search).
    autotune_warm_start: int = 0

    # --- link-class calibration store (docs/cost-model.md): the
    #     microbenchmark-fitted (bandwidth, latency, quant-rate) triples,
    #     kept beside the autotune cache by default ---
    calibration_cache: Optional[str] = None

    # --- compile-once runtime (docs/compile.md): JAX persistent
    #     compilation cache + serialized-executable registry, armed from
    #     init so warm reruns / restarted workers skip lower+compile.
    #     Placed by JAX_COMPILATION_CACHE_DIR (compile/cache.py). ---
    compile_cache: bool = True

    # --- timeline (operations.cc:420-434) ---
    timeline: Optional[str] = None
    timeline_mark_cycles: bool = False

    # --- metrics registry / sinks (docs/observability.md) ---
    metrics_jsonl: Optional[str] = None  # snapshot JSONL sink path
    metrics_port: Optional[int] = None   # Prometheus endpoint (0 = any port)
    metrics_interval: float = 0.0        # reporter period secs (0 = off)
    metrics_aggregate: bool = False      # cross-rank aggregate per interval

    # --- flight recorder (docs/observability.md): always-on forensic
    #     ring of recent events, dumped to the dir on crash paths ---
    flight_recorder_dir: Optional[str] = None
    flight_recorder_events: int = 4096  # ring capacity (0 disables)

    # --- stall inspector (stall_inspector.h:36-66) ---
    stall_check_disable: bool = False
    stall_warning_time_seconds: float = 60.0
    stall_shutdown_time_seconds: float = 0.0

    # --- resilience supervisor (docs/robustness.md): preemption-notice
    #     priority-snapshot deadline and the restart-from-last-commit
    #     budget of the failure-policy supervisor ---
    preempt_snapshot_deadline_secs: float = 5.0
    resilience_restart_budget: int = 3

    # --- logging ---
    log_level: str = "warning"
    log_hide_timestamp: bool = False

    # --- elastic (launcher-injected; gloo_run.py:65-76) ---
    elastic: bool = False

    # --- launcher-injected world description (gloo_run.py:65-76) ---
    rank: Optional[int] = None
    size: Optional[int] = None
    local_rank: Optional[int] = None
    local_size: Optional[int] = None
    cross_rank: Optional[int] = None
    cross_size: Optional[int] = None
    rendezvous_addr: Optional[str] = None
    rendezvous_port: Optional[int] = None

    # --- controller transport (env_parser.h:26-32 analogue) ---
    controller: str = "tcp"  # "tcp" (rank-0 coordinator over sockets) | "none"
    cpu_operations: str = "ring"  # CPU eager data plane: "ring" | "naive"

    # --- number of independent collective streams (HOROVOD_NUM_NCCL_STREAMS) ---
    num_streams: int = 1


def from_env() -> Config:
    """Read all knobs from the environment (reference: operations.cc:416-518)."""
    return Config(
        fusion_threshold_bytes=_env_int("HOROVOD_FUSION_THRESHOLD", 64 * 1024 * 1024),
        cycle_time_ms=_env_float("HOROVOD_CYCLE_TIME", 5.0),
        cache_capacity=_env_int("HOROVOD_CACHE_CAPACITY", 1024),
        hierarchical_allreduce=_env_bool("HOROVOD_HIERARCHICAL_ALLREDUCE", False),
        hierarchical_allgather=_env_bool("HOROVOD_HIERARCHICAL_ALLGATHER", False),
        quantized_allreduce=_env_bool("HOROVOD_QUANTIZED_ALLREDUCE", False),
        quant_block=_env_int("HOROVOD_QUANT_BLOCK", 256),
        zero_sharding=_env_bool("HOROVOD_ZERO_SHARDING", False),
        zero_stage=_env_int("HOROVOD_ZERO_STAGE", 0),
        overlap=_env_bool("HOROVOD_OVERLAP", False),
        num_comm_streams=_env_int("HOROVOD_NUM_COMM_STREAMS", 1),
        quantized_pod=_env_bool("HOROVOD_QUANTIZED_POD", False),
        pp_stages=_env_int("HOROVOD_PP_STAGES", 0),
        pp_microbatches=_env_int("HOROVOD_PP_MICROBATCHES", 0),
        pp_schedule=_env_str("HOROVOD_PP_SCHEDULE", "interleaved_1f1b")
        or "interleaved_1f1b",
        pp_interleave=_env_int("HOROVOD_PP_INTERLEAVE", 1),
        pp_quantized=_env_bool("HOROVOD_PP_QUANTIZED", False),
        ep_size=_env_int("HOROVOD_EP_SIZE", 0),
        moe_experts=_env_int("HOROVOD_MOE_EXPERTS", 0),
        moe_topk=_env_int("HOROVOD_MOE_TOPK", 2),
        moe_capacity_factor=_env_float("HOROVOD_MOE_CAPACITY_FACTOR",
                                       1.25),
        moe_quantized=_env_bool("HOROVOD_MOE_QUANTIZED", False),
        autotune=_env_bool("HOROVOD_AUTOTUNE", False),
        autotune_log=_env_str("HOROVOD_AUTOTUNE_LOG", None),
        autotune_warmup_samples=_env_int("HOROVOD_AUTOTUNE_WARMUP_SAMPLES", 3),
        autotune_steps_per_sample=_env_int("HOROVOD_AUTOTUNE_STEPS_PER_SAMPLE", 10),
        autotune_bayes_opt_max_samples=_env_int(
            "HOROVOD_AUTOTUNE_BAYES_OPT_MAX_SAMPLES", 20
        ),
        autotune_gaussian_process_noise=_env_float(
            "HOROVOD_AUTOTUNE_GAUSSIAN_PROCESS_NOISE", 0.8
        ),
        autotune_warm_start=_env_int("HOROVOD_AUTOTUNE_WARM_START", 0),
        calibration_cache=_env_str("HOROVOD_CALIBRATION_CACHE", None),
        compile_cache=_env_bool("HOROVOD_COMPILE_CACHE", True),
        timeline=_env_str("HOROVOD_TIMELINE", None),
        timeline_mark_cycles=_env_bool("HOROVOD_TIMELINE_MARK_CYCLES", False),
        metrics_jsonl=_env_str("HOROVOD_METRICS_JSONL", None),
        metrics_port=_opt_int("HOROVOD_METRICS_PORT"),
        metrics_interval=_env_float("HOROVOD_METRICS_INTERVAL", 0.0),
        metrics_aggregate=_env_bool("HOROVOD_METRICS_AGGREGATE", False),
        flight_recorder_dir=_env_str("HOROVOD_FLIGHT_RECORDER_DIR", None),
        flight_recorder_events=_env_int("HOROVOD_FLIGHT_RECORDER_EVENTS",
                                        4096),
        stall_check_disable=_env_bool("HOROVOD_STALL_CHECK_DISABLE", False),
        stall_warning_time_seconds=_env_float("HOROVOD_STALL_CHECK_TIME_SECONDS", 60.0),
        stall_shutdown_time_seconds=_env_float(
            "HOROVOD_STALL_SHUTDOWN_TIME_SECONDS", 0.0
        ),
        preempt_snapshot_deadline_secs=_env_float(
            "HOROVOD_PREEMPT_SNAPSHOT_DEADLINE_SECS", 5.0
        ),
        resilience_restart_budget=_env_int(
            "HOROVOD_RESILIENCE_RESTART_BUDGET", 3
        ),
        log_level=_env_str("HOROVOD_LOG_LEVEL", "warning") or "warning",
        log_hide_timestamp=_env_bool("HOROVOD_LOG_HIDE_TIME", False),
        elastic=_env_bool("HOROVOD_ELASTIC", False),
        rank=_opt_int("HOROVOD_RANK"),
        size=_opt_int("HOROVOD_SIZE"),
        local_rank=_opt_int("HOROVOD_LOCAL_RANK"),
        local_size=_opt_int("HOROVOD_LOCAL_SIZE"),
        cross_rank=_opt_int("HOROVOD_CROSS_RANK"),
        cross_size=_opt_int("HOROVOD_CROSS_SIZE"),
        rendezvous_addr=_env_str("HOROVOD_GLOO_RENDEZVOUS_ADDR", None),
        rendezvous_port=_opt_int("HOROVOD_GLOO_RENDEZVOUS_PORT"),
        controller=_env_str("HOROVOD_CONTROLLER", "tcp") or "tcp",
        cpu_operations=_env_str("HOROVOD_CPU_OPERATIONS", "ring") or "ring",
        num_streams=_env_int("HOROVOD_NUM_STREAMS", 1),
    )


def _opt_int(name: str) -> Optional[int]:
    v = os.environ.get(name)
    return None if v in (None, "") else int(v)
