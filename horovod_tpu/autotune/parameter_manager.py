"""Trial state machine for the collective-knob autotuner.

Reference: ``cc/src/parameter_manager.cc`` (mirroring
``horovod/common/parameter_manager.cc``): warmup windows are discarded,
every later window scores the current knob setting, the next setting
comes from expected improvement over a GP fit on the normalized scores,
and after ``max_samples`` scored windows the manager freezes on the best
configuration seen.

The compiled-path differences from the native eager manager:

* knobs are :class:`TunedParams` — fusion threshold (1–256 MiB,
  log-space), ``quant_block`` (64–1024, log-space, power-of-two snapped,
  searched only when the quantized wire is on), the hierarchical
  allreduce flag, and the ``zero_stage`` level (0/1/2 as thirds of the
  unit axis; searched only when the session's step accepts it — it
  restructures the optimizer state, see docs/zero.md; stage 3 is
  excluded from the search because it restructures the TRAINING LOOP —
  the params become shards — which no tuned_params override can do to
  an already-built step). Cycle time and the response cache do not
  exist on the compiled path (the XLA schedule replaces both —
  ops/fusion.py);
* scores are wall-clock **steps/sec** of a real training window (the
  driver times them), not coordinator bytes/sec — on the compiled path
  the collective schedule is inside the step, so step rate is the
  end-to-end objective the knobs exist to move;
* proposals are deduplicated against already-tried configurations:
  log-space snapping makes the space effectively discrete, and repeat
  trials would each cost a recompile.
"""

from __future__ import annotations

import csv
import dataclasses
import logging
import math
import os
from typing import IO, List, Optional, Sequence, Tuple

from ..plan import planner as _wire_planner
from .gp import GaussianProcess

log = logging.getLogger("horovod_tpu.autotune")

# Search bounds, log2-space (ISSUE 3: fusion threshold 1-256 MiB,
# quant_block 64-1024; ISSUE 5: num_comm_streams pow2 1-4).
_MIN_FUSION_LOG = 20.0  # 2^20 = 1 MiB
_MAX_FUSION_LOG = 28.0  # 2^28 = 256 MiB
_MIN_QBLOCK_LOG = 6.0   # 2^6  = 64
_MAX_QBLOCK_LOG = 10.0  # 2^10 = 1024
_MAX_STREAMS_LOG = 2.0  # 2^2  = 4 bucket collectives in flight
# The 6 unit-cube dims now read as a compact PLAN encoding (ISSUE 9,
# docs/wire-plan.md): fusion threshold, per-hop int8 scale block, leg
# order (flat/tree vs the ZeRO rs+ag split via the zero dims), and the
# stream placement (overlap, flight width). Proposals canonicalize
# through horovod_tpu.plan.encode_tuned/decode_tuned, so two knob
# settings that compile to the SAME wire plan (e.g. hierarchical under
# ZeRO, or a stream count with overlap off) collapse to one trial
# instead of costing two recompiles.
# v8 adds the pipeline schedule pair (docs/pipeline.md): pp_microbatches
# (pow2, snapped to a multiple of the stage count) and pp_interleave
# (pow2 virtual-stage degree) — both gated by tune_pp and dead (0 / 1)
# when the session's step is not pipelined, where canonicalization
# collapses them to one trial.
# v9 adds the MoE routing pair (docs/moe.md): moe_capacity_factor
# (quarter-snapped 1.0-2.0 dispatch headroom) and moe_quantized (the
# int8 a2a wire) — both gated by tune_moe and dead (0.0 / False) when
# the session's step carries no MoE layer, where canonicalization
# collapses them to one trial.
# v10 adds the disaggregated-serving pair (docs/serving.md):
# spec_draft_k (speculative draft window 0-4; 0 = plain decode) and
# kv_migrate_quantized (the int8+EF prefill→decode KV wire) — both
# gated by tune_serve and dead (0 / False) in a training session,
# where canonicalization collapses them to one trial.
# v11 adds the pipeline schedule family (docs/pipeline.md):
# pp_schedule ("interleaved_1f1b" vs the zero-bubble "zb1" B/W split) —
# gated by tune_pp like the v8 pair and dead ("interleaved_1f1b") when
# the session's step is not pipelined, where canonicalization
# collapses it to one trial.
_DIMS = 13  # fusion, qblock, tree, zero, overlap, streams,
#             ppM, ppV, moeCap, moeQ, svK, svQ, ppZb

_MIN_PPM_LOG = 1.0   # 2^1 = 2 microbatches
_MAX_PPM_LOG = 5.0   # 2^5 = 32 microbatches
_MAX_PPV_LOG = 2.0   # 2^2 = 4 virtual stages per rank

_MIN_MOE_CAP = 1.0   # dispatch capacity factor search box
_MAX_MOE_CAP = 2.0   # (quarter-snapped: 1.0, 1.25, ..., 2.0)

_MAX_SPEC_K = 4      # speculative draft-window search box (0..4)

# CSV schema (reference: parameter_manager.cc:47-50 writes knobs then the
# window score; same layout here with the compiled-path knob set).
# zero_sharding (= zero_stage > 0) stays a column for log compatibility;
# zero_stage carries the actual level. v5 appends the canonical `plan`
# encoding column. read_log stays tolerant of v3/v4 logs lacking the
# newer columns, and takes what it knows by name: the `fused` column of
# a v6..v12 log (a knob that is gone) is ignored.
# v8 appends the pipeline pair; read_log stays tolerant of v3..v7 logs
# lacking the newer columns.
# v9 appends the MoE pair; read_log stays tolerant of v3..v8 logs
# lacking the newer columns.
# v10 appends the serving pair; read_log stays tolerant of v3..v9 logs
# lacking the newer columns.
# v11 appends the pipeline schedule family; read_log stays tolerant of
# v3..v10 logs lacking the newer columns.
# v12 appends the per-trial compile pair (docs/compile.md): compile_ms
# is the trial's build+absorb wall time (overlapped with the prior
# trial's window when compile-ahead prefetch hit), compile_cache_hit
# whether the executable cache served it without an XLA compile.
# read_log stays tolerant of v3..v11 logs lacking the newer columns.
CSV_FIELDS = ("sample", "fusion_threshold_bytes", "quant_block",
              "hierarchical_allreduce", "zero_sharding", "zero_stage",
              "overlap", "num_comm_streams",
              "pp_microbatches", "pp_interleave",
              "moe_capacity_factor", "moe_quantized",
              "spec_draft_k", "kv_migrate_quantized",
              "pp_schedule",
              "score_steps_per_sec", "plan",
              "compile_ms", "compile_cache_hit")


@dataclasses.dataclass(frozen=True)
class TunedParams:
    """One knob setting to build (or that built) a compiled step — the
    analogue of the Params struct the reference coordinator broadcasts
    (SynchronizeParameters, controller.cc:34-48). Hashable so trial
    dedup and the warm-start cache can key on it."""

    fusion_threshold_bytes: int = 64 * 1024 * 1024
    quant_block: int = 256
    hierarchical_allreduce: bool = False
    zero_stage: int = 0
    overlap: bool = False
    num_comm_streams: int = 1
    # Pipeline schedule pair (docs/pipeline.md): 0 / 1 = "not a
    # pipelined step" — the canonical dead-knob values. pp_schedule
    # picks the table family ("interleaved_1f1b" vs the zero-bubble
    # "zb1" B/W split); "interleaved_1f1b" is also the canonical dead
    # value when pp is off.
    pp_microbatches: int = 0
    pp_interleave: int = 1
    pp_schedule: str = "interleaved_1f1b"
    # MoE routing pair (docs/moe.md): 0.0 / False = "not an MoE step" —
    # the canonical dead-knob values.
    moe_capacity_factor: float = 0.0
    moe_quantized: bool = False
    # Disaggregated-serving pair (docs/serving.md): 0 / False = "not a
    # serving session" — the canonical dead-knob values.
    spec_draft_k: int = 0
    kv_migrate_quantized: bool = False

    @property
    def zero_sharding(self) -> bool:
        """Back-compat boolean view of ``zero_stage`` (the PR-4 knob):
        True when any ZeRO stage is on."""
        return self.zero_stage > 0

    def as_dict(self) -> dict:
        return {
            "fusion_threshold_bytes": int(self.fusion_threshold_bytes),
            "quant_block": int(self.quant_block),
            "hierarchical_allreduce": bool(self.hierarchical_allreduce),
            "zero_sharding": bool(self.zero_sharding),
            "zero_stage": int(self.zero_stage),
            "overlap": bool(self.overlap),
            "num_comm_streams": int(self.num_comm_streams),
            "pp_microbatches": int(self.pp_microbatches),
            "pp_interleave": int(self.pp_interleave),
            "pp_schedule": str(self.pp_schedule),
            "moe_capacity_factor": float(self.moe_capacity_factor),
            "moe_quantized": bool(self.moe_quantized),
            "spec_draft_k": int(self.spec_draft_k),
            "kv_migrate_quantized": bool(self.kv_migrate_quantized),
        }

    @classmethod
    def from_dict(cls, d: dict) -> "TunedParams":
        # .get: entries cached before the zero/overlap knobs existed stay
        # readable (the cache key's schema version gates real reuse);
        # a pre-v4 boolean zero_sharding maps to stage 2 (the PR-4
        # behavior it named).
        stage = d.get("zero_stage")
        if stage is None:
            stage = 2 if d.get("zero_sharding", False) else 0
        return cls(
            fusion_threshold_bytes=int(d["fusion_threshold_bytes"]),
            quant_block=int(d["quant_block"]),
            hierarchical_allreduce=bool(d["hierarchical_allreduce"]),
            zero_stage=int(stage),
            overlap=bool(d.get("overlap", False)),
            num_comm_streams=int(d.get("num_comm_streams", 1)),
            pp_microbatches=int(d.get("pp_microbatches", 0) or 0),
            pp_interleave=int(d.get("pp_interleave", 1) or 1),
            pp_schedule=str(d.get("pp_schedule", "interleaved_1f1b")
                            or "interleaved_1f1b"),
            moe_capacity_factor=float(
                d.get("moe_capacity_factor", 0.0) or 0.0),
            moe_quantized=bool(d.get("moe_quantized", False)),
            spec_draft_k=int(d.get("spec_draft_k", 0) or 0),
            kv_migrate_quantized=bool(
                d.get("kv_migrate_quantized", False)),
        )

    @classmethod
    def from_config(cls, config) -> "TunedParams":
        """Seed from a :class:`horovod_tpu.common.config.Config` (the
        hand-set env knobs are trial 0, as in the reference where tuning
        starts from the configured values)."""
        stage = getattr(config, "zero_stage", 0)
        if not stage and getattr(config, "zero_sharding", False):
            stage = 2
        return cls(
            fusion_threshold_bytes=config.fusion_threshold_bytes,
            quant_block=config.quant_block,
            hierarchical_allreduce=config.hierarchical_allreduce,
            zero_stage=stage,
            overlap=getattr(config, "overlap", False),
            num_comm_streams=getattr(config, "num_comm_streams", 1),
            pp_microbatches=getattr(config, "pp_microbatches", 0) or 0,
            pp_interleave=getattr(config, "pp_interleave", 1) or 1,
            pp_schedule=str(getattr(config, "pp_schedule",
                                    "interleaved_1f1b")
                            or "interleaved_1f1b"),
            moe_capacity_factor=(
                getattr(config, "moe_capacity_factor", 0.0)
                if getattr(config, "moe_experts", 0) else 0.0),
            moe_quantized=bool(getattr(config, "moe_quantized", False)
                               and getattr(config, "moe_experts", 0)),
            spec_draft_k=getattr(config, "spec_draft_k", 0) or 0,
            kv_migrate_quantized=bool(
                getattr(config, "kv_migrate_quantized", False)),
        )


class _XorShift:
    """xorshift64* — the reference manager's deterministic proposal RNG
    (parameter_manager.cc:106-113); seedable so sessions replay."""

    def __init__(self, seed: int = 0x9E3779B97F4A7C15) -> None:
        self.state = seed & 0xFFFFFFFFFFFFFFFF or 0x9E3779B97F4A7C15

    def next(self) -> float:
        s = self.state
        s ^= (s >> 12) & 0xFFFFFFFFFFFFFFFF
        s = (s ^ (s << 25)) & 0xFFFFFFFFFFFFFFFF
        s ^= s >> 27
        self.state = s
        return ((s * 0x2545F4914F6CDD1D & 0xFFFFFFFFFFFFFFFF) >> 11) / float(
            1 << 53)


class ParameterManager:
    """Warmup → sample → freeze over :class:`TunedParams` trials.

    Drive it like the reference's ``Update`` loop, one scored window at a
    time::

        pm = ParameterManager(initial, tune_quant_block=..., ...)
        while not pm.done:
            score = measure(pm.current)   # steps/sec of a timed window
            pm.record_sample(score)
        winner = pm.best

    ``warmup_samples`` windows run on the initial setting and are
    discarded (parameter_manager.cc:162 — JIT/dispatch warmup must not
    enter the GP); then every window is scored, and after ``max_samples``
    scored windows the manager freezes (``done``) on the best setting.
    """

    def __init__(
        self,
        initial: TunedParams,
        *,
        tune_quant_block: bool = False,
        tune_hierarchical: bool = True,
        tune_zero: bool = False,
        tune_overlap: bool = False,
        tune_pp: bool = False,
        pp_stages: int = 0,
        pp_max_interleave: int = 1,
        tune_moe: bool = False,
        moe_experts: int = 0,
        tune_serve: bool = False,
        warmup_samples: int = 3,
        steps_per_sample: int = 10,
        max_samples: int = 20,
        gp_noise: float = 0.8,
        log_path: Optional[str] = None,
        seed: int = 0x9E3779B97F4A7C15,
        seeds: Sequence[TunedParams] = (),
    ) -> None:
        if max_samples < 1:
            raise ValueError("max_samples must be >= 1")
        self.initial = initial
        self.current = initial
        self.best = initial
        self.best_score = -math.inf
        self.tune_quant_block = tune_quant_block
        self.tune_hierarchical = tune_hierarchical
        # zero_sharding restructures the step (ZeroState layout), so it is
        # searched only when the session's step builder declares it can
        # accept the knob (autotune_session(tune_zero=True)).
        self.tune_zero = tune_zero
        # overlap restructures the microbatch loop when composed with
        # backward_passes_per_step (OverlapMultiStepsState), so it is
        # gated the same way (autotune_session(tune_overlap=True));
        # num_comm_streams rides the same gate — it only means anything
        # with overlap on.
        self.tune_overlap = tune_overlap
        # The pipeline pair restructures the WHOLE training schedule
        # (microbatch count + virtual-stage interleave are trace-time
        # schedule geometry), so like zero/overlap it is searched only
        # when the session's step builder declares it can rebuild at a
        # proposed (pp_microbatches, pp_interleave)
        # (autotune_session(tune_pp=True, pp_stages=S)). With pp off the
        # encoding drops the segment and both knobs canonicalize dead.
        self.tune_pp = tune_pp
        self.pp_stages = max(0, int(pp_stages))
        self.pp_max_interleave = max(1, int(pp_max_interleave))
        # The MoE pair restructures the dispatch-buffer geometry
        # (capacity is trace-time shape) and the a2a wire dtype, so like
        # zero/overlap/pp it is searched only when the session's step
        # builder declares it can rebuild at a proposed
        # (moe_capacity_factor, moe_quantized)
        # (autotune_session(tune_moe=True, moe_experts=E)). With moe
        # off the encoding drops the segment and both knobs
        # canonicalize dead.
        self.tune_moe = tune_moe
        self.moe_experts = max(0, int(moe_experts))
        # The serving pair restructures the decode step (the speculative
        # window W = k+1 is trace-time geometry) and the prefill→decode
        # KV wire dtype, so like zero/overlap/pp/moe it is searched only
        # when the session drives a serving engine that can rebuild at a
        # proposed (spec_draft_k, kv_migrate_quantized)
        # (autotune_session(tune_serve=True)). In a training session the
        # encoding drops the segment and both knobs canonicalize dead.
        self.tune_serve = tune_serve
        self.warmup_samples = max(0, warmup_samples)
        self.steps_per_sample = max(1, steps_per_sample)
        self.max_samples = max_samples
        self.gp_noise = gp_noise
        self.done = False
        self.history: List[Tuple[TunedParams, float]] = []
        self._warmups_done = 0
        self._rng = _XorShift(seed)
        self._tried = {self._unit_key(initial)}
        # Warm-start seeds (docs/cost-model.md): the cost model's ranked
        # shortlist, walked IN ORDER before the GP proposes — the first
        # scored trials are the analytically best-priced plans, so the
        # GP fits an informed neighborhood instead of random exploration.
        self._seed_queue: List[TunedParams] = []
        seen_seeds = set(self._tried)
        for s in seeds:
            c = self._canonicalize(s)
            k = self._unit_key(c)
            if k in seen_seeds:
                continue
            seen_seeds.add(k)
            self._seed_queue.append(c)
        self.seeded = len(self._seed_queue)
        self._log: Optional[IO[str]] = None
        self._csv = None
        if log_path:
            os.makedirs(os.path.dirname(os.path.abspath(log_path)),
                        exist_ok=True)
            self._log = open(log_path, "w", newline="")
            self._csv = csv.writer(self._log)
            self._csv.writerow(CSV_FIELDS)
            self._log.flush()

    # -- unit-cube coordinates (parameter_manager.cc:63-86) -------------

    def _to_unit(self, p: TunedParams) -> Tuple[float, ...]:
        f = math.log2(max(1, p.fusion_threshold_bytes))
        q = math.log2(max(1, p.quant_block))
        s = math.log2(max(1, p.num_comm_streams))
        ppm = math.log2(max(2, p.pp_microbatches or 2))
        ppv = math.log2(max(1, p.pp_interleave))
        cap = min(_MAX_MOE_CAP,
                  max(_MIN_MOE_CAP, p.moe_capacity_factor
                      or _MIN_MOE_CAP))
        return (
            (f - _MIN_FUSION_LOG) / (_MAX_FUSION_LOG - _MIN_FUSION_LOG),
            (q - _MIN_QBLOCK_LOG) / (_MAX_QBLOCK_LOG - _MIN_QBLOCK_LOG),
            # Booleans (relaxed categoricals) sit at 0.25/0.75, well
            # inside the box.
            0.75 if p.hierarchical_allreduce else 0.25,
            # zero_stage 0/1/2 sits at the thirds' centers (stage 3
            # restructures the training loop and is never searched).
            (min(p.zero_stage, 2) + 0.5) / 3.0,
            0.75 if p.overlap else 0.25,
            s / _MAX_STREAMS_LOG,
            (ppm - _MIN_PPM_LOG) / (_MAX_PPM_LOG - _MIN_PPM_LOG),
            ppv / _MAX_PPV_LOG,
            (cap - _MIN_MOE_CAP) / (_MAX_MOE_CAP - _MIN_MOE_CAP),
            0.75 if p.moe_quantized else 0.25,
            min(_MAX_SPEC_K, max(0, p.spec_draft_k)) / _MAX_SPEC_K,
            0.75 if p.kv_migrate_quantized else 0.25,
            0.75 if p.pp_schedule == "zb1" else 0.25,
        )

    def _from_unit(self, u) -> TunedParams:
        f = _MIN_FUSION_LOG + u[0] * (_MAX_FUSION_LOG - _MIN_FUSION_LOG)
        if self.tune_quant_block:
            # Snap to a power of two: scale blocks align with the
            # ATOMIC_UNIT-padded bucket layout (ops/fusion.py).
            q = _MIN_QBLOCK_LOG + u[1] * (_MAX_QBLOCK_LOG - _MIN_QBLOCK_LOG)
            qblock = 1 << max(int(_MIN_QBLOCK_LOG),
                              min(int(_MAX_QBLOCK_LOG), round(q)))
        else:
            qblock = self.initial.quant_block
        hier = (u[2] >= 0.5 if self.tune_hierarchical
                else self.initial.hierarchical_allreduce)
        stage = (min(2, int(u[3] * 3)) if self.tune_zero
                 else self.initial.zero_stage)
        if self.tune_overlap:
            ov = u[4] >= 0.5
            # pow2 snap 1-4; only meaningful with overlap on — pin the
            # dead dimension so it never splits otherwise-equal trials.
            ns = 1 << max(0, min(int(_MAX_STREAMS_LOG),
                                 round(u[5] * _MAX_STREAMS_LOG)))
            if not ov:
                ns = 1
        else:
            ov = self.initial.overlap
            ns = self.initial.num_comm_streams
        if self.tune_pp:
            # pow2 snap, then round up to a multiple of the stage count
            # (the interleaved grouping needs M % stages == 0).
            ppm_l = _MIN_PPM_LOG + u[6] * (_MAX_PPM_LOG - _MIN_PPM_LOG)
            ppm = 1 << max(int(_MIN_PPM_LOG),
                           min(int(_MAX_PPM_LOG), round(ppm_l)))
            if self.pp_stages > 1:
                ppm = max(ppm, self.pp_stages)
                ppm += (-ppm) % self.pp_stages
            ppv = 1 << max(0, min(int(_MAX_PPV_LOG),
                                  round(u[7] * _MAX_PPV_LOG)))
            ppv = min(ppv, self.pp_max_interleave)
            # Schedule family (v11): a relaxed boolean at the tail so
            # pre-v11 unit tuples stay valid coordinates.
            u12 = u[12] if len(u) > 12 else 0.25
            pps = "zb1" if u12 >= 0.5 else "interleaved_1f1b"
        else:
            ppm = self.initial.pp_microbatches
            ppv = self.initial.pp_interleave
            pps = self.initial.pp_schedule
        if self.tune_moe:
            # Quarter-snap inside the [1.0, 2.0] box: capacity is a
            # trace-time buffer shape, so the space is effectively
            # discrete (finer steps cannot change the padded capacity
            # by more than rounding). Tolerant of pre-v9 unit tuples
            # lacking the trailing dims.
            u8 = u[8] if len(u) > 8 else 0.25
            u9 = u[9] if len(u) > 9 else 0.25
            cap = _MIN_MOE_CAP + u8 * (_MAX_MOE_CAP - _MIN_MOE_CAP)
            cap = round(cap * 4) / 4.0
            moe_cap = min(_MAX_MOE_CAP, max(_MIN_MOE_CAP, cap))
            moe_q = u9 >= 0.5
        else:
            moe_cap = self.initial.moe_capacity_factor
            moe_q = self.initial.moe_quantized
        if self.tune_serve:
            # Integer-snap the draft window inside [0, _MAX_SPEC_K]
            # (the window W = k+1 is trace-time geometry — the space IS
            # discrete). Tolerant of pre-v10 unit tuples lacking the
            # trailing dims.
            u10 = u[10] if len(u) > 10 else 0.0
            u11 = u[11] if len(u) > 11 else 0.25
            sv_k = max(0, min(_MAX_SPEC_K, round(u10 * _MAX_SPEC_K)))
            sv_q = u11 >= 0.5
        else:
            sv_k = self.initial.spec_draft_k
            sv_q = self.initial.kv_migrate_quantized
        return self._canonicalize(TunedParams(
            fusion_threshold_bytes=int(2.0 ** f),
            quant_block=qblock,
            hierarchical_allreduce=hier,
            zero_stage=stage,
            overlap=ov,
            num_comm_streams=ns,
            pp_microbatches=ppm,
            pp_interleave=ppv,
            pp_schedule=pps,
            moe_capacity_factor=moe_cap,
            moe_quantized=moe_q,
            spec_draft_k=sv_k,
            kv_migrate_quantized=sv_q,
        ))

    def _plan_of(self, p: TunedParams) -> str:
        """The canonical wire-plan encoding of a knob setting — the
        search-space coordinate the GP actually explores (``plan``
        column of the CSV, ``plan`` field of the v5 cache entry)."""
        return _wire_planner.encode_tuned(
            p, quantized=self.tune_quant_block, pp=self.tune_pp,
            moe=self.tune_moe, serve=self.tune_serve)

    def _canonicalize(self, p: TunedParams) -> TunedParams:
        """Snap a proposal onto its wire plan: knobs that are dead in
        the plan it encodes (hierarchical under the ZeRO rs+ag split,
        stream count with overlap off) reset to the canonical value, so
        equal plans are equal TunedParams and dedup as one trial."""
        d = _wire_planner.decode_tuned(self._plan_of(p))
        return dataclasses.replace(
            p,
            hierarchical_allreduce=d["hierarchical_allreduce"],
            zero_stage=d["zero_stage"],
            overlap=d["overlap"],
            num_comm_streams=d["num_comm_streams"],
            quant_block=d.get("quant_block", p.quant_block),
            pp_microbatches=d.get("pp_microbatches", 0),
            pp_interleave=d.get("pp_interleave", 1),
            pp_schedule=d.get("pp_schedule", "interleaved_1f1b"),
            moe_capacity_factor=d.get("moe_capacity_factor", 0.0),
            moe_quantized=d.get("moe_quantized", False),
            spec_draft_k=d.get("spec_draft_k", 0),
            kv_migrate_quantized=d.get("kv_migrate_quantized", False))

    def _unit_key(self, p: TunedParams) -> tuple:
        """Dedup key: the snapped fusion threshold plus the canonical
        plan encoding, so two unit points that collapse to the same
        compiled wire plan count as one trial."""
        # Fusion threshold dedups at 1/4-octave resolution — finer than
        # that cannot change a bucket plan by more than rounding.
        return (round(math.log2(max(1, p.fusion_threshold_bytes)) * 4),
                p.quant_block, self._plan_of(p))

    # -- sampling loop ---------------------------------------------------

    @property
    def warming_up(self) -> bool:
        return (not self.done
                and self._warmups_done < self.warmup_samples)

    @property
    def samples_done(self) -> int:
        return len(self.history)

    def peek_next(self) -> Optional[TunedParams]:
        """The setting the NEXT ``record_sample`` will make current,
        when that is knowable without the pending score: the initial
        setting during warmup (warmup windows never advance it), the
        first untried cost-model seed during the seed-queue phase.
        None once proposals are GP-driven (they depend on the score
        being measured right now) or when the next sample freezes the
        session — the driver's compile-ahead prefetch only overlaps
        builds this method can name exactly (docs/compile.md)."""
        if self.done:
            return None
        if self._warmups_done < self.warmup_samples:
            return self.current
        if len(self.history) + 1 >= self.max_samples:
            return None  # next record freezes at best: no new trial
        for cand in self._seed_queue:
            if self._unit_key(cand) not in self._tried:
                return cand
        return None

    def record_sample(self, score: float, *,
                      compile_ms: float = 0.0,
                      compile_cache_hit: bool = False) -> None:
        """Feed one scored window (steps/sec of ``current``); advances the
        warmup → sample → freeze machine (parameter_manager.cc:139-194).
        ``compile_ms``/``compile_cache_hit`` describe the trial's build
        step for the v12 CSV columns (docs/compile.md)."""
        if self.done:
            raise RuntimeError("record_sample() after convergence")
        if self._warmups_done < self.warmup_samples:
            self._warmups_done += 1
            return  # discarded: current stays the initial setting
        score = float(score)
        self.history.append((self.current, score))
        self._write_row(score, compile_ms, compile_cache_hit)
        if score > self.best_score:
            self.best_score = score
            self.best = self.current
        if len(self.history) >= self.max_samples:
            self._freeze()
            return
        self.current = self._propose_next()

    def _write_row(self, score: float, compile_ms: float = 0.0,
                   compile_cache_hit: bool = False) -> None:
        if self._csv is None:
            return
        p = self.current
        self._csv.writerow([len(self.history), p.fusion_threshold_bytes,
                            p.quant_block,
                            int(p.hierarchical_allreduce),
                            int(p.zero_sharding),
                            int(p.zero_stage),
                            int(p.overlap),
                            int(p.num_comm_streams),
                            int(p.pp_microbatches),
                            int(p.pp_interleave),
                            f"{p.moe_capacity_factor:g}",
                            int(p.moe_quantized),
                            int(p.spec_draft_k),
                            int(p.kv_migrate_quantized),
                            p.pp_schedule,
                            f"{score:.6g}",
                            self._plan_of(p),
                            f"{float(compile_ms):.3f}",
                            int(compile_cache_hit)])
        self._log.flush()

    def _freeze(self) -> None:
        self.done = True
        self.current = self.best
        self.close()
        log.info(
            "autotune converged after %d samples: fusion_threshold=%d "
            "quant_block=%d hierarchical=%s zero_stage=%d overlap=%s "
            "streams=%d (best %.3f steps/sec)",
            len(self.history), self.best.fusion_threshold_bytes,
            self.best.quant_block, self.best.hierarchical_allreduce,
            self.best.zero_stage, self.best.overlap,
            self.best.num_comm_streams, self.best_score)

    def _sample_unit(self) -> Tuple[float, ...]:
        # The v11 tail dim (pp_schedule) draws from the stream only
        # when the pp pair is live, so pre-v11 seed trajectories — and
        # any replayed logs — are unchanged for non-pipelined sessions.
        u = [self._rng.next() for _ in range(_DIMS - 1)]
        u.append(self._rng.next() if self.tune_pp else 0.25)
        if not self.tune_hierarchical:
            u[2] = 0.25
        if not self.tune_zero:
            u[3] = 0.25
        if not self.tune_overlap:
            u[4] = 0.25
            u[5] = 0.0
        if not self.tune_pp:
            u[6] = 0.0
            u[7] = 0.0
        if not self.tune_moe:
            u[8] = 0.25
            u[9] = 0.25
        if not self.tune_serve:
            u[10] = 0.0
            u[11] = 0.25
        return tuple(u)

    def _propose_next(self) -> TunedParams:
        """Warm-start seeds first (the cost model's ranked shortlist,
        in predicted-ms order); then EI-argmax over random candidates
        once the GP fits, random exploration before that
        (parameter_manager.cc:88-137). Prefers configurations not yet
        tried (each repeat costs a recompile)."""
        while self._seed_queue:
            cand = self._seed_queue.pop(0)
            key = self._unit_key(cand)
            if key in self._tried:
                continue  # a prior trial already covered this plan
            self._tried.add(key)
            return cand
        xs = [self._to_unit(p) for p, _ in self.history]
        ys = [s for _, s in self.history]
        # Normalize scores to zero-mean/unit-variance for the GP.
        mean = sum(ys) / len(ys)
        sd = math.sqrt(sum((y - mean) ** 2 for y in ys) / len(ys)) or 1.0
        yn = [(y - mean) / sd for y in ys]
        best_n = max(yn)
        gp = GaussianProcess(_DIMS, 0.3, self.gp_noise)
        fitted = len(xs) >= 2 and gp.fit(xs, yn)

        # EI-argmax among candidates snapping to an untried configuration;
        # if every candidate collapses onto tried points (degenerate
        # space), take the overall argmax. With a fitted GP, EI
        # evaluates in one batched predict (gp.predict_batch) over the
        # 1000-candidate pool; unfitted, each candidate draws its
        # random score right after its coordinates (the original
        # interleaved order, so replay seeds keep their trajectories).
        cands, eis = [], []
        for _ in range(1000 if fitted else 64):
            cands.append(self._sample_unit())
            if not fitted:
                eis.append(self._rng.next())
        if fitted:
            eis = gp.expected_improvement_batch(cands, best_n)
        new_x, new_ei = None, -1.0
        any_x, any_ei = None, -1.0
        for cand, ei in zip(cands, eis):
            if any_x is None or ei > any_ei:
                any_x, any_ei = cand, ei
            if ei > new_ei and \
                    self._unit_key(self._from_unit(cand)) not in self._tried:
                new_x, new_ei = cand, ei
        proposal = self._from_unit(new_x if new_x is not None else any_x)
        self._tried.add(self._unit_key(proposal))
        return proposal

    def close(self) -> None:
        if self._log is not None:
            self._log.close()
            self._log = None
            self._csv = None


def read_log(path: str) -> List[dict]:
    """Parse a ``HOROVOD_AUTOTUNE_LOG`` CSV back into typed rows — the
    round-trip counterpart of the manager's writer (tests assert the
    schema; analysis notebooks get typed values for free).

    Tolerant of older schemas: pre-v4 logs lack ``zero_stage``/
    ``overlap``/``num_comm_streams`` (the boolean ``zero_sharding``
    named stage 2), pre-v5 logs lack the ``plan`` encoding column — it
    is re-derived from the knob columns so every row carries one."""
    rows: List[dict] = []
    with open(path, newline="") as f:
        for rec in csv.DictReader(f):
            sharding = bool(int(rec.get("zero_sharding", 0) or 0))
            # Pre-v4 logs carried only the boolean; it named stage 2.
            stage = int(rec.get("zero_stage", 2 if sharding else 0) or 0)
            row = {
                "sample": int(rec["sample"]),
                "fusion_threshold_bytes": int(
                    rec["fusion_threshold_bytes"]),
                "quant_block": int(rec["quant_block"]),
                "hierarchical_allreduce": bool(
                    int(rec["hierarchical_allreduce"])),
                "zero_sharding": sharding or stage > 0,
                "zero_stage": stage,
                "overlap": bool(int(rec.get("overlap", 0) or 0)),
                "num_comm_streams": int(rec.get("num_comm_streams", 1)
                                        or 1),
                "pp_microbatches": int(rec.get("pp_microbatches", 0)
                                       or 0),
                "pp_interleave": int(rec.get("pp_interleave", 1) or 1),
                "moe_capacity_factor": float(
                    rec.get("moe_capacity_factor", 0.0) or 0.0),
                "moe_quantized": bool(int(rec.get("moe_quantized", 0)
                                          or 0)),
                "spec_draft_k": int(rec.get("spec_draft_k", 0) or 0),
                "kv_migrate_quantized": bool(
                    int(rec.get("kv_migrate_quantized", 0) or 0)),
                "pp_schedule": str(rec.get("pp_schedule")
                                   or "interleaved_1f1b"),
                "score_steps_per_sec": float(rec["score_steps_per_sec"]),
                # v12 compile pair; pre-v12 logs never timed the build.
                "compile_ms": float(rec.get("compile_ms", 0.0) or 0.0),
                "compile_cache_hit": bool(
                    int(rec.get("compile_cache_hit", 0) or 0)),
            }
            enc = (rec.get("plan") or "").strip()
            if not enc:  # pre-v5 log: derive the canonical encoding
                enc = _wire_planner.encode_tuned(
                    TunedParams.from_dict(row))
            row["plan"] = enc
            rows.append(row)
    return rows
