"""The tuning-session driver loop: recompile-per-trial + warm-start cache.

Reference: the coordinator's per-cycle ``parameter_manager.Update`` hook
(operations.cc:614-621) — there, new knob values apply between cycles at
zero cost. On the compiled path every knob is baked into the traced
program (bucket plans are trace-time, ops/fusion.py), so a trial is a
**recompile**: :func:`autotune_session` asks the caller to rebuild its
step for each :class:`TunedParams` proposal, times a scoring window of
real steps, and feeds wall-clock step rate to the
:class:`~.parameter_manager.ParameterManager`.

Recompiles dominate session cost, so the frozen winner is persisted to
the shared autotune cache (``HOROVOD_AUTOTUNE_CACHE``, one JSON file with
the Pallas block-size entries of ops/kernel_autotune.py) keyed on
(model-tree-hash, mesh shape, world size): a rerun of the same job skips
every trial and compiles once, straight at the winner.
"""

from __future__ import annotations

import dataclasses
import hashlib
import logging
import threading
import time
from typing import Callable, List, Optional, Tuple

from ..common import basics
from .parameter_manager import ParameterManager, TunedParams

log = logging.getLogger("horovod_tpu.autotune")

# Cache-entry schema version; bump when TunedParams gains/changes knobs.
# v2: + zero_sharding (ZeRO-1 sharded optimizer).
# v3: + overlap / num_comm_streams (overlapped gradient reduction).
# v4: zero_sharding → zero_stage {0,1,2} (ZeRO-2/3; from_dict still
#     reads pre-v4 entries, but the key's version gates real reuse).
# v5: + the canonical wire-plan encoding (horovod_tpu.plan encode_tuned:
#     leg order | per-hop dtype | stream placement) stored alongside the
#     knobs — the GP now searches plan space (docs/wire-plan.md);
#     from_dict/load stay tolerant of v3/v4 entries.
# v6: a `fused` kernel-backend knob that is gone again; from_dict takes
#     what it knows by key, so an entry that still carries it loads.
# v7: cost-model-driven warm start (docs/cost-model.md) — the cache key
#     carries the full geometry fingerprint (mesh shape x world x device
#     kind, basics.mesh_geometry: a winner tuned on one chip kind never
#     warm-starts another) and entries record the analytic predicted_ms
#     of the frozen winner beside its measured score, so drift between
#     the cost model and reality is auditable from the cache alone.
#     from_dict/load stay tolerant of v6/v5 entries (the params schema
#     is unchanged; the version segment in the key gates real reuse).
# v8: pipeline parallelism (docs/pipeline.md) — TunedParams gains the
#     pp_microbatches/pp_interleave pair (tune_pp-gated; the plan
#     encoding's trailing `|ppM/V` segment), and pipeline meshes carry
#     a `ppS` marker in the geometry fingerprint so a winner tuned at
#     one stage count never warm-starts another. from_dict/load stay
#     tolerant of v7/v6 entries (pp fields default to the dead-knob
#     0 / 1 values — the exact pre-v8 step).
# v9: expert-parallel MoE (docs/moe.md) — TunedParams gains the
#     moe_capacity_factor/moe_quantized pair (tune_moe-gated; the plan
#     encoding's trailing `|moeC/q8|fp` segment), and expert-parallel
#     meshes carry an `epE` marker in the geometry fingerprint so a
#     winner tuned at one expert-group count never warm-starts another.
#     from_dict/load stay tolerant of v8/v7 entries (moe fields default
#     to the dead-knob 0.0 / False values — the exact pre-v9 step).
# v10: disaggregated serving (docs/serving.md) — TunedParams gains the
#     spec_draft_k/kv_migrate_quantized pair (tune_serve-gated; the plan
#     encoding's trailing `|svK/q8|fp` segment). from_dict/load stay
#     tolerant of v9/v8 entries (serve fields default to the dead-knob
#     0 / False values — the exact pre-v10 step).
# v11: zero-bubble pipelines (docs/pipeline.md) — TunedParams gains the
#     pp_schedule family knob (tune_pp-gated; the plan encoding's
#     optional `|zb1` segment riding the `|ppM/V` group). from_dict/load
#     stay tolerant of v10/v9 entries (pp_schedule defaults to the
#     dead-knob "interleaved_1f1b" value — the exact pre-v11 step).
# v12: compile-once runtime (docs/compile.md) — the trial CSV gains the
#     per-trial `compile_ms`/`compile_cache_hit` pair (the previously
#     untimed build+absorb step, now bracketed by AUTOTUNE:COMPILE
#     spans and overlapped with the prior trial's measurement window
#     when the next setting is knowable). The TunedParams schema is
#     unchanged; read_log stays tolerant of v11/v10 logs lacking the
#     new columns (compile_ms defaults 0.0, compile_cache_hit False).
_CACHE_VERSION = 12

# Process-lifetime session counter — hvd.shutdown() warns when
# HOROVOD_AUTOTUNE=1 never reached a session (the knob is otherwise a
# silent no-op on the compiled path; see docs/autotune.md).
_sessions_run = [0]


def sessions_run() -> int:
    """How many tuning sessions (including cache hits) this process ran."""
    return _sessions_run[0]


@dataclasses.dataclass(frozen=True)
class AutotuneResult:
    """What a tuning session produced.

    ``params`` is the frozen winner (feed it back as the
    ``tuned_params=`` override of :class:`horovod_tpu.DistributedOptimizer`
    / :func:`horovod_tpu.allreduce_pytree`). ``history`` is the scored
    trial list in order; empty on a warm-start ``cache_hit``.
    """

    params: TunedParams
    history: Tuple[Tuple[TunedParams, float], ...] = ()
    cache_hit: bool = False
    best_score: Optional[float] = None
    # Cost-model warm start (docs/cost-model.md): how many priced seeds
    # the session walked before the GP proposed, and the ranked
    # shortlist rows (plan encoding + predicted_ms) they came from.
    warm_start: int = 0
    shortlist: Tuple[dict, ...] = ()

    @property
    def samples(self) -> int:
        return len(self.history)


def cache_key_for(tree, mesh=None) -> str:
    """Warm-start cache key: (model-tree-hash, geometry fingerprint).

    ``tree`` is any pytree whose *structure and leaf shapes/dtypes*
    identify the workload (pass the parameter tree); values never enter
    the hash, so a checkpoint restore keys the same as a fresh init. The
    bucket plan is a pure function of leaf order/shape/dtype
    (ops/fusion.py plan_buckets is deterministic), which is exactly what
    makes this key sound. The geometry half is
    :func:`~horovod_tpu.common.basics.mesh_geometry` — mesh shape x
    world x device kind, shared with the link-calibration store, so a
    winner tuned on one chip kind never warm-starts another.
    """
    import jax

    if isinstance(tree, str):
        sig = tree
    else:
        leaves, treedef = jax.tree.flatten(tree)
        parts = [str(treedef)]
        for leaf in leaves:
            parts.append(f"{jax.numpy.shape(leaf)}:"
                         f"{jax.numpy.asarray(leaf).dtype}")
        sig = hashlib.md5("|".join(parts).encode()).hexdigest()
    geo = basics.mesh_geometry(mesh=mesh)
    return f"collective_tune|{sig}|{geo}|v{_CACHE_VERSION}"


def load_cached_params(key: str) -> Optional[TunedParams]:
    """The frozen winner cached under ``key``, or None."""
    from ..ops import kernel_autotune

    entry = kernel_autotune.cache_lookup(key)
    if not isinstance(entry, dict) or "params" not in entry:
        return None
    try:
        return TunedParams.from_dict(entry["params"])
    except (KeyError, TypeError, ValueError):
        return None  # stale/foreign entry: tune fresh rather than crash


def _store_cached_params(key: str, params: TunedParams, *,
                         score: float, samples: int,
                         quantized: bool = False, pp: bool = False,
                         moe: bool = False, serve: bool = False,
                         predicted_ms: Optional[float] = None) -> None:
    from ..plan import planner as _wire_planner
    from ..ops import kernel_autotune

    entry = {
        "params": params.as_dict(),
        "plan": _wire_planner.encode_tuned(params, quantized=quantized,
                                           pp=pp, moe=moe, serve=serve),
        "score_steps_per_sec": score,
        "samples": samples,
        "geometry": basics.mesh_geometry(),
    }
    if predicted_ms is not None:
        # v7: the analytic prediction for the winner, stored beside the
        # measured score so cost-model drift is auditable from the cache
        # alone (docs/cost-model.md).
        entry["predicted_ms"] = round(float(predicted_ms), 6)
    kernel_autotune.cache_store(key, entry)


def _priced_seeds(payload_bytes: float, k: int, *, initial: TunedParams,
                  quantized: bool, tune_hierarchical: bool,
                  tune_zero: bool, tune_overlap: bool,
                  tune_pp: bool = False,
                  pp_stages: int = 0, pp_max_interleave: int = 1,
                  tune_moe: bool = False, moe_experts: int = 0):
    """Top-``k`` cost-model-priced candidates for this session's search
    space (docs/cost-model.md): the planner enumerates every legal plan
    the session's gates allow, prices them with the calibrated (or
    static) link model, and the ranked head seeds the GP."""
    from ..plan import calibrate as _calibrate
    from ..plan import planner as _wire_planner

    model = _calibrate.get_cost_model()
    return _wire_planner.shortlist(
        payload_bytes, quantized=quantized, k=k,
        tune_hierarchical=tune_hierarchical, tune_zero=tune_zero,
        tune_overlap=tune_overlap,
        tune_pp=tune_pp, pp_stages=pp_stages,
        pp_max_interleave=pp_max_interleave,
        tune_moe=tune_moe, moe_experts=moe_experts,
        initial=initial, model=model)


def _timeline_instant(name: str, args: dict) -> None:
    tl = basics._state.timeline if basics.is_initialized() else None
    if tl is not None:
        tl.instant(name, tid="autotune", args=args)


def _timeline_span(name: str, ph: str, args: Optional[dict] = None) -> None:
    # Compile spans ride their own tid: a background prefetch build can
    # open while the main autotune tid is mid-window, and per-tid B/E
    # balance (span_audit) must hold on both. Builds themselves are
    # serialized (at most one prefetch thread, joined before any
    # foreground build), so this tid never nests concurrent spans.
    tl = basics._state.timeline if basics.is_initialized() else None
    if tl is not None:
        tl.emit(name, ph, tid="autotune.compile", args=args)


def _build_trial(make_step, tuned: TunedParams, box: dict,
                 *, background: bool) -> None:
    """Build (and absorb the compile of) one trial's step into ``box``.

    ``box`` gains ``step`` (the callable to time), ``compile_ms`` and
    ``cache_hit`` (executable-cache miss delta == 0 across the build) on
    success, ``error`` on failure. Runs either inline or as the
    compile-ahead prefetch thread overlapping the prior trial's
    measurement window (docs/compile.md); AUTOTUNE:COMPILE brackets the
    build either way — the step that was untimed before v12."""
    from .. import compile as _xc

    s0 = _xc.stats()
    _timeline_span("AUTOTUNE:COMPILE", "B",
                   {"background": background, **tuned.as_dict()})
    try:
        t0 = time.perf_counter()
        step = make_step(tuned)
        if hasattr(step, "lower"):
            # An un-called jit step: drive the AOT path so the XLA
            # compile genuinely happens here (on the prefetch thread,
            # off the measured window) instead of at first dispatch.
            step = step.lower().compile()
        box["step"] = step
        box["compile_ms"] = (time.perf_counter() - t0) * 1e3
        s1 = _xc.stats()
        box["cache_hit"] = (s1["misses"] == s0["misses"]
                            and s1["hits"] > s0["hits"])
    except Exception as e:
        box["error"] = e
    finally:
        _timeline_span("AUTOTUNE:COMPILE", "E",
                       {"background": background,
                        "compile_ms": round(box.get("compile_ms", 0.0), 3)})


def autotune_session(
    make_step: Callable[[TunedParams], Callable[[], object]],
    *,
    cache_key=None,
    initial: Optional[TunedParams] = None,
    enabled: Optional[bool] = None,
    tune_quant_block: Optional[bool] = None,
    tune_hierarchical: bool = True,
    tune_zero: bool = False,
    tune_overlap: bool = False,
    tune_pp: bool = False,
    pp_stages: int = 0,
    pp_max_interleave: int = 1,
    tune_moe: bool = False,
    moe_experts: int = 0,
    tune_serve: bool = False,
    warmup_samples: Optional[int] = None,
    steps_per_sample: Optional[int] = None,
    max_samples: Optional[int] = None,
    gp_noise: Optional[float] = None,
    log_path: Optional[str] = None,
    use_cache: bool = True,
    seed: int = 0x9E3779B97F4A7C15,
    warm_start=None,
) -> AutotuneResult:
    """Run an online tuning session and return the frozen winner.

    ``make_step(tuned)`` must build (and implicitly compile) the training
    step with the :class:`TunedParams` override applied — thread ``tuned``
    into ``DistributedOptimizer(tuned_params=...)`` or
    ``allreduce_pytree(tuned_params=...)`` — and return a zero-argument
    callable that advances ONE real training step (owning its state in a
    closure) and returns that step's outputs, which the driver blocks on
    for wall-clock timing. It is called once per trial; each call is a
    retrace.

    Knob defaults come from :func:`horovod_tpu.init`'s Config
    (``HOROVOD_AUTOTUNE_WARMUP_SAMPLES`` / ``_STEPS_PER_SAMPLE`` /
    ``_BAYES_OPT_MAX_SAMPLES`` / ``_GAUSSIAN_PROCESS_NOISE`` /
    ``_LOG``); explicit arguments override. ``enabled`` defaults to the
    ``HOROVOD_AUTOTUNE`` knob: with it off the session is a no-op that
    returns the initial (hand-set) parameters untouched, keeping the
    default path bit-identical.

    ``tune_zero`` adds the ZeRO-sharding flag to the search space; leave
    it False (the default) unless ``make_step`` actually threads
    ``tuned.zero_sharding`` through (``DistributedOptimizer(tuned_params=
    tuned)`` + ``hvd.value_and_grad(..., reduce=False)`` do) — the
    knob restructures the optimizer state, so a step built without it
    would silently score a config it never ran. ``tune_overlap`` gates
    the ``overlap`` + ``num_comm_streams`` pair the same way (overlap ×
    ``backward_passes_per_step`` restructures the accumulation state,
    docs/overlap.md). ``tune_pp`` (with ``pp_stages`` = the mesh's stage
    count and ``pp_max_interleave`` = the deepest virtual-stage split the
    model's layer count allows) adds the pipeline schedule pair —
    ``pp_microbatches`` (pow2, snapped to a stage-count multiple) and
    ``pp_interleave`` (pow2) — gated exactly like zero/overlap: both
    restructure the traced schedule, so only a step builder that
    rebuilds at the proposed values may search them (docs/pipeline.md).
    ``tune_moe`` (with ``moe_experts`` = the mesh's expert-group count)
    adds the MoE routing pair — ``moe_capacity_factor``
    (quarter-snapped 1.0–2.0) and ``moe_quantized`` (the int8 a2a
    wire) — under the same gate: capacity is trace-time dispatch-buffer
    shape, so only a step builder that rebuilds at the proposed values
    may search it (docs/moe.md). ``tune_serve`` adds the
    disaggregated-serving pair — ``spec_draft_k`` (the speculative
    draft window, 0–4) and ``kv_migrate_quantized`` (the int8+EF
    prefill→decode KV wire) — under the same gate: the window is
    trace-time decode geometry, so only a serving session whose
    ``make_step`` rebuilds its engines at the proposed values may
    search it (docs/serving.md).

    ``cache_key`` (a pytree — pass the parameter tree — or a string)
    activates the warm-start cache: a prior frozen winner for the same
    (model, geometry) returns immediately with ``cache_hit=True`` and
    zero trials; a fresh session persists its winner on convergence.
    ``use_cache=False`` forces re-tuning (the winner still overwrites the
    cache entry).

    ``warm_start`` (default: the ``HOROVOD_AUTOTUNE_WARM_START`` config,
    0 = off) seeds the GP with the cost model's ranked shortlist
    (docs/cost-model.md): an integer K derives the top-K priced
    candidates for this session's search space (the gradient payload
    size comes from the ``cache_key`` pytree, so pass the parameter
    tree), or pass an explicit sequence of :class:`TunedParams`. Seeds
    are scored FIRST, in predicted-ms order, before the GP proposes; a
    warm-started session also shrinks its trial budget to
    ``len(seeds) + 4`` windows unless ``max_samples`` is set explicitly
    — the analytic shortlist replaces the cold exploration phase.
    """
    import jax

    cfg = basics.config() if basics.is_initialized() else None
    if enabled is None:
        enabled = bool(cfg.autotune) if cfg is not None else False
    if initial is None:
        initial = (TunedParams.from_config(cfg) if cfg is not None
                   else TunedParams())
    if not enabled:
        log.info("autotune_session: HOROVOD_AUTOTUNE is off — returning "
                 "the configured parameters untuned")
        return AutotuneResult(params=initial)
    _sessions_run[0] += 1
    if tune_quant_block is None:
        tune_quant_block = bool(cfg.quantized_allreduce) if cfg else False
    if warmup_samples is None:
        warmup_samples = cfg.autotune_warmup_samples if cfg else 3
    if steps_per_sample is None:
        steps_per_sample = cfg.autotune_steps_per_sample if cfg else 10
    explicit_max = max_samples is not None
    if max_samples is None:
        max_samples = cfg.autotune_bayes_opt_max_samples if cfg else 20
    if gp_noise is None:
        gp_noise = cfg.autotune_gaussian_process_noise if cfg else 0.8
    if log_path is None:
        log_path = cfg.autotune_log if cfg else None
    if warm_start is None:
        warm_start = getattr(cfg, "autotune_warm_start", 0) if cfg else 0

    key = cache_key_for(cache_key) if cache_key is not None else None
    if key is not None and use_cache:
        cached = load_cached_params(key)
        if cached is not None:
            log.warning(
                "horovod_tpu autotune: warm-start cache hit (%s) — "
                "skipping trials, compiling straight at fusion_threshold="
                "%d quant_block=%d hierarchical=%s", key,
                cached.fusion_threshold_bytes, cached.quant_block,
                cached.hierarchical_allreduce)
            _timeline_instant("AUTOTUNE:CACHE_HIT",
                              {"key": key, **cached.as_dict()})
            return AutotuneResult(params=cached, cache_hit=True)

    # Gradient payload size (for pricing) from the cache_key pytree.
    payload_bytes = None
    if cache_key is not None and not isinstance(cache_key, str):
        try:
            payload_bytes = float(sum(
                jax.numpy.asarray(l).nbytes
                for l in jax.tree.leaves(cache_key)))
        except Exception:
            payload_bytes = None

    seeds = []
    shortlist_rows = ()
    if isinstance(warm_start, (list, tuple)):
        seeds = list(warm_start)
    elif warm_start and int(warm_start) > 0:
        if payload_bytes:
            ranked = _priced_seeds(
                payload_bytes, int(warm_start), initial=initial,
                quantized=bool(tune_quant_block),
                tune_hierarchical=tune_hierarchical,
                tune_zero=tune_zero, tune_overlap=tune_overlap,
                tune_pp=tune_pp, pp_stages=pp_stages,
                pp_max_interleave=pp_max_interleave,
                tune_moe=tune_moe, moe_experts=moe_experts)
            seeds = [pp.params for pp in ranked]
            shortlist_rows = tuple(pp.as_dict() for pp in ranked)
            if ranked:
                log.warning(
                    "horovod_tpu autotune: cost-model warm start — %d "
                    "priced seeds for a %.1f MB payload, top %s @ "
                    "%.4f predicted ms", len(ranked),
                    payload_bytes / 1e6, ranked[0].plan.encode(),
                    ranked[0].predicted_ms)
        else:
            log.warning(
                "horovod_tpu autotune: warm_start=%s requested but "
                "cache_key is not a pytree (no payload size to price) "
                "— falling back to the cold search", warm_start)
    pm = ParameterManager(
        initial,
        tune_quant_block=tune_quant_block,
        tune_hierarchical=tune_hierarchical,
        tune_zero=tune_zero,
        tune_overlap=tune_overlap,
        tune_pp=tune_pp,
        pp_stages=pp_stages,
        pp_max_interleave=pp_max_interleave,
        tune_moe=tune_moe,
        moe_experts=moe_experts,
        tune_serve=tune_serve,
        warmup_samples=warmup_samples,
        steps_per_sample=steps_per_sample,
        max_samples=max_samples,
        gp_noise=gp_noise,
        log_path=log_path,
        seed=seed,
        seeds=seeds,
    )
    if pm.seeded and not explicit_max:
        # The priced shortlist replaces the cold exploration phase: the
        # budget is the (deduplicated) seeds plus a handful of GP
        # refinements.
        pm.max_samples = min(pm.max_samples, pm.seeded + 4)
        max_samples = pm.max_samples
    log.warning(
        "horovod_tpu autotune: tuning session started (%d warmup + up to "
        "%d scored windows of %d steps; each new configuration is a "
        "recompile%s)", warmup_samples, max_samples, steps_per_sample,
        f"; {pm.seeded} cost-model seeds" if pm.seeded else "")
    _timeline_instant("AUTOTUNE:SESSION_START", {
        "warmup_samples": warmup_samples, "max_samples": max_samples,
        "steps_per_sample": steps_per_sample,
        "warm_start_seeds": pm.seeded})

    built: Optional[Tuple[TunedParams, Callable[[], object]]] = None
    # Compile-ahead prefetch (docs/compile.md): while trial k's window
    # is being measured, trial k+1's step lowers/compiles on a host
    # thread — but only when the NEXT setting is knowable without the
    # pending score (warmup repeats + the cost-model seed queue;
    # ParameterManager.peek_next). GP-phase proposals depend on the
    # score, so those builds stay in the foreground.
    prefetch: Optional[Tuple[TunedParams, threading.Thread, dict]] = None
    while not pm.done:
        tuned = pm.current
        warmup = pm.warming_up
        compile_ms = 0.0
        cache_hit = False
        try:
            if built is None or built[0] != tuned:
                box: dict = {}
                if prefetch is not None:
                    p_tuned, p_thread, p_box = prefetch
                    prefetch = None
                    p_thread.join()
                    if p_tuned == tuned and "step" in p_box:
                        box = p_box
                if "step" not in box:
                    box = {}
                    _build_trial(make_step, tuned, box, background=False)
                    if "error" in box:
                        raise box["error"]
                compile_ms = box.get("compile_ms", 0.0)
                cache_hit = bool(box.get("cache_hit", False))
                built = (tuned, box["step"])
                # One untimed step absorbs this trial's first dispatch
                # so the scored window measures steady state.
                jax.block_until_ready(built[1]())
                log.info("autotune trial build %s: %.0fms compile%s",
                         tuned.as_dict(), compile_ms,
                         " (cache hit)" if cache_hit else "")
            step = built[1]
            nxt = pm.peek_next()
            if nxt is not None and nxt != tuned and prefetch is None:
                p_box: dict = {}
                p_thread = threading.Thread(
                    target=_build_trial, args=(make_step, nxt, p_box),
                    kwargs={"background": True}, daemon=True,
                    name="autotune-compile-ahead")
                p_thread.start()
                prefetch = (nxt, p_thread, p_box)
            t0 = time.perf_counter()
            for _ in range(pm.steps_per_sample):
                out = step()
            jax.block_until_ready(out)
            dt = time.perf_counter() - t0
            score = pm.steps_per_sample / dt if dt > 0 else 0.0
        except Exception as e:
            # A candidate that cannot build or run (compile failure, OOM
            # at a huge threshold) is a terrible score, not a session
            # abort — the GP learns to avoid the region (the same skip
            # discipline as the kernel autotuner's failing candidates).
            built = None
            score = 0.0
            log.warning("autotune trial %s failed (%s: %s); scoring 0",
                        tuned.as_dict(), type(e).__name__, str(e)[:200])
        pm.record_sample(score, compile_ms=compile_ms,
                         compile_cache_hit=cache_hit)
        _timeline_instant("AUTOTUNE:SAMPLE", {
            "warmup": warmup, "score_steps_per_sec": round(score, 4),
            "compile_ms": round(compile_ms, 3),
            "compile_cache_hit": cache_hit,
            **tuned.as_dict()})
        if not warmup:
            log.info("autotune sample %d/%d: %s -> %.3f steps/sec",
                     pm.samples_done, max_samples, tuned.as_dict(), score)

    if prefetch is not None:
        # A frozen session can leave one compile-ahead build in flight;
        # join it so its AUTOTUNE:COMPILE span closes before the
        # timeline can be dumped (span_audit strict mode).
        prefetch[1].join()
    best = pm.best
    _timeline_instant("AUTOTUNE:CONVERGED", {
        "samples": pm.samples_done,
        "score_steps_per_sec": round(pm.best_score, 4),
        **best.as_dict()})
    log.warning(
        "horovod_tpu autotune: converged after %d samples — "
        "fusion_threshold=%d quant_block=%d hierarchical=%s "
        "(%.3f steps/sec)", pm.samples_done, best.fusion_threshold_bytes,
        best.quant_block, best.hierarchical_allreduce, pm.best_score)
    if key is not None:
        predicted_ms = None
        if payload_bytes:
            try:
                from ..plan import calibrate as _calibrate
                from ..plan import cost as _cost
                from ..plan import planner as _wire_planner

                sp = _wire_planner.describe_plan(
                    tuned_params=best, quantized=bool(tune_quant_block),
                    quantized_pod=False,
                    pp_stages=pp_stages if tune_pp else None,
                    moe_experts=moe_experts if tune_moe else 0,
                    moe_quantized=(best.moe_quantized if tune_moe
                                   else None))
                predicted_ms = _cost.price_step(
                    sp, payload_bytes,
                    model=_calibrate.get_cost_model()).predicted_ms
            except Exception:  # pricing must never fail the session
                predicted_ms = None
        _store_cached_params(key, best, score=pm.best_score,
                             samples=pm.samples_done,
                             quantized=bool(tune_quant_block),
                             pp=tune_pp, moe=tune_moe, serve=tune_serve,
                             predicted_ms=predicted_ms)
    return AutotuneResult(params=best, history=tuple(pm.history),
                          best_score=pm.best_score,
                          warm_start=pm.seeded,
                          shortlist=shortlist_rows)
