"""DistributedOptimizer: gradient-allreducing optimizer wrapper.

Reference: ``hvd.DistributedOptimizer`` for TF (tensorflow/__init__.py:293-336,
435-508) and torch (torch/optimizer.py:103-200). There, per-parameter hooks
fire asynchronous allreduces as gradients become ready and ``step()`` blocks
on all handles.

TPU-native redesign
-------------------
Our optimizer story is optax. ``DistributedOptimizer(tx)`` returns an
``optax.GradientTransformation`` whose ``update`` first allreduces the
gradient pytree — fused into per-dtype flat buckets (ops/fusion.py), with
optional bf16/fp16 wire compression — and then runs the wrapped
transformation. Because the whole step is compiled, XLA overlaps the bucket
collectives with the optimizer math and backward compute automatically; the
reference needs its background thread + ready-event machinery
(operations.cc:354-624) to get the same overlap dynamically.

``backward_passes_per_step`` reproduces the reference's local gradient
accumulation (torch/optimizer.py:67-68,133-149): gradients are accumulated
locally for k microbatches and allreduced once, via ``optax.MultiSteps``.

ZeRO sharded optimizer (``zero_stage={1,2,3}`` / ``HOROVOD_ZERO_STAGE``)
------------------------------------------------------------------------
The reference optimizer allreduces full gradients and then has every rank
redundantly run the identical update on a full replica of the moments —
on a pod that wastes ``(world-1)/world`` of the optimizer-state HBM and
repeats the update math ``world`` times. The reduce-scatter decomposition
fixes both: reduce-scatter the fused gradient buckets (half an
allreduce's bytes), run the wrapped optax transformation only on this
rank's contiguous ``1/world`` flat shard of each bucket, and all-gather
the updated values. Moments live as flat ``[bucket_padded // world]``
leaves riding ``P(HVD_AXES)``, cutting optimizer-state bytes per rank by
``world``×, and because the whole step compiles, XLA overlaps the
all-gather of early buckets with the update math of later ones — the
compile-time analogue of T3's fine-grained compute/collective overlap.

The three stages shard progressively more of the step's persistent
state (docs/zero.md):

* **stage 1** — optimizer state only. With
  ``backward_passes_per_step`` k > 1 the gradient accumulator is the
  classic FULL local-gradient pytree (per-rank leading-axis state,
  :class:`ZeroFullMultiStepsState`) — what ZeRO-2 exists to shrink.
* **stage 2** — + gradient-accumulation state: accumulation happens
  AFTER the reduce-scatter on the scattered shard
  (:class:`ZeroMultiStepsState`), so the accumulator is a
  ``[padded // world]`` leaf — grad-state bytes drop ``world``×.
  ``zero=True`` (the PR-4 spelling) is an alias for stage 2; with
  k == 1 stages 1 and 2 are the same program.
* **stage 3** — + parameters: the training loop holds only this rank's
  flat bucket shards (:func:`zero3_shard_params`), the forward pass
  gathers each bucket just in time (:func:`zero3_gather_params`, issued
  in forward order through the PR-5 stream entry points so later
  buckets' gathers overlap with earlier layers' compute), and the
  update returns SHARD updates — no trailing all-gather at all.
  Param + grad + optimizer-state persistent bytes are all ``1/world``.
"""

from __future__ import annotations

import contextlib
from typing import Any, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from ..common import basics
from ..common.config import _env_bool, _env_int
from ..monitor import registry as _metrics
from ..ops import collective_ops as C
from ..ops import fusion
from ..ops.compression import Compression


def _reject_reduced_gradients(leaves, axes) -> None:
    """One reducer per step: refuse replicated gradients in a trace where
    the tape has already reduced. This optimizer reads a replicated
    gradient as autodiff's cross-rank SUM (plain ``jax.grad`` over
    replicated parameters) and divides by the world; the tape's averages
    look the same and would be divided twice — silently, and only on more
    than one device. (A step that really has both, a reducing tape for one
    model and plain ``jax.grad`` for another, is refused too: hand this
    optimizer ``reduce=False`` gradients.)"""
    from .tape import reduced_in_this_trace

    axes_t = C._resolve_axes(axes)
    if not (axes_t and reduced_in_this_trace()):
        return
    if any(jnp.issubdtype(jnp.asarray(l).dtype, jnp.floating)
           and C._is_replicated(l, axes_t) for l in leaves):
        raise ValueError(
            "hvd.DistributedOptimizer was handed gradients that "
            "hvd.value_and_grad / hvd.allreduce_gradients already reduced "
            "in this step: it would divide them by the world size a "
            "second time. Keep one reducer: pass reduce=False to "
            "hvd.value_and_grad so the optimizer owns the reduction, or "
            "keep the reducing tape and use the plain optax "
            "transformation.")


def _with_update_guard(tx, axes=None):
    """The checks every DistributedOptimizer flavour makes on ``update``.

    Inside a trace (the compiled path) the one-reducer-per-step check
    runs (:func:`_reject_reduced_gradients`). When ``update`` runs eagerly
    (the host path / process-world mode) each call IS one optimizer step
    and is counted in the metrics registry (``optimizer.steps``). Device-
    trace step markers come from :func:`hvd.profile_window` on both paths.
    """
    inner_update = tx.update

    def update(grads, state, params=None, **extra):
        leaves = jax.tree.leaves(grads)
        if leaves and isinstance(leaves[0], jax.core.Tracer):
            _reject_reduced_gradients(leaves, axes)
        else:
            _metrics.counter("optimizer.steps").inc()
        return inner_update(grads, state, params, **extra)

    return optax.GradientTransformationExtraArgs(tx.init, update)


class ZeroState(NamedTuple):
    """Optimizer state of a ZeRO-sharded ``DistributedOptimizer``.

    ``inner`` is the wrapped transformation's state, initialized and run
    **only on this rank's flat bucket shards** — every moment leaf is a
    1-D ``[bucket_padded_size // world]`` array (plus replicated scalars
    like step counts). Outside the trace the global form of each moment
    leaf is the full flat bucket ``[bucket_padded_size]``; sharding it
    with ``P(HVD_AXES)`` hands each rank exactly its rank-major shard
    (:mod:`horovod_tpu.ops.fusion` shard layout), which is what the
    in-trace update produces and consumes. Use
    :func:`zero_state_pspecs` to build the matching in/out spec tree.

    ``residual`` / ``gather_residual`` are the error-feedback
    accumulators of the quantized wire (one entry per bucket, ``None``
    when the bucket or the knob is not quantized): ``residual`` feeds the
    gradient reduce-scatter's DCN leg (per rank ``padded // local_size``
    elements — the post-ICI shard it quantizes), ``gather_residual`` the
    update all-gather's DCN leg (per rank its owned ``padded // world``
    segment). Both are rank-local state and carry a leading per-rank
    axis riding ``P(HVD_AXES)`` — and both shrink with the shard, vs the
    full parameter-sized residual of :class:`QuantizedEFState`.
    """

    inner: Any
    residual: Any
    gather_residual: Any


def zero_state_pspecs(state):
    """PartitionSpec tree for a :class:`ZeroState` under ``jax.shard_map``:
    every non-scalar leaf is ZeRO-sharded along its leading axis
    (``P(HVD_AXES)`` — flat bucket moments, MultiSteps accumulators, and
    EF residuals all shard rank-major), scalars (step counters) replicate
    (``P()``). The contract this relies on: a wrapped transformation's
    non-scalar state mirrors its inputs, which here are the flat bucket
    shards — true of the standard optax optimizers (sgd, adam(w), lamb,
    rmsprop, ...); an inner transformation carrying non-scalar state that
    does NOT mirror the params needs a hand-built spec tree instead."""
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(
        lambda l: P(basics.HVD_AXES) if getattr(l, "ndim", 0) >= 1 else P(),
        state)


def _lead_read(tree):
    """Strip the per-rank leading axis of a leading-axis state tree:
    in-trace each rank's slice is its ``[1, ...]`` row; eagerly row
    ``rank()`` of the full ``[world, ...]`` stack (the
    :class:`QuantizedEFState` residual convention)."""
    r = 0 if C._hvd_axes_in_trace() else (
        basics.rank() if basics.is_initialized() else 0)
    return jax.tree.map(lambda a: a[r], tree)


def _lead_write(tree, new_local):
    """Write this rank's row back into a leading-axis state tree. In-trace
    the row is pvaried first so the ``P(HVD_AXES)`` out-spec always sees a
    device-varying value (a branchless ``where`` can hand back provably
    replicated zeros)."""
    axes = C._hvd_axes_in_trace()
    if axes:
        return jax.tree.map(
            lambda a: C.pvary_missing(a, axes)[None], new_local)
    r = basics.rank() if basics.is_initialized() else 0
    return jax.tree.map(lambda a, v: a.at[r].set(v), tree, new_local)


class OverlapMultiStepsState(NamedTuple):
    """State of the double-buffered microbatch accumulator
    (``overlap=True`` + ``backward_passes_per_step`` k > 1 on the
    replicated path — docs/overlap.md mechanism 1).

    ``inner`` is the wrapped transformation's state and ``acc`` the
    running sum of *reduced* gradients — both replicated (``P()``).
    ``pending`` holds the previous microbatch's raw per-rank local
    gradients and ``residual`` the quantized wire's error-feedback
    accumulator (``None`` unquantized); both are rank-local state with a
    leading per-rank axis riding ``P(hvd.HVD_AXES)`` in/out specs, the
    :class:`QuantizedEFState` residual convention
    (:func:`overlap_state_pspecs` builds the matching spec tree).

    Call *t* of a cycle reduces microbatch *t−1*'s buckets (``pending``)
    — a reduction with NO data dependence on the caller's microbatch-*t*
    backward traced in the same program region, which is exactly what
    lets the latency-hiding scheduler run the two concurrently. The
    final call folds the last two microbatches into one reduction (the
    wire is linear, so the accumulated sum is unchanged) and overlaps it
    with the optimizer update of already-reduced buckets. Each cycle
    issues k bucket reductions (vs ``optax.MultiSteps``' single deferred
    one): the classic DDP trade of wire volume for comm time hidden
    under backward.
    """

    mini_step: Any  # int32 scalar, 0..k-1
    inner: Any
    acc: Any
    pending: Any
    residual: Any


def overlap_state_pspecs(state: "OverlapMultiStepsState"):
    """PartitionSpec tree for an :class:`OverlapMultiStepsState` under
    ``hvd.shard_map``: ``pending``/``residual`` shard their leading
    per-rank axis (``P(HVD_AXES)``), everything else replicates."""
    from jax.sharding import PartitionSpec as P

    lead = lambda t: jax.tree.map(lambda _: P(basics.HVD_AXES), t)  # noqa: E731
    rep = lambda t: jax.tree.map(lambda _: P(), t)  # noqa: E731
    return OverlapMultiStepsState(
        mini_step=P(), inner=rep(state.inner), acc=rep(state.acc),
        pending=lead(state.pending),
        residual=None if state.residual is None else lead(state.residual))


class QuantizedEFState(NamedTuple):
    """Optimizer state of a quantized ``DistributedOptimizer``.

    ``inner`` is the wrapped transformation's state. ``residual`` is the
    error-feedback accumulator: a pytree matching the parameters whose
    leaves carry a leading **per-rank axis** — each rank's residual is
    rank-local state (every EF-SGD formulation keeps it per worker), so
    under ``jax.shard_map`` the leaves must ride ``P(hvd.HVD_AXES)``
    in/out specs (shape ``[world, *param_shape]`` outside the trace, this
    rank's ``[1, *param_shape]`` slice inside), not the replicated ``P()``
    of the inner state. A spec prefix of
    ``QuantizedEFState(P(), hvd.data_pspec())`` does exactly that — see
    ``tests/test_overlap.py::test_overlap_quantized_ef_bit_identical`` for
    the worked example.
    """

    inner: Any
    residual: Any


def _overlap_multi_steps(
    inner: optax.GradientTransformation,
    k: int,
    allreduce_fn,
    *,
    quantized: bool,
):
    """Double-buffered microbatch accumulation for the replicated path
    (``overlap=True`` + ``backward_passes_per_step`` k > 1) — see
    :class:`OverlapMultiStepsState` for the schedule and its contract.

    Branchless like :func:`_zero_multi_steps` (``where``-selected apply,
    never ``lax.cond``). Meaningful for per-rank local gradients
    (``hvd.value_and_grad(..., reduce=False)``); already-psummed
    replicated gradients are detected statically (VMA) and fall back to
    accumulate-locally + one final reduction — MultiSteps semantics, no
    extra wire."""

    def init_fn(params):
        world = basics.size() if basics.is_initialized() else 1
        rows = jax.tree.map(
            lambda p: jnp.zeros((world,) + jnp.shape(p),
                                jnp.asarray(p).dtype), params)
        return OverlapMultiStepsState(
            mini_step=jnp.zeros((), jnp.int32),
            inner=inner.init(params),
            acc=jax.tree.map(
                lambda p: jnp.zeros(jnp.shape(p), jnp.float32), params),
            pending=rows,
            residual=(jax.tree.map(jnp.zeros_like, rows)
                      if quantized else None),
        )

    def update_fn(grads, state, params=None, **extra):
        t = state.mini_step
        is_last = t == (k - 1)
        axes_t = C._hvd_axes_in_trace()
        gleaves = jax.tree.leaves(grads)
        presummed = bool(axes_t) and all(
            C._is_replicated(l, axes_t) for l in gleaves)
        res = None if state.residual is None else _lead_read(state.residual)
        if presummed:
            # Auto-psummed replicated gradients: already reduced, nothing
            # to hide — accumulate locally, reduce the mean once (the
            # reduction short-circuits per-leaf on invariant values).
            acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype),
                               state.acc, grads)
            mean = jax.tree.map(
                lambda a, g: (a / k).astype(jnp.asarray(g).dtype),
                acc, grads)
            if res is not None:
                red, new_res = allreduce_fn(mean, res)
            else:
                red, new_res = allreduce_fn(mean), None
            pend_next = jax.tree.map(jnp.zeros_like, grads)
        else:
            # Double buffer: reduce microbatch t-1 (pending) now — no
            # data dependence on this call's backward — folding the last
            # microbatch into the final call's payload (linear wire).
            pend = _lead_read(state.pending)
            payload = jax.tree.map(
                lambda p_, g_: jnp.where(is_last, p_ + g_, p_), pend, grads)
            if res is not None:
                rpay, new_res = allreduce_fn(payload, res)
            else:
                rpay, new_res = allreduce_fn(payload), None
            acc = jax.tree.map(lambda a, r: a + r.astype(a.dtype),
                               state.acc, rpay)
            mean = jax.tree.map(
                lambda a, g_: (a / k).astype(jnp.asarray(g_).dtype),
                acc, grads)
            red = mean
            pend_next = jax.tree.map(
                lambda g_: jnp.where(is_last, jnp.zeros_like(g_), g_),
                grads)
        with jax.named_scope("hvd.optimizer_update"):
            upd, inner_new = inner.update(red, state.inner, params, **extra)
        updates = jax.tree.map(
            lambda u: jnp.where(is_last, u, jnp.zeros_like(u)), upd)
        inner_next = jax.tree.map(
            lambda old, new: jnp.where(is_last, new, old),
            state.inner, inner_new)
        acc_next = jax.tree.map(
            lambda a: jnp.where(is_last, jnp.zeros_like(a), a), acc)
        return updates, OverlapMultiStepsState(
            mini_step=(t + 1) % k,
            inner=inner_next,
            acc=acc_next,
            pending=_lead_write(state.pending, pend_next),
            residual=(None if state.residual is None
                      else _lead_write(state.residual, new_res)),
        )

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def DistributedOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    compression=Compression.none,
    op: C.ReduceOp = C.ReduceOp.AVERAGE,
    backward_passes_per_step: int = 1,
    gradient_predivide_factor: float = 1.0,
    fusion_threshold_bytes: Optional[int] = None,
    hierarchical: Optional[bool] = None,
    quantized: Optional[bool] = None,
    zero: Optional[bool] = None,
    zero_stage: Optional[int] = None,
    overlap: Optional[bool] = None,
    num_comm_streams: Optional[int] = None,
    axes=None,
    tuned_params=None,
    plan=None,
    pp_stages: Optional[int] = None,
    pp_microbatches: Optional[int] = None,
    pp_schedule: Optional[str] = None,
    pp_interleave: Optional[int] = None,
    moe_experts: Optional[int] = None,
    moe_capacity_factor: Optional[float] = None,
    moe_topk: Optional[int] = None,
) -> optax.GradientTransformation:
    """Wrap an optax transformation with fused gradient allreduce.

    Args mirror the reference's DistributedOptimizer signature
    (tensorflow/__init__.py:435-508): ``compression`` (wire dtype),
    ``op`` (Average | Sum | Adasum), ``backward_passes_per_step``
    (local accumulation), ``gradient_predivide_factor`` (split the averaging
    divisor across pre/post scaling: prescale = 1/f applied before the sum,
    postscale = f/N after — tensorflow/__init__.py:462-476).

    ``quantized`` (default: the ``HOROVOD_QUANTIZED_ALLREDUCE`` knob) moves
    each fused gradient bucket over the blockwise-int8 DCN wire with
    per-bucket error feedback: the state becomes a
    :class:`QuantizedEFState` wrapping the inner state plus a per-rank
    residual pytree, and each step's quantization error is carried into
    the next step's gradient, keeping convergence at full-precision
    quality. Only meaningful when the gradients reaching ``update`` are
    per-rank locals (e.g. via ``hvd.value_and_grad(..., reduce=False)``);
    auto-psummed replicated gradients never touch the wire, so there is
    nothing to quantize.

    ``zero_stage`` (default: the ``HOROVOD_ZERO_STAGE`` knob; ``zero=True``
    is an alias for stage 2 and ``HOROVOD_ZERO_SHARDING=1`` still maps
    there) selects the ZeRO reduce-scatter decomposition: gradients
    reduce-scatter, the wrapped transformation runs only on this rank's
    ``1/world`` flat bucket shards (state becomes a :class:`ZeroState`;
    shard it with :func:`zero_state_pspecs`), and — stages 1/2 — the
    updates all-gather back. Stage 1 keeps the classic full
    local-gradient accumulator when ``backward_passes_per_step`` k > 1
    (:class:`ZeroFullMultiStepsState`); stage 2 accumulates AFTER the
    reduce-scatter on the scattered shard, shrinking gradient state
    ``world``×; stage 3 additionally expects the PARAMETERS as flat
    bucket shards (``params=`` is the :func:`zero3_shard_params` tuple,
    the forward runs on :func:`zero3_gather_params` output) and returns
    shard updates with no trailing all-gather. All stages compose with
    ``gradient_predivide_factor`` and ``quantized`` (the DCN legs ride
    the blockwise-int8 wire with shard-local error feedback). Like
    ``quantized``, the wire savings need per-rank local gradients
    (``hvd.value_and_grad(..., zero=True)`` or ``reduce=False``);
    already-psummed replicated gradients still shard the update math and
    the moments. See docs/zero.md.

    ``overlap`` (default: the ``HOROVOD_OVERLAP`` knob) streams the fused
    gradient buckets into collectives while backward compute still runs
    (docs/overlap.md): buckets issue in reverse-layer order through the
    per-bucket stream entry points in flights of ``num_comm_streams``
    (pow2 1–4), and with ``backward_passes_per_step`` k > 1 the
    accumulation loop double-buffers so microbatch t's backward and
    microbatch t−1's bucket reduction are dependence-free in the same
    program region (state becomes an :class:`OverlapMultiStepsState`; on
    the ZeRO path the shard accumulator double-buffers the packed
    buckets instead). With k == 1 overlap changes only collective issue
    order, so it is bit-identical to off; ``hvd.init`` arms the XLA
    async-collective/latency-hiding flags on TPU (graceful no-op
    elsewhere).

    ``tuned_params`` (an ``autotune.TunedParams``, e.g. the winner of
    :func:`horovod_tpu.autotune_session`) overrides the fusion threshold,
    hierarchical flag, int8 scale-block, ZeRO flag, and the
    ``overlap``/``num_comm_streams`` pair for this optimizer's gradient
    reduction wherever the explicit kwargs above were left unset —
    rebuilding the optimizer with a new override is exactly what one
    autotune trial does (the step retraces with the new bucket plan).

    ``plan`` (a :class:`horovod_tpu.plan.StepPlan`, e.g. from
    :func:`horovod_tpu.describe_plan`) threads the resolved wire plan
    instead of the boolean knobs, which remain as aliases: wherever a
    knob above is unset it derives from the plan's knob record, and the
    replicated path's bucket collectives lower through exactly
    ``plan.gradient`` (docs/wire-plan.md). Explicit kwargs still win;
    ``tuned_params`` applies after the plan.

    ``pp_stages`` / ``pp_microbatches`` / ``pp_schedule`` /
    ``pp_interleave`` (defaults: the live mesh's ``hvd_pp`` axis and the
    ``HOROVOD_PP_*`` knobs; a ``plan``'s pp record and ``tuned_params``'
    pp fields fill unset values first) declare the pipeline composition
    this optimizer's step runs under (docs/pipeline.md). The gradient
    wire itself is already pipeline-safe — ``axes=None`` resolves to the
    DATA axes, so per-stage reductions never cross the pp axis — these
    knobs validate the composition up front (stage count vs mesh,
    schedule family, microbatch divisibility) and fail loudly instead of
    letting a mismatched schedule train garbage.

    ``moe_experts`` / ``moe_capacity_factor`` / ``moe_topk`` (defaults:
    the live mesh's ``hvd_ep`` axis and the ``HOROVOD_MOE_*`` knobs; a
    ``plan``'s moe record and ``tuned_params``' moe fields fill unset
    values first) declare the MoE composition the same way
    (docs/moe.md): the gradient wire is already expert-parallel-safe —
    ``axes=None`` resolves to the DATA axes, so an expert's gradients
    reduce only within its own data group and never across ``hvd_ep``
    — these knobs validate up front (expert count vs the ep axis,
    capacity/topk bounds) and fail loudly on a misconfiguration.
    """
    if gradient_predivide_factor != 1.0 and op != C.ReduceOp.AVERAGE:
        raise ValueError(
            "gradient_predivide_factor is only supported with op=Average "
            "(reference: tensorflow/__init__.py:452-455)")
    if backward_passes_per_step < 1:
        raise ValueError("backward_passes_per_step must be >= 1")
    _validate_pp_knobs(pp_stages, pp_microbatches, pp_schedule,
                       pp_interleave, plan=plan,
                       tuned_params=tuned_params)
    _validate_moe_knobs(moe_experts, moe_capacity_factor, moe_topk,
                        plan=plan, tuned_params=tuned_params)
    quant_block = None
    grad_plan = None
    if plan is not None:
        step_plan = plan
        if not hasattr(step_plan, "gradient"):
            raise ValueError(
                "DistributedOptimizer(plan=...) expects a StepPlan "
                "(hvd.describe_plan(...)); pass a bare WirePlan to the "
                "collective entry points or allreduce_pytree instead")
        if quantized is None:
            quantized = step_plan.quantized
        if zero_stage is None and zero is None:
            zero_stage = step_plan.zero_stage
        if overlap is None:
            overlap = step_plan.overlap
        if num_comm_streams is None:
            num_comm_streams = step_plan.num_comm_streams
        if hierarchical is None:
            hierarchical = step_plan.hierarchical
        if fusion_threshold_bytes is None:
            fusion_threshold_bytes = step_plan.fusion_threshold_bytes
        if step_plan.quantized:
            quant_block = step_plan.quant_block
        if step_plan.zero_stage == 0:
            grad_plan = step_plan.gradient
    if zero_stage is None and zero is not None:
        zero_stage = 2 if zero else 0  # zero=True is the stage-2 alias
    if tuned_params is not None:
        if fusion_threshold_bytes is None:
            fusion_threshold_bytes = tuned_params.fusion_threshold_bytes
        if hierarchical is None:
            hierarchical = tuned_params.hierarchical_allreduce
        if zero_stage is None:
            zero_stage = tuned_params.zero_stage
        if overlap is None:
            overlap = tuned_params.overlap
        if num_comm_streams is None:
            num_comm_streams = tuned_params.num_comm_streams
    if quantized is None:
        quantized = (basics.config().quantized_allreduce
                     if basics.is_initialized()
                     else _env_bool("HOROVOD_QUANTIZED_ALLREDUCE", False))
    if zero_stage is None:
        zero_stage = _resolve_zero_stage_config()
    if zero_stage not in (0, 1, 2, 3):
        raise ValueError(f"zero_stage must be 0, 1, 2, or 3, got "
                         f"{zero_stage!r}")
    zero = zero_stage > 0
    if overlap is None:
        overlap = (basics.config().overlap if basics.is_initialized()
                   else _env_bool("HOROVOD_OVERLAP", False))
    if num_comm_streams is None:
        num_comm_streams = (basics.config().num_comm_streams
                            if basics.is_initialized() else 1)
    num_comm_streams = max(1, int(num_comm_streams))
    if zero:
        if op not in (C.ReduceOp.AVERAGE, C.ReduceOp.SUM):
            raise ValueError(
                f"zero=True supports op=Average/Sum (a reduce-scatter of "
                f"{op} has no decomposition), got {op}")
        return _with_update_guard(_build_zero_transform(
            optimizer,
            compression=compression,
            op=op,
            backward_passes_per_step=backward_passes_per_step,
            gradient_predivide_factor=gradient_predivide_factor,
            fusion_threshold_bytes=fusion_threshold_bytes,
            quantized=quantized,
            quant_block=quant_block,
            overlap=bool(overlap),
            num_comm_streams=num_comm_streams,
            axes=axes,
            stage=zero_stage,
        ), axes)

    if gradient_predivide_factor != 1.0:
        # Average == Sum with the divisor split across pre/post scaling.
        prescale = 1.0 / gradient_predivide_factor
        reduce_op = C.ReduceOp.SUM
        # postscale completes the average: f / N, with N resolved at trace
        # time inside _allreduce (world size is static under the mesh).
        postscale_mode = "predivide"
    else:
        prescale = 1.0
        reduce_op = op
        postscale_mode = None

    def _allreduce(grads, error_feedback=None):
        postscale = 1.0
        if postscale_mode == "predivide":
            axes_t = C._resolve_axes(axes)
            n = C._world_size(axes_t) if axes_t else 1
            postscale = gradient_predivide_factor / n
        with jax.named_scope("hvd.allreduce_grads"):
            return fusion.allreduce_pytree(
                grads,
                op=reduce_op,
                compression=compression,
                threshold_bytes=fusion_threshold_bytes,
                axes=axes,
                hierarchical=hierarchical,
                prescale_factor=prescale,
                postscale_factor=postscale,
                presummed=True,  # invariant grads are autodiff-psummed sums
                quantized=quantized,
                error_feedback=error_feedback,
                block=quant_block,
                overlap=overlap,
                num_comm_streams=num_comm_streams,
                plan=grad_plan,
            )

    if overlap and backward_passes_per_step > 1:
        # Mechanism 1 (docs/overlap.md): the double-buffered microbatch
        # accumulator owns the reduction (and, when quantized, the EF
        # residual) so microbatch t's backward and microbatch t-1's
        # bucket reduction share a program region dependence-free.
        return _with_update_guard(
            _overlap_multi_steps(optimizer, backward_passes_per_step,
                                 _allreduce, quantized=quantized), axes)

    _res_read, _res_write = _lead_read, _lead_write

    def init_fn(params):
        inner = optimizer.init(params)
        if not quantized:
            return inner
        world = basics.size() if basics.is_initialized() else 1
        residual = jax.tree.map(
            lambda p: jnp.zeros((world,) + jnp.shape(p), jnp.asarray(p).dtype),
            params)
        return QuantizedEFState(inner=inner, residual=residual)

    def update_fn(grads, state, params=None, **extra):
        if not quantized:
            reduced = _allreduce(grads)
            with jax.named_scope("hvd.optimizer_update"):
                return optimizer.update(reduced, state, params, **extra)
        reduced, new_res = _allreduce(grads, _res_read(state.residual))
        with jax.named_scope("hvd.optimizer_update"):
            updates, new_inner = optimizer.update(
                reduced, state.inner, params, **extra)
        return updates, QuantizedEFState(
            inner=new_inner,
            residual=_res_write(state.residual, new_res))

    tx = optax.GradientTransformationExtraArgs(init_fn, update_fn)
    if backward_passes_per_step > 1:
        # Accumulate locally, allreduce + apply every k-th microbatch
        # (reference: torch/optimizer.py:133-149).
        tx = optax.MultiSteps(tx, every_k_schedule=backward_passes_per_step)
    return _with_update_guard(tx, axes)


def _validate_pp_knobs(pp_stages, pp_microbatches, pp_schedule,
                       pp_interleave, *, plan=None,
                       tuned_params=None) -> dict:
    """Resolve + validate the pipeline knobs of a training step
    (docs/pipeline.md). The optimizer's gradient collectives are already
    pipeline-safe by construction — ``axes=None`` resolves to the DATA
    axes, never ``hvd_pp`` — so these knobs exist to fail loudly on a
    misconfigured composition (a stage count that disagrees with the
    live mesh, an unknown schedule, an interleave the schedule cannot
    honor) and to record the resolved values for describe/debug.

    Returns the resolved ``{pp_stages, pp_microbatches, pp_schedule,
    pp_interleave}`` dict. Shared by :class:`DistributedOptimizer` and
    :func:`horovod_tpu.value_and_grad`."""
    from .pipeline import PP_SCHEDULES

    if plan is not None and hasattr(plan, "pp_stages"):
        if pp_stages is None and getattr(plan, "pp_stages", 0):
            pp_stages = plan.pp_stages
        if pp_microbatches is None and getattr(plan, "pp_microbatches", 0):
            pp_microbatches = plan.pp_microbatches
        if pp_schedule is None and getattr(plan, "send", None) is not None:
            pp_schedule = plan.pp_schedule
        if pp_interleave is None and getattr(plan, "send", None) is not None:
            pp_interleave = plan.pp_interleave
    if tuned_params is not None:
        if pp_microbatches is None:
            pp_microbatches = getattr(tuned_params, "pp_microbatches",
                                      0) or None
        if pp_interleave is None:
            pp_interleave = getattr(tuned_params, "pp_interleave",
                                    0) or None
    cfg = basics.config() if basics.is_initialized() else None
    if pp_stages is None:
        pp_stages = (basics.pp_size() if basics.is_initialized()
                     else (cfg.pp_stages if cfg else 0))
    if pp_schedule is None:
        pp_schedule = cfg.pp_schedule if cfg else "interleaved_1f1b"
    if pp_interleave is None:
        pp_interleave = (cfg.pp_interleave if cfg else 1) or 1
    if pp_microbatches is None:
        pp_microbatches = (cfg.pp_microbatches if cfg else 0)
    pp_stages = int(pp_stages or 0)
    pp_interleave = max(1, int(pp_interleave))
    pp_microbatches = int(pp_microbatches or 0)
    if pp_stages > 1:
        if pp_schedule not in PP_SCHEDULES:
            raise ValueError(
                f"unknown pp_schedule {pp_schedule!r}: one of "
                f"{PP_SCHEDULES} (docs/pipeline.md)")
        if basics.is_initialized() and basics.pp_size() > 1 \
                and pp_stages != basics.pp_size():
            raise ValueError(
                f"pp_stages={pp_stages} disagrees with the live mesh's "
                f"hvd_pp axis of {basics.pp_size()} stages — the stage "
                f"count is mesh geometry (hvd.init(pp_stages=...))")
        if pp_interleave > 1 and pp_schedule not in ("interleaved_1f1b",
                                                     "zb1"):
            raise ValueError(
                f"pp_interleave={pp_interleave} needs "
                f"pp_schedule='interleaved_1f1b' or 'zb1'; "
                f"{pp_schedule!r} does not interleave virtual stages")
        if (pp_schedule in ("interleaved_1f1b", "zb1")
                and pp_interleave > 1
                and pp_microbatches and pp_microbatches % pp_stages):
            raise ValueError(
                f"pp_microbatches={pp_microbatches} must divide by "
                f"pp_stages={pp_stages} for the interleaved schedule "
                f"(docs/pipeline.md)")
    return {"pp_stages": pp_stages, "pp_microbatches": pp_microbatches,
            "pp_schedule": pp_schedule, "pp_interleave": pp_interleave}


def _validate_moe_knobs(moe_experts, moe_capacity_factor, moe_topk, *,
                        plan=None, tuned_params=None) -> dict:
    """Resolve + validate the MoE knobs of a training step
    (docs/moe.md). Like the pp knobs, the optimizer's gradient
    collectives are already expert-parallel-safe by construction —
    ``axes=None`` resolves to the DATA axes, never ``hvd_ep`` — so these
    exist to fail loudly on a misconfigured composition: an expert
    count that does not divide by the live hvd_ep axis, a non-positive
    capacity factor, a topk out of range.

    Returns the resolved ``{moe_experts, moe_capacity_factor,
    moe_topk}`` dict. Shared by :class:`DistributedOptimizer` and
    :func:`horovod_tpu.value_and_grad`."""
    if plan is not None and hasattr(plan, "moe_experts"):
        if moe_experts is None and getattr(plan, "moe_experts", 0):
            moe_experts = plan.moe_experts
        if moe_capacity_factor is None and getattr(
                plan, "moe", None) is not None:
            moe_capacity_factor = plan.moe_capacity_factor
        if moe_topk is None and getattr(plan, "moe", None) is not None:
            moe_topk = plan.moe_topk
    if tuned_params is not None and moe_capacity_factor is None:
        moe_capacity_factor = getattr(tuned_params,
                                      "moe_capacity_factor", 0.0) or None
    cfg = basics.config() if basics.is_initialized() else None
    if moe_experts is None:
        if basics.is_initialized() and basics.ep_size() > 1:
            moe_experts = basics.ep_size()
        else:
            moe_experts = cfg.moe_experts if cfg else 0
    if moe_capacity_factor is None:
        moe_capacity_factor = (cfg.moe_capacity_factor if cfg else 1.25)
    if moe_topk is None:
        moe_topk = cfg.moe_topk if cfg else 2
    moe_experts = int(moe_experts or 0)
    moe_topk = int(moe_topk or 0)
    moe_capacity_factor = float(moe_capacity_factor or 0.0)
    if moe_experts > 1:
        if basics.is_initialized() and basics.ep_size() > 1 \
                and moe_experts % basics.ep_size():
            raise ValueError(
                f"moe_experts={moe_experts} does not divide by the live "
                f"mesh's hvd_ep axis of {basics.ep_size()} expert "
                f"groups — expert placement is mesh geometry "
                f"(hvd.init(ep_size=...), docs/moe.md)")
        if moe_capacity_factor <= 0:
            raise ValueError(
                f"moe_capacity_factor must be > 0, got "
                f"{moe_capacity_factor} — the dispatch buffer needs "
                f"headroom (docs/moe.md)")
        if not (1 <= moe_topk <= moe_experts):
            raise ValueError(
                f"moe_topk={moe_topk} out of range 1..{moe_experts} "
                f"(experts per token cannot exceed the expert count)")
    return {"moe_experts": moe_experts,
            "moe_capacity_factor": moe_capacity_factor,
            "moe_topk": moe_topk}


# ---------------------------------------------------------------------------
# ZeRO: reduce-scatter data parallelism with per-rank optax updates.
# ---------------------------------------------------------------------------


def _resolve_zero_stage_config() -> int:
    """The configured ZeRO stage: ``HOROVOD_ZERO_STAGE`` (0-3) wins;
    ``HOROVOD_ZERO_SHARDING=1`` (the PR-4 boolean) maps to stage 2."""
    if basics.is_initialized():
        cfg = basics.config()
        stage = getattr(cfg, "zero_stage", 0)
        if stage:
            return stage
        return 2 if cfg.zero_sharding else 0
    stage = _env_int("HOROVOD_ZERO_STAGE", 0)
    if stage:
        return stage
    return 2 if _env_bool("HOROVOD_ZERO_SHARDING", False) else 0


def _zero_worlds(axes) -> Tuple[int, int, bool]:
    """(plan_world, own_world, in_trace).

    ``plan_world`` fixes the bucket padding (``shard_multiple``) and must
    agree between init and update — it is always the full mesh world.
    ``own_world`` is how many ranks actually split the state at this call
    site: the mesh world in-trace, the process world under the eager
    process model (each worker owns its shard — the true ZeRO memory
    win), and 1 for host-side calls under single-controller SPMD (init
    there produces the GLOBAL state — full flat buckets — which
    ``device_put`` with :func:`zero_state_pspecs` then shards)."""
    axes_t = C._resolve_axes(axes)
    if axes_t:
        w = C._world_size(axes_t)
        return w, w, True
    if not basics.is_initialized():
        return 1, 1, False
    # On a pipeline / expert-parallel / 4-D composed mesh the ZeRO
    # world is the DATA world: each (stage, expert-group) cell's shards
    # split over (cross, local) only — exactly what the in-trace path
    # resolves, since hvd_pp/hvd_ep are never world axes.
    plan_w = basics.size() // (basics.pp_size() * basics.ep_size())
    own_w = plan_w if basics._process_world() else 1
    return plan_w, own_w, False


def _zero_local_size(in_trace: bool) -> int:
    if in_trace:
        bound = basics._bound_axes()
        return (C._axis_size(basics.LOCAL_AXIS)
                if basics.LOCAL_AXIS in bound else 1)
    return basics.local_size() if basics.is_initialized() else 1


def _zero_residual_shapes(plan, world: int, local_size: int):
    """Per-bucket (rs_shape, ag_shape) of the EF residuals, or None for
    buckets that never ride the quantized wire (non-float)."""
    out = []
    for b in plan:
        if not jnp.issubdtype(b.dtype, jnp.floating):
            out.append(None)
            continue
        seg = b.padded_size // world
        sn = b.padded_size // local_size
        out.append(((sn,), (seg,)))
    return out


class ZeroMultiStepsState(NamedTuple):
    """Shard-level gradient-accumulation state (``zero=True`` +
    ``backward_passes_per_step > 1``): ``acc_grads`` holds the running
    mean of the *scattered* shards — ``1/world`` the footprint of the
    full-gradient accumulator ``optax.MultiSteps`` keeps on the
    replicated path."""

    mini_step: Any  # int32 scalar, 0..k-1
    inner: Any
    acc_grads: Any


class ZeroFullMultiStepsState(NamedTuple):
    """Full-gradient accumulation state (``zero_stage=1`` +
    ``backward_passes_per_step`` k > 1) — the classic ZeRO-1 layout.

    ``acc`` holds the running sum of this rank's RAW local gradients in
    model-tree layout (one entry per flattened gradient leaf), i.e. the
    full-size accumulator stage 2 exists to shrink: per-rank state with
    a leading per-rank axis riding ``P(HVD_AXES)`` (the residual
    convention — ``[world, *shape]`` outside the trace, ``[1, *shape]``
    inside). The mean of the k accumulated microbatches feeds the
    reduce-scatter on the k-th call; inner state and emitted updates are
    ``where``-selected (branchless), so the wire runs every microbatch but
    non-final results are discarded. Reshard only at cycle boundaries
    (``mini_step == 0``, ``acc`` zeros); :func:`zero_reshard_state`
    rebuilds the accumulator as zeros at the new world."""

    mini_step: Any  # int32 scalar, 0..k-1
    inner: Any
    acc: Any        # per grad leaf, [lead, *shape], leading per-rank axis


class ZeroOverlapMultiStepsState(NamedTuple):
    """Shard-level double-buffered accumulation state (``zero=True`` +
    ``overlap=True`` + ``backward_passes_per_step`` k > 1).

    Like :class:`ZeroMultiStepsState` the accumulator (``acc_shards``)
    holds scattered ``1/world`` shards, but the reduce-scatter is
    double-buffered: ``pending`` carries the previous microbatch's packed
    raw bucket buffers (leading per-rank axis, the residual convention),
    so call *t* reduce-scatters microbatch *t−1*'s buckets dependence-free
    alongside microbatch *t*'s backward, and the final call folds the
    last two microbatches into one reduction (linear wire — the
    accumulated shard sum is unchanged). Same k collectives per cycle as
    the non-overlapped ZeRO accumulator, shifted one call late."""

    mini_step: Any  # int32 scalar, 0..k-1
    inner: Any
    acc_shards: Any  # per bucket, fp32, flat-bucket (shard) convention
    pending: Any     # per bucket, [lead, padded], leading per-rank axis


def _zero_multi_steps(inner: optax.GradientTransformation, k: int):
    """Branchless ``optax.MultiSteps`` equivalent for the shard level.

    ``optax.MultiSteps`` selects between its accumulate and apply arms
    with ``lax.cond``, whose branches produce different replication types
    under ``shard_map`` (varying shard updates vs replicated zeros) and
    fail the rep/vma checker. At shard level the inner update is
    ``1/world`` the size of the replicated one, so running it every
    microbatch and selecting the result with ``where`` is both cheaper
    than a host of conds and type-stable: emitted updates are zeros
    except on every k-th call, where they are the inner update on the
    running mean of the k accumulated shards (the MultiSteps contract).
    """

    def init_fn(params):
        return ZeroMultiStepsState(
            mini_step=jnp.zeros((), jnp.int32),
            inner=inner.init(params),
            acc_grads=jax.tree.map(
                lambda p: jnp.zeros_like(p, dtype=jnp.float32), params))

    def update_fn(grads, state, params=None, **extra):
        t = state.mini_step
        # Running mean: acc += (g - acc) / (t + 1).
        acc = jax.tree.map(
            lambda a, g: a + (g.astype(a.dtype) - a) / (t + 1).astype(
                a.dtype),
            state.acc_grads, grads)
        is_last = t == (k - 1)
        mean = jax.tree.map(lambda a, g: a.astype(jnp.asarray(g).dtype),
                            acc, grads)
        with jax.named_scope("hvd.optimizer_update"):
            upd, inner_new = inner.update(mean, state.inner, params, **extra)
        updates = jax.tree.map(
            lambda u: jnp.where(is_last, u, jnp.zeros_like(u)), upd)
        inner_next = jax.tree.map(
            lambda old, new: jnp.where(is_last, new, old),
            state.inner, inner_new)
        acc_next = jax.tree.map(
            lambda a: jnp.where(is_last, jnp.zeros_like(a), a), acc)
        return updates, ZeroMultiStepsState(
            mini_step=(t + 1) % k, inner=inner_next, acc_grads=acc_next)

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


def _build_zero_transform(
    optimizer: optax.GradientTransformation,
    *,
    compression,
    op: C.ReduceOp,
    backward_passes_per_step: int,
    gradient_predivide_factor: float,
    fusion_threshold_bytes: Optional[int],
    quantized: bool,
    quant_block: Optional[int],
    axes,
    overlap: bool = False,
    num_comm_streams: int = 1,
    stage: int = 2,
) -> optax.GradientTransformation:
    """The ZeRO optax wrapper: reduce-scatter → shard update → (stages
    1/2) all-gather, with the wrapped transformation living entirely on
    this rank's flat bucket shards.

    ``stage`` picks the accumulation/parameter layout (docs/zero.md):
    stage 1 accumulates FULL local gradients before the wire
    (:class:`ZeroFullMultiStepsState`); stage 2 accumulates the scattered
    shard after it (:class:`ZeroMultiStepsState`, ``1/world`` the
    state); stage 3 is stage 2 whose ``params`` argument is the
    :func:`zero3_shard_params` tuple — the inner update runs shard vs
    shard and the returned updates stay in shard space (the caller
    applies them to its shard tree; the just-in-time forward gather is
    :func:`zero3_gather_params`). With k == 1 stages 1 and 2 trace the
    identical program.

    ``overlap`` issues the per-bucket reduce-scatter/all-gather through
    the reverse-layer stream schedule in flights of ``num_comm_streams``
    (docs/overlap.md); with ``backward_passes_per_step`` k > 1 it also
    double-buffers the accumulation loop (:class:`ZeroOverlapMultiSteps
    State`) so each call's reduce-scatter covers the PREVIOUS microbatch
    and runs dependence-free next to the current backward (this shard-
    level double buffer serves every stage — overlap trades stage 1's
    full-accumulator layout for the hidden wire)."""
    # Stage 2/3: backward_passes_per_step accumulates INSIDE the shard,
    # so the accumulator is a [padded // world] leaf, not a full gradient
    # replica. Stage 1 keeps the classic full local-gradient accumulator
    # (per-rank leading-axis state); the wire still runs every microbatch
    # — branchless where-selection cannot elide a collective — so stage 1's
    # distinguishing property is the accumulator LAYOUT, which is what
    # tests/test_zero.py::test_stage1_full_accumulator_layout pins.
    k = backward_passes_per_step
    db = overlap and k > 1  # double-buffered accumulation
    s1 = stage == 1 and k > 1 and not db  # full-grad accumulation
    stx = (_zero_multi_steps(optimizer, k)
           if k > 1 and not db and not s1 else optimizer)
    num_comm_streams = max(1, int(num_comm_streams))

    if gradient_predivide_factor != 1.0:
        prescale = 1.0 / gradient_predivide_factor
        reduce_op = C.ReduceOp.SUM
        postscale_mode = "predivide"
    else:
        prescale = 1.0
        reduce_op = op
        postscale_mode = None

    def _threshold():
        if fusion_threshold_bytes is not None:
            return fusion_threshold_bytes
        return None  # plan_buckets resolves the config default

    def _plan(leaves, plan_world):
        return fusion.plan_buckets(leaves, _threshold(),
                                   shard_multiple=plan_world)

    def _rank(in_trace: bool):
        if in_trace:
            return lax.axis_index(C._resolve_axes(axes))  # traced index
        return basics.rank() if basics.is_initialized() else 0

    def _shard_params(plan, leaves, own_world, in_trace):
        if own_world == 1:
            return tuple(fusion.pack(b, leaves) for b in plan)
        r = _rank(in_trace)
        return tuple(
            fusion.shard_slice(fusion.pack(b, leaves), own_world, r)
            for b in plan)

    def _res_read(res_entry, in_trace):
        if res_entry is None:
            return None
        r = 0 if in_trace else _rank(False)
        return res_entry[r]

    def _res_write(old_entry, new_local, in_trace):
        if old_entry is None:
            return None
        if in_trace:
            return new_local[None]
        r = _rank(False)
        return old_entry.at[r].set(new_local)

    def init_fn(params):
        # Every stage's init takes the MODEL-tree params (host-side the
        # full pytree; stage 3 callers shard the params separately with
        # zero3_shard_params — the optimizer state layout is identical).
        leaves, _ = jax.tree.flatten(params)
        plan_world, own_world, in_trace = _zero_worlds(axes)
        plan = _plan(leaves, plan_world)
        shards = _shard_params(plan, leaves, own_world, in_trace)
        inner = stx.init(shards)
        lead = 1 if in_trace else max(1, plan_world)
        if db:
            inner = ZeroOverlapMultiStepsState(
                mini_step=jnp.zeros((), jnp.int32),
                inner=inner,
                acc_shards=tuple(
                    jnp.zeros(jnp.shape(s), jnp.float32) for s in shards),
                pending=tuple(
                    jnp.zeros((lead, b.padded_size), b.dtype)
                    for b in plan))
        elif s1:
            inner = ZeroFullMultiStepsState(
                mini_step=jnp.zeros((), jnp.int32),
                inner=inner,
                acc=tuple(
                    jnp.zeros((lead,) + tuple(jnp.shape(l)), jnp.float32)
                    for l in leaves))
        if not quantized:
            return ZeroState(inner=inner, residual=None,
                             gather_residual=None)
        nl = _zero_local_size(in_trace)
        # In-trace state carries the [1, ...] per-rank leading axis slice
        # (P(HVD_AXES) convention); host-side init builds the full
        # [world, ...] stack.
        rs, ag = [], []
        for shp in _zero_residual_shapes(plan, plan_world, nl):
            if shp is None:
                rs.append(None)
                ag.append(None)
            else:
                rs.append(jnp.zeros((lead,) + shp[0], jnp.float32))
                ag.append(jnp.zeros((lead,) + shp[1], jnp.float32))
        # Stage 3 has no trailing all-gather, hence no gather residual.
        return ZeroState(inner=inner, residual=tuple(rs),
                         gather_residual=(None if stage == 3
                                          else tuple(ag)))

    def update_fn(grads, state, params=None, **extra):
        gleaves, treedef = jax.tree.flatten(grads)
        plan_world, own_world, in_trace = _zero_worlds(axes)
        plan = _plan(gleaves, plan_world)
        axes_t = C._resolve_axes(axes)

        postscale = 1.0
        if postscale_mode == "predivide":
            postscale = gradient_predivide_factor / max(1, own_world)

        if in_trace and axes_t:
            # Already-psummed replicated gradients (the auto-psum of
            # replicated params under shard_map autodiff) become exact
            # per-rank locals: rank 0 contributes the full sum, everyone
            # else zeros — bitwise-exact under any reduction order, and
            # it keeps mixed replicated/varying buckets correct through
            # one reduce-scatter.
            r0 = lax.axis_index(axes_t) == 0
            gleaves = [
                jnp.where(r0, leaf, jnp.zeros_like(leaf))
                if C._is_replicated(leaf, axes_t) else leaf
                for leaf in gleaves
            ]

        # Host-side update under single-controller SPMD (own_world == 1):
        # the state is global, the "shard" is the whole bucket, and — as
        # on the replicated path's eager allreduce over a world of one —
        # no collective runs.
        eager_local = (not in_trace) and own_world == 1

        use_quant = quantized
        order = (fusion.stream_order(plan) if overlap
                 else tuple(range(len(plan))))
        flight = num_comm_streams if overlap else 1

        ms = state.inner if (db or s1) else None
        if db or s1:
            t = ms.mini_step
            is_last = t == (k - 1)
        new_acc_full: Optional[Tuple[Any, ...]] = None
        if s1:
            # Stage 1: accumulate the RAW local gradients (full model
            # layout, per-rank leading-axis state) BEFORE the wire; the
            # running mean feeds every call's reduce-scatter and only
            # the k-th call's result survives the where-selection.
            acc_loc = tuple(_res_read(a, in_trace) for a in ms.acc)
            acc_new = tuple(a + g.astype(a.dtype)
                            for a, g in zip(acc_loc, gleaves))
            gleaves = [(a / float(k)).astype(jnp.asarray(g).dtype)
                       for a, g in zip(acc_new, gleaves)]
            new_acc_full = tuple(
                _res_write(old, jnp.where(is_last, jnp.zeros_like(n), n),
                           in_trace)
                for old, n in zip(ms.acc, acc_new))
        new_pending: List[Any] = [None] * len(plan)

        gshards: List[Any] = [None] * len(plan)
        new_rs: List[Any] = [None] * len(plan)
        with jax.named_scope("hvd.allreduce_grads"):
            for s in range(0, len(order), flight):
                issued = []
                for i in order[s:s + flight]:
                    b = plan[i]
                    buf = fusion.pack(b, gleaves)
                    if db:
                        # Double buffer: this call's wire carries the PREVIOUS
                        # microbatch's packed buckets (no dependence on this
                        # call's backward); the final call folds the last
                        # microbatch in (the wire is linear).
                        pend = _res_read(ms.pending[i], in_trace)
                        new_pending[i] = _res_write(
                            ms.pending[i],
                            jnp.where(is_last, jnp.zeros_like(buf), buf),
                            in_trace)
                        buf = jnp.where(is_last, pend + buf, pend)
                    is_float = jnp.issubdtype(b.dtype, jnp.floating)
                    wire, ctx = compression.compress(buf)
                    if eager_local:
                        shard = C._scale(C._scale(wire, prescale), postscale)
                        new_rs[i] = (None if state.residual is None
                                     else state.residual[i])
                        gshards[i] = compression.decompress(shard, ctx)
                        continue
                    res = (None
                           if not (use_quant and is_float and state.residual)
                           else _res_read(state.residual[i], in_trace))
                    rs_kw = dict(op=reduce_op, prescale_factor=prescale,
                                 postscale_factor=postscale,
                                 block=quant_block, _presummed=True)
                    if res is not None:
                        if overlap:
                            shard, nres = C.reduce_scatter_stream(
                                wire, res, bucket_id=i, quantized=True,
                                **rs_kw)
                        else:
                            shard, nres = C.reduce_scatter(
                                wire, res, quantized=True, **rs_kw)
                        new_rs[i] = _res_write(state.residual[i], nres,
                                               in_trace)
                    else:
                        if overlap:
                            shard = C.reduce_scatter_stream(
                                wire, bucket_id=i,
                                quantized=use_quant and is_float, **rs_kw)
                        else:
                            shard = C.reduce_scatter(
                                wire, quantized=use_quant and is_float,
                                **rs_kw)
                        new_rs[i] = (None if state.residual is None
                                     else state.residual[i])
                    issued.append((i, shard, ctx))
                # Decompress after the whole flight is issued: no consumer
                # between in-flight scatters (flight == 1 == the serial
                # schedule exactly).
                for i, shard, ctx in issued:
                    gshards[i] = compression.decompress(shard, ctx)

        pshards = None
        if params is not None:
            pleaves, _ = jax.tree.flatten(params)
            if stage == 3:
                # Stage 3: params arrive ALREADY in shard space — the
                # zero3_shard_params tuple the training loop owns (each
                # rank's [padded // world] flat bucket shards in-trace;
                # the global [padded] buckets host-side).
                if len(pleaves) != len(plan):
                    raise ValueError(
                        f"zero_stage=3 expects params as the "
                        f"zero3_shard_params tuple ({len(plan)} flat "
                        f"bucket shards), got {len(pleaves)} leaves — "
                        f"pass the shard tree the loop applies updates "
                        f"to, not the gathered model params")
                pshards = tuple(pleaves)
            else:
                pshards = _shard_params(plan, pleaves, own_world, in_trace)

        if db:
            acc = tuple(a + g.astype(a.dtype)
                        for a, g in zip(ms.acc_shards, gshards))
            mean = tuple((a / k).astype(jnp.asarray(g).dtype)
                         for a, g in zip(acc, gshards))
            with jax.named_scope("hvd.optimizer_update"):
                upd, inner_new = optimizer.update(mean, ms.inner, pshards,
                                                  **extra)
            ushards = tuple(
                jnp.where(is_last, u, jnp.zeros_like(u)) for u in upd)
            inner_next = jax.tree.map(
                lambda old, new: jnp.where(is_last, new, old),
                ms.inner, inner_new)
            acc_next = tuple(
                jnp.where(is_last, jnp.zeros_like(a), a) for a in acc)
            new_inner = ZeroOverlapMultiStepsState(
                mini_step=(t + 1) % k, inner=inner_next,
                acc_shards=acc_next, pending=tuple(new_pending))
        elif s1:
            with jax.named_scope("hvd.optimizer_update"):
                upd, inner_new = optimizer.update(tuple(gshards), ms.inner,
                                                  pshards, **extra)
            ushards = tuple(
                jnp.where(is_last, u, jnp.zeros_like(u)) for u in upd)
            inner_next = jax.tree.map(
                lambda old, new: jnp.where(is_last, new, old),
                ms.inner, inner_new)
            new_inner = ZeroFullMultiStepsState(
                mini_step=(t + 1) % k, inner=inner_next,
                acc=new_acc_full)
        else:
            with jax.named_scope("hvd.optimizer_update"):
                ushards, new_inner = stx.update(tuple(gshards), state.inner,
                                                pshards, **extra)

        if stage == 3:
            # No trailing all-gather: the updates stay in shard space and
            # the caller applies them to its shard tree (the next step's
            # forward re-gathers just in time). This is where stage 3's
            # wire asymmetry lives — the gather moved from the update's
            # tail to the forward's head, where it overlaps with compute.
            new_state = ZeroState(
                inner=new_inner,
                residual=None if state.residual is None else tuple(new_rs),
                gather_residual=None)
            if params is not None:
                updates = jax.tree.unflatten(
                    jax.tree.structure(params), list(ushards))
            else:
                updates = tuple(ushards)
            return updates, new_state

        uleaves: List[Any] = [None] * len(gleaves)
        new_ag: List[Any] = [None] * len(plan)
        for s in range(0, len(order), flight):
            issued = []
            for i in order[s:s + flight]:
                b = plan[i]
                is_float = jnp.issubdtype(b.dtype, jnp.floating)
                if eager_local:
                    new_ag[i] = (None if state.gather_residual is None
                                 else state.gather_residual[i])
                    issued.append((i, ushards[i], None))
                    continue
                wire, ctx = compression.compress(ushards[i])
                res = (None
                       if not (use_quant and is_float
                               and state.gather_residual)
                       else _res_read(state.gather_residual[i], in_trace))
                if res is not None:
                    if overlap:
                        full, nres = C.all_gather_stream(
                            wire, res, bucket_id=i, quantized=True,
                            block=quant_block)
                    else:
                        full, nres = C.all_gather(
                            wire, res, quantized=True, block=quant_block)
                    new_ag[i] = _res_write(state.gather_residual[i], nres,
                                           in_trace)
                else:
                    if overlap:
                        full = C.all_gather_stream(
                            wire, bucket_id=i,
                            quantized=use_quant and is_float,
                            block=quant_block)
                    else:
                        full = C.all_gather(
                            wire, quantized=use_quant and is_float,
                            block=quant_block)
                    new_ag[i] = (None if state.gather_residual is None
                                 else state.gather_residual[i])
                issued.append((i, full, ctx))
            for i, full, ctx in issued:
                if ctx is not None or not eager_local:
                    full = compression.decompress(full, ctx)
                for j, leaf in zip(plan[i].leaf_indices,
                                   fusion.unpack(plan[i], full)):
                    uleaves[j] = leaf

        new_state = ZeroState(
            inner=new_inner,
            residual=None if state.residual is None else tuple(new_rs),
            gather_residual=(None if state.gather_residual is None
                             else tuple(new_ag)))
        return jax.tree.unflatten(treedef, uleaves), new_state

    return optax.GradientTransformationExtraArgs(init_fn, update_fn)


# ---------------------------------------------------------------------------
# ZeRO-3 parameter sharding: the training loop owns flat bucket shards;
# the forward gathers them just in time (docs/zero.md).
# ---------------------------------------------------------------------------


def _zero3_rank(in_trace: bool, axes=None):
    if in_trace:
        return lax.axis_index(C._resolve_axes(axes))
    return basics.rank() if basics.is_initialized() else 0


def zero3_plan(params_template, *, fusion_threshold_bytes=None, axes=None):
    """The stage-3 bucket plan of a parameter pytree —
    ``plan_buckets(shard_multiple=world)`` over the flattened leaves, the
    SAME plan :class:`DistributedOptimizer`'s update derives from the
    gradient tree, so parameter, gradient, and moment shard layouts all
    agree (``params_template`` needs only shapes/dtypes)."""
    leaves, _ = jax.tree.flatten(params_template)
    plan_world, _, _ = _zero_worlds(axes)
    return fusion.plan_buckets(leaves, fusion_threshold_bytes,
                               shard_multiple=plan_world)


def zero3_shard_params(params, *, fusion_threshold_bytes=None, axes=None):
    """Pack a parameter pytree into its flat bucket (shard) tuple — what
    a ``zero_stage=3`` training loop owns instead of the model tree.

    Host-side (single-controller SPMD) this returns the GLOBAL form —
    one full ``[padded]`` flat buffer per bucket; ``device_put`` with
    :func:`zero3_param_pspecs` then hands each rank its rank-major
    ``1/world`` slice. In-trace (or under the eager process world) it
    returns this rank's ``[padded // world]`` shards directly. Round-trip
    with :func:`zero3_gather_params`."""
    leaves, _ = jax.tree.flatten(params)
    plan_world, own_world, in_trace = _zero_worlds(axes)
    plan = fusion.plan_buckets(leaves, fusion_threshold_bytes,
                               shard_multiple=plan_world)
    if own_world == 1:
        return tuple(fusion.pack(b, leaves) for b in plan)
    r = _zero3_rank(in_trace, axes)
    return tuple(
        fusion.shard_slice(fusion.pack(b, leaves), own_world, r)
        for b in plan)


def zero3_param_pspecs(pshards):
    """PartitionSpec tree for a :func:`zero3_shard_params` tuple: every
    flat bucket shards rank-major along its (only) axis —
    ``P(HVD_AXES)``, exactly like the ZeRO moment leaves."""
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(lambda _: P(basics.HVD_AXES), pshards)


def zero3_gather_params(
    pshards,
    params_template,
    *,
    fusion_threshold_bytes=None,
    axes=None,
    overlap: Optional[bool] = None,
    num_comm_streams: Optional[int] = None,
    fill_sched=None,
):
    """Reassemble the full model pytree from stage-3 parameter shards —
    the just-in-time gather a ``zero_stage=3`` forward runs on.

    In-trace each bucket all-gathers (replicated by construction, so the
    result feeds replicated consumers directly) in FORWARD order
    (:func:`~horovod_tpu.ops.fusion.gather_order` — lowest leaf index
    first, the layers the forward needs soonest), through the PR-5
    stream entry points in flights of ``num_comm_streams`` when
    ``overlap`` is on: unpacking is deferred past the flight so the
    latency-hiding scheduler can run deeper layers' gathers under the
    already-gathered layers' compute. Host-side, on the GLOBAL shard
    form, this is a pure unpack (no wire) — the exact inverse of
    :func:`zero3_shard_params`. ``params_template`` supplies structure
    and shapes only (``jax.ShapeDtypeStruct`` leaves work).

    ``fill_sched`` (a ``PPSchedule``) opens a T3-style
    :func:`~horovod_tpu.plan.accounting.bubble_fill` window around the
    streamed gathers: up to ``fill_sched.idle_ticks_per_rank`` bucket
    flights are credited against the pipeline schedule's idle ticks
    (``WireStats.bubble_hidden_bytes`` / ``comm.pp.filled_ticks``,
    docs/pipeline.md). Accounting-only — the issue order is unchanged;
    requires ``overlap`` (unstreamed gathers cannot be latency-hidden).
    """
    tleaves, treedef = jax.tree.flatten(params_template)
    plan_world, own_world, in_trace = _zero_worlds(axes)
    plan = fusion.plan_buckets(tleaves, fusion_threshold_bytes,
                               shard_multiple=plan_world)
    shards = list(jax.tree.leaves(pshards))
    if len(shards) != len(plan):
        raise ValueError(
            f"pshards has {len(shards)} buckets but the template plans "
            f"{len(plan)} — pass the tuple zero3_shard_params produced "
            f"for this parameter tree (same threshold, same world)")
    overlap_on, flight = fusion._resolve_overlap(overlap, num_comm_streams,
                                                 None)
    order = fusion.gather_order(plan)
    if not overlap_on:
        flight = 1
    eager_local = (not in_trace) and own_world == 1
    fill_ctx = contextlib.nullcontext()
    if fill_sched is not None and overlap_on and not eager_local:
        from ..plan import accounting as _acct_mod

        fill_ctx = _acct_mod.bubble_fill(fill_sched.idle_ticks_per_rank,
                                         kind="zero3.ag")
    uleaves: List[Any] = [None] * len(tleaves)
    with fill_ctx:
        for s in range(0, len(order), flight):
            issued = []
            for i in order[s:s + flight]:
                if eager_local:
                    full = shards[i]  # global form already
                elif overlap_on:
                    full = C.all_gather_stream(shards[i], bucket_id=i,
                                               axes=axes)
                else:
                    full = C.all_gather(shards[i], axes=axes)
                issued.append((i, full))
            # Unpack AFTER the whole flight is issued (ops/fusion.py
            # flight contract): no consumer sits between in-flight
            # gathers.
            for i, full in issued:
                for j, leaf in zip(plan[i].leaf_indices,
                                   fusion.unpack(plan[i], full)):
                    uleaves[j] = leaf
    return jax.tree.unflatten(treedef, uleaves)


def zero3_reshard_params(
    pshards,
    params_template,
    *,
    from_world: int,
    to_world: int,
    fusion_threshold_bytes: Optional[int] = None,
):
    """Re-shard a GLOBAL (host-side) stage-3 parameter tuple between
    world sizes — the elastic/checkpoint-restore path, the parameter
    analogue of :func:`zero_reshard_state`. Exact: each bucket unpacks to
    parameter layout under the old plan and repacks under the new one
    (leaf→bucket assignment is world-independent, padding holds zeros),
    so a round-trip is the identity."""
    tleaves, _ = jax.tree.flatten(params_template)
    plan_f = fusion.plan_buckets(tleaves, fusion_threshold_bytes,
                                 shard_multiple=from_world)
    plan_t = fusion.plan_buckets(tleaves, fusion_threshold_bytes,
                                 shard_multiple=to_world)
    shards = list(jax.tree.leaves(pshards))
    if len(shards) != len(plan_f):
        raise ValueError(
            f"pshards has {len(shards)} buckets, plan has {len(plan_f)}")
    return tuple(
        fusion.pack(bt, _scatter_unpack(bf, buf, len(tleaves)))
        for bf, bt, buf in zip(plan_f, plan_t, shards))


def zero_reshard_state(
    state: ZeroState,
    params,
    *,
    from_world: int,
    to_world: int,
    to_local_size: Optional[int] = None,
    fusion_threshold_bytes: Optional[int] = None,
) -> ZeroState:
    """Re-shard a GLOBAL (host-side) :class:`ZeroState` between world
    sizes — the elastic resize path.

    Bucket padding depends on the world size
    (``plan_buckets(shard_multiple=world)``), so a state saved at one
    world cannot be ``device_put`` at another directly. This unpacks
    every bucket-flat moment leaf back to parameter layout under the old
    plan and repacks it under the new plan (leaf→bucket assignment is
    world-independent, so the mapping is exact and a round-trip is the
    identity — padding slots hold zeros by construction). EF residuals
    are approximation state tied to the old wire geometry and reset to
    zeros at the new one.

    Expects ``state`` in its global form (full ``[padded]`` flat leaves —
    what host-side ``init`` produces and what ``jax.device_get`` of a
    ``P(HVD_AXES)``-sharded running state yields); ``params`` is the
    matching parameter pytree. Shard with
    :func:`zero_state_pspecs` after resharding.

    Generalizes across all three stages (stage-3 PARAMETER shards are
    loop-owned, not optimizer state — reshard those with
    :func:`zero3_reshard_params`): bucket-flat moment groups (and the
    stage-2 :class:`ZeroMultiStepsState` shard accumulator, which shares
    their signature) remap exactly, mid-cycle included. Leading-axis
    per-rank MICROBATCH state — the stage-1
    :class:`ZeroFullMultiStepsState` accumulator and the overlap
    double-buffer's pending buckets — is wire/cycle geometry and is
    rebuilt as zeros at the new world, so reshard at a cycle boundary
    (``mini_step == 0``), where those buffers hold zeros anyway and the
    round-trip stays the identity.
    """
    leaves_p, _ = jax.tree.flatten(params)
    plan_f = fusion.plan_buckets(leaves_p, fusion_threshold_bytes,
                                 shard_multiple=from_world)
    plan_t = fusion.plan_buckets(leaves_p, fusion_threshold_bytes,
                                 shard_multiple=to_world)
    k = len(plan_f)
    n_leaves = len(leaves_p)
    sig = [(jnp.dtype(b.dtype), b.padded_size) for b in plan_f]
    pshapes = [tuple(jnp.shape(l)) for l in leaves_p]

    flat, treedef = jax.tree.flatten(state.inner)
    out: List[Any] = []
    j = 0
    while j < len(flat):
        group = flat[j:j + k]
        if (len(group) == k and all(
                getattr(g, "ndim", 0) == 1
                and jnp.dtype(g.dtype) == d and g.shape[0] == p
                for g, (d, p) in zip(group, sig))):
            # One moment group (e.g. Adam's mu across all buckets):
            # bucket-flat under plan_f → param layout → bucket-flat
            # under plan_t.
            for g, bf, bt in zip(group, plan_f, plan_t):
                out.append(
                    fusion.pack(bt, _scatter_unpack(bf, g, len(leaves_p))))
            j += k
            continue
        if (len(group) == k and all(
                getattr(g, "ndim", 0) == 2
                and jnp.dtype(g.dtype) == d
                and g.shape == (from_world, p)
                for g, (d, p) in zip(group, sig))):
            # Overlap double-buffer pending ([world, padded] per bucket):
            # cycle-boundary zeros, rebuilt at the new world's padding.
            if from_world == to_world:
                out.extend(group)
            else:
                out.extend(
                    jnp.zeros((to_world, bt.padded_size), g.dtype)
                    for g, bt in zip(group, plan_t))
            j += k
            continue
        groupa = flat[j:j + n_leaves]
        if (len(groupa) == n_leaves and n_leaves > 0 and all(
                getattr(g, "ndim", -1) == 1 + len(ps)
                and tuple(g.shape) == (from_world,) + ps
                for g, ps in zip(groupa, pshapes))):
            # Stage-1 full-gradient accumulator ([world, *param_shape]
            # per leaf): cycle-boundary zeros at the new world.
            if from_world == to_world:
                out.extend(groupa)
            else:
                out.extend(
                    jnp.zeros((to_world,) + ps, g.dtype)
                    for g, ps in zip(groupa, pshapes))
            j += n_leaves
            continue
        out.append(flat[j])
        j += 1
    inner = jax.tree.unflatten(treedef, out)

    if state.residual is None:
        return ZeroState(inner=inner, residual=None, gather_residual=None)
    nl = (to_local_size if to_local_size is not None
          else (basics.local_size() if basics.is_initialized()
                else to_world))
    rs, ag = [], []
    for shp in _zero_residual_shapes(plan_t, to_world, nl):
        if shp is None:
            rs.append(None)
            ag.append(None)
        else:
            rs.append(jnp.zeros((to_world,) + shp[0], jnp.float32))
            ag.append(jnp.zeros((to_world,) + shp[1], jnp.float32))
    return ZeroState(inner=inner, residual=tuple(rs),
                     gather_residual=tuple(ag))


def _scatter_unpack(bucket, buf, n_leaves: int) -> List[Any]:
    """Unpack one bucket-flat buffer into a dense leaf list positioned at
    the bucket's leaf indices (so ``fusion.pack`` of the TARGET plan —
    whose ``leaf_indices`` are identical — can repack it)."""
    leaves: List[Any] = [None] * n_leaves
    for i, leaf in zip(bucket.leaf_indices, fusion.unpack(bucket, buf)):
        leaves[i] = leaf
    return leaves
