"""Gradient-transform wrappers: the DistributedGradientTape equivalent.

Reference: ``hvd.DistributedGradientTape`` (tensorflow/__init__.py:511-576)
wraps a TF GradientTape so ``tape.gradient`` returns allreduced gradients,
via ``_make_allreduce_grads_fn`` (tensorflow/__init__.py:246-278).

JAX has no tape — gradients come from ``jax.grad`` / ``jax.value_and_grad``.
The equivalents here wrap those transforms so the returned gradients are
already fused-allreduced across the mesh, which is exactly what the
reference's tape wrapper does at the same point in the step.

A subtlety makes this more than sugar: under ``jax.shard_map`` autodiff
*auto-psums* gradients of replicated inputs (the transpose of the implicit
replicate-to-varying broadcast), producing per-parameter fp32 SUM
collectives outside our control — no fusion policy, no compression, no
Adasum. To reclaim Horovod semantics we first cast the differentiated
arguments to device-varying (``lax.pcast(..., to='varying')``), so the raw
gradients are true per-rank locals, then run them through the fused
allreduce exactly as the reference does.
"""

from __future__ import annotations

import threading
from typing import Optional

import jax
from jax import lax

from ..ops import collective_ops as C
from ..ops import fusion
from ..ops.compression import Compression


# One gradient reducer per step. Gradients this module has reduced come
# back replicated, and a replicated gradient is exactly what
# DistributedOptimizer reads as autodiff's cross-rank SUM (it would divide
# by the world a second time). Nothing in a value's type tells the two
# apart, so the reduction leaves a note on the trace it ran in and the
# optimizer refuses replicated gradients in that trace.
_reduced = threading.local()


def _note_reduced(axes) -> None:
    if C._resolve_axes(axes):
        _reduced.trace = jax.core.get_opaque_trace_state()


def reduced_in_this_trace() -> bool:
    """True when :func:`allreduce_gradients` (and so the default
    :func:`value_and_grad`) has reduced gradients in the trace that is
    current now."""
    noted = getattr(_reduced, "trace", None)
    return noted is not None and noted == jax.core.get_opaque_trace_state()


def _pvary_tree(tree, axes_t):
    """Cast every leaf to be varying over ``axes_t`` so autodiff produces
    local (un-psummed) gradients for it."""
    return jax.tree.map(lambda x: C.pvary_missing(x, axes_t), tree)


def allreduce_gradients(
    grads,
    *,
    op: C.ReduceOp = C.ReduceOp.AVERAGE,
    compression=Compression.none,
    fusion_threshold_bytes: Optional[int] = None,
    axes=None,
    hierarchical: Optional[bool] = None,
    quantized: Optional[bool] = None,
    error_feedback=None,
    tuned_params=None,
    overlap: Optional[bool] = None,
    num_comm_streams: Optional[int] = None,
    plan=None,
):
    """Allreduce a gradient pytree (reference: _make_allreduce_grads_fn,
    tensorflow/__init__.py:246-278). Fused into per-dtype buckets;
    ``presummed=True`` because invariant gradient leaves under shard_map are
    autodiff-psummed sums, not equal per-rank contributions.

    ``quantized`` selects the blockwise-int8 DCN wire per bucket;
    ``error_feedback`` (a pytree of per-rank residuals matching ``grads``,
    zeros initially) switches the return value to
    ``(reduced, new_error_feedback)`` so callers can thread EF state
    functionally — :class:`horovod_tpu.DistributedOptimizer` does this
    inside its optax state instead. ``tuned_params`` applies an autotuner
    override (see :func:`~horovod_tpu.ops.fusion.allreduce_pytree`).
    ``overlap`` (default ``HOROVOD_OVERLAP``) issues the buckets through
    the reverse-layer stream schedule in flights of ``num_comm_streams``
    — bit-identical values, overlap-friendly issue order
    (docs/overlap.md). ``plan`` threads an explicit wire plan (a
    :class:`horovod_tpu.plan.WirePlan`, or a
    :class:`~horovod_tpu.plan.StepPlan` whose ``gradient`` is used) in
    place of the boolean knobs, which remain as aliases
    (docs/wire-plan.md)."""
    if plan is not None and hasattr(plan, "gradient"):
        plan = plan.gradient  # a StepPlan: thread its gradient wire
    _note_reduced(axes)
    with jax.named_scope("hvd.allreduce_grads"):
        return fusion.allreduce_pytree(
            grads, op=op, compression=compression,
            threshold_bytes=fusion_threshold_bytes, axes=axes,
            hierarchical=hierarchical, presummed=True,
            quantized=quantized, error_feedback=error_feedback,
            tuned_params=tuned_params, overlap=overlap,
            num_comm_streams=num_comm_streams, plan=plan)


def value_and_grad(
    fun,
    argnums=0,
    has_aux: bool = False,
    *,
    op: C.ReduceOp = C.ReduceOp.AVERAGE,
    compression=Compression.none,
    fusion_threshold_bytes: Optional[int] = None,
    axes=None,
    hierarchical: Optional[bool] = None,
    quantized: Optional[bool] = None,
    zero: Optional[bool] = None,
    zero_stage: Optional[int] = None,
    overlap: Optional[bool] = None,
    num_comm_streams: Optional[int] = None,
    tuned_params=None,
    plan=None,
    reduce: bool = True,
    pp_stages: Optional[int] = None,
    pp_microbatches: Optional[int] = None,
    pp_schedule: Optional[str] = None,
    pp_interleave: Optional[int] = None,
    moe_experts: Optional[int] = None,
    moe_capacity_factor: Optional[float] = None,
    moe_topk: Optional[int] = None,
    **jax_kwargs,
):
    """``jax.value_and_grad`` whose gradients are allreduced across ranks —
    the DistributedGradientTape of the JAX world
    (reference: tensorflow/__init__.py:511-576).

    A step has ONE gradient reducer. The default (``reduce=True``) is the
    owner here: the gradients come back replicated and reduced, ready for
    a plain optax transformation. ``reduce=False`` still pvaries the
    differentiated arguments (so the gradients come back as true per-rank
    locals instead of auto-psummed fp32 sums) but skips the allreduce —
    the hand-off that makes :class:`~horovod_tpu.DistributedOptimizer`
    the owner. Handing the default's reduced gradients to a
    ``DistributedOptimizer`` raises at trace time: it would read them as
    autodiff's cross-rank sum and divide by the world a second time.

    ``zero`` / ``zero_stage`` (defaults: the ``HOROVOD_ZERO_STAGE`` /
    ``HOROVOD_ZERO_SHARDING`` knobs; ``zero=True`` aliases stage 2) mark
    the step as ZeRO-sharded: under ZeRO the gradient reduction IS the
    optimizer's reduce-scatter, so any stage > 0 behaves as
    ``reduce=False`` — raw per-rank local gradients are handed to the
    ``DistributedOptimizer(zero_stage=N)`` update, whose bucket
    reduce-scatter is then the one and only gradient collective. This is
    the knob's thread-through point: a step built with
    ``hvd.value_and_grad(..., zero_stage=n)`` + ``DistributedOptimizer(
    ..., zero_stage=n)`` flips between the replicated and sharded
    schedules with one flag (see docs/zero.md). ``plan`` (a
    :class:`horovod_tpu.plan.StepPlan` or bare ``WirePlan``) threads the
    wire plan instead of the booleans — a StepPlan with ``zero_stage>0``
    implies ``reduce=False`` exactly like the ``zero`` knob.

    ``pp_stages``/``pp_microbatches``/``pp_schedule``/``pp_interleave``
    validate the pipeline composition the step runs under exactly like
    :class:`~horovod_tpu.DistributedOptimizer`'s pp knobs
    (docs/pipeline.md) — the fused pipeline schedules
    (:func:`horovod_tpu.pipelined_gpt_train` /
    :func:`~horovod_tpu.parallel.pipeline.interleaved_1f1b`) compute
    their own gradients, so here the knobs are a loud-failure contract,
    not a behavior switch; the returned gradients are still reduced over
    the DATA axes only (``axes=None`` never includes ``hvd_pp``).

    ``moe_experts``/``moe_capacity_factor``/``moe_topk`` validate the
    MoE composition the same way (docs/moe.md): expert gradients stay
    isolated per expert group because ``axes=None`` never includes
    ``hvd_ep`` — the knobs fail loudly on a misconfiguration (expert
    count vs the live ep axis, capacity/topk bounds)."""
    if any(k is not None for k in (pp_stages, pp_microbatches,
                                   pp_schedule, pp_interleave)):
        from .optimizer import _validate_pp_knobs

        _validate_pp_knobs(pp_stages, pp_microbatches, pp_schedule,
                           pp_interleave, plan=plan,
                           tuned_params=tuned_params)
    if any(k is not None for k in (moe_experts, moe_capacity_factor,
                                   moe_topk)):
        from .optimizer import _validate_moe_knobs

        _validate_moe_knobs(moe_experts, moe_capacity_factor, moe_topk,
                            plan=plan, tuned_params=tuned_params)
    if plan is not None and hasattr(plan, "gradient"):
        if zero is None and zero_stage is None:
            zero = plan.zero_stage > 0
        if overlap is None:
            overlap = plan.overlap
        if num_comm_streams is None:
            num_comm_streams = plan.num_comm_streams
        if quantized is None:
            quantized = plan.quantized
        if hierarchical is None:
            hierarchical = plan.hierarchical
        plan = plan.gradient if plan.zero_stage == 0 else None
    if zero is None and zero_stage is not None:
        zero = zero_stage > 0
    if zero is None and tuned_params is not None:
        zero = tuned_params.zero_sharding
    vg = jax.value_and_grad(fun, argnums=argnums, has_aux=has_aux,
                            **jax_kwargs)
    idxs = (argnums,) if isinstance(argnums, int) else tuple(argnums)

    def wrapped(*args, **kwargs):
        zero_eff = zero
        if zero_eff is None:
            from ..parallel.optimizer import _resolve_zero_stage_config

            zero_eff = _resolve_zero_stage_config() > 0
        axes_t = C._resolve_axes(axes)
        if axes_t:
            args = list(args)
            for i in idxs:
                args[i] = _pvary_tree(args[i], axes_t)
        with jax.named_scope("hvd.grad"):
            val, grads = vg(*args, **kwargs)
        if not reduce or zero_eff:
            return val, grads
        grads = allreduce_gradients(
            grads, op=op, compression=compression,
            fusion_threshold_bytes=fusion_threshold_bytes, axes=axes,
            hierarchical=hierarchical, quantized=quantized,
            tuned_params=tuned_params, overlap=overlap,
            num_comm_streams=num_comm_streams, plan=plan)
        return val, grads

    return wrapped


def grad(fun, argnums=0, has_aux: bool = False, **kwargs):
    """``jax.grad`` with allreduced gradients (see :func:`value_and_grad`).
    Mirrors the jax.grad contract: with ``has_aux`` returns
    ``(grads, aux)``, otherwise just ``grads``."""
    vg = value_and_grad(fun, argnums=argnums, has_aux=has_aux, **kwargs)

    def wrapped(*args, **kw):
        val, grads = vg(*args, **kw)
        if has_aux:
            return grads, val[1]
        return grads

    return wrapped


class DistributedGradientTape:
    """Name-parity shim for reference users porting TF2 code
    (tensorflow/__init__.py:511-576).

    Usage::

        tape = hvd.DistributedGradientTape(loss_fn)
        loss, grads = tape.gradient(params, batch)

    where ``loss_fn(params, *inputs)`` is a scalar loss. The gradients
    returned are allreduced. New code should call
    :func:`horovod_tpu.value_and_grad` directly.
    """

    def __init__(self, loss_fn, **kwargs):
        self._vg = value_and_grad(loss_fn, **kwargs)

    def gradient(self, params, *inputs):
        return self._vg(params, *inputs)
