"""Collective operations: allreduce / allgather / broadcast / alltoall / join.

Reference surface: the op set of ``horovod/common/message.h:50-52``
(ALLREDUCE, ALLGATHER, BROADCAST, JOIN, ADASUM, ALLTOALL) exposed per
framework as ``hvd.allreduce/allgather/broadcast/alltoall``
(torch/mpi_ops.py:130-646, tensorflow/mpi_ops.py).

TPU-native redesign
-------------------
The reference executes every collective from a background thread through
NCCL/MPI/Gloo after a rank-0 negotiation round (operations.cc:571-624).  On
TPU the fast path is the opposite: collectives are **compiled into the XLA
program** over the ICI mesh, where XLA schedules and fuses them with compute.
So each op here has two modes, selected automatically:

* **compiled (in-jit)** — when tracing under ``jax.shard_map`` over the
  Horovod mesh axes, ops lower straight to ``lax.psum`` / ``lax.all_gather``
  / ``lax.all_to_all`` / masked-``psum`` broadcast.  This is the analogue of
  the reference's NCCL ops (nccl_operations.cc), with XLA playing the role of
  the fusion buffer and stream scheduler.
* **eager (host)** — outside jit, ops run over the *process world* (one
  participant per host), matching how a reference user would allreduce a
  metric or broadcast an object outside the training graph. Data rides a
  cached one-op jit program over the leader chips.

Hierarchical allreduce (reference: NCCLHierarchicalAllreduce,
nccl_operations.cc:190-380) decomposes into intra-host ``psum_scatter`` (ICI)
→ cross-host ``psum`` (DCN) → intra-host ``all_gather`` (ICI), enabled by
``HOROVOD_HIERARCHICAL_ALLREDUCE`` or per-call.
"""

from __future__ import annotations

import contextlib
import enum
import os
import threading
import time
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import basics
from ..common.basics import CROSS_AXIS, HVD_AXES, LOCAL_AXIS
from ..common.exceptions import (DuplicateTensorNameError,
                                 NotInitializedError)
from ..monitor import registry as _metrics
from ..plan import accounting as _accounting
from ..plan import compiler as _plan_compiler
from ..plan import planner as _planner
# Wire accounting + overlap instrumentation live with the plan compiler
# (horovod_tpu/plan/accounting.py, docs/wire-plan.md); re-exported here
# for the public `hvd.record_wire_stats` surface and compatibility.
from ..plan.accounting import (  # noqa: F401
    WireStats,
    _acct,
    _acct_enabled,
    _modeled_wire_ms,
    _wire_recorders,
    record_wire_stats,
)
from . import compression as _compression
from .compression import Compression


class ReduceOp(enum.IntEnum):
    """Reduction ops (reference: torch/mpi_ops.py:48-56 — Average, Sum,
    Adasum; plus Min/Max/Product which XLA gives us for free)."""

    AVERAGE = 0
    SUM = 1
    ADASUM = 2
    MIN = 3
    MAX = 4
    PRODUCT = 5


# Reference-style aliases (hvd.Average / hvd.Sum / hvd.Adasum).
Average = ReduceOp.AVERAGE
Sum = ReduceOp.SUM
Adasum = ReduceOp.ADASUM
Min = ReduceOp.MIN
Max = ReduceOp.MAX
Product = ReduceOp.PRODUCT


def _hvd_axes_in_trace() -> Tuple[str, ...]:
    """Horovod mesh axes bound in the current trace, in rank-major
    ``(pod, cross, local)`` order (the pod axis only exists on a 3-level
    ``mesh_shape=(cross, local, pods)`` mesh)."""
    return basics._trace_world_axes()


def _resolve_axes(axes) -> Tuple[str, ...]:
    if axes is None:
        return _hvd_axes_in_trace()
    if isinstance(axes, str):
        return (axes,)
    return tuple(axes)


# Canonical axis helpers live in common/basics.py (the plan compiler uses
# them too); aliased here for the historical `C._axis_size` call sites.
_axis_size = basics._axis_size
_unbound_axis_error = basics._unbound_axis_error


def _world_size(axes: Tuple[str, ...]):
    n = 1
    for a in axes:
        n *= _axis_size(a)
    return n


def _vma(x) -> frozenset:
    """Varying-manual-axes of ``x``: which mesh axes the value differs
    across, as the aval tracks it (``jax.typeof(x).vma``). An empty set
    means the value is provably identical on every device (or is not an
    array at all)."""
    try:
        return frozenset(jax.typeof(x).vma)
    except (TypeError, AttributeError):  # not an array-like value
        return frozenset()


def _pvary(x, axes) -> "jax.Array":
    """Cast ``x`` to be varying over ``axes`` (a free type-level
    broadcast)."""
    if not axes:
        return x
    return lax.pcast(x, tuple(axes), to="varying")


def pvary_missing(x, axes) -> "jax.Array":
    """Cast ``x`` to be varying over whichever of ``axes`` it is not
    already varying over (a free type-level broadcast; no-op when none
    are missing). The single home for this idiom — used by the gradient
    tape, the Pallas kernel wrappers, and the pipeline scan inits."""
    missing = tuple(a for a in axes if a not in _vma(x))
    return _pvary(x, missing) if missing else x


def _is_replicated(x, axes: Tuple[str, ...]) -> bool:
    return not (set(axes) & _vma(x))


def _scale(tensor, factor):
    """Pre/post scaling (reference: prescale/postscale in message.h:48-113 and
    the ScaleBuffer CUDA kernel, ops/cuda/cuda_kernels.cu:128). On TPU this is
    a fused elementwise multiply XLA folds into the surrounding program."""
    if factor is None or factor == 1.0:
        return tensor
    if jnp.issubdtype(tensor.dtype, jnp.integer):
        return (tensor * factor).astype(tensor.dtype)
    return tensor * jnp.asarray(factor, dtype=tensor.dtype)


# ---------------------------------------------------------------------------
# Wire lowering: every compiled collective below routes through the plan
# compiler (horovod_tpu/plan/, docs/wire-plan.md). The entry points here
# keep the public reference-parity API — op semantics, scaling,
# compression casts, replicated short-circuits, eager fallbacks — derive
# a WirePlan from the knobs (or take an explicit ``plan=``), and hand the
# wire composition to plan.compiler, which owns the leg lowering rules
# and the trace-time wire accounting (the bench A/B instrumentation).
# ---------------------------------------------------------------------------


def _quant_block_size(block: Optional[int]) -> int:
    if block:
        return int(block)
    if basics.is_initialized():
        return basics.config().quant_block
    return _compression.QUANT_BLOCK


def _resolve_plan(plan, default_fn):
    """An explicit validated ``plan=`` wins; otherwise derive the default
    from the knob set (``default_fn`` is a zero-arg planner call)."""
    if plan is not None:
        return plan.validate()
    return default_fn()


# ---------------------------------------------------------------------------
# Bucket-level reduce-scatter / all-gather — the ZeRO-1 wire pair.
#
# A fused gradient bucket planned with ``plan_buckets(shard_multiple=world)``
# (ops/fusion.py) reduce-scatters into ``world`` contiguous flat shards in
# RANK-MAJOR order (rank r = cross*local_size + local owns
# ``[r*seg, (r+1)*seg)``), the optimizer updates only its shard, and the
# updated values all-gather back. Rank-major ordering matches how
# ``P(HVD_AXES)`` splits a leading dim, so sharded optimizer state outside
# the trace is the flat bucket itself — no permutation.
#
# The hierarchical decomposition follows HiCCL's placement rule (the
# compiler enforces it as an IR validation rule): the ICI leg always
# rides the payload dtype; only the cross-host DCN leg is eligible for
# the blockwise-int8 wire. The reduce_scatter plan is the reduce half of
# the quantized-allreduce plan, the all_gather plan its gather half —
# ZeRO splits that collective around the optimizer update. Both lower
# through plan.compiler (lower_reduce_scatter / lower_all_gather).
# ---------------------------------------------------------------------------


def _rs_postscale(shard, op: ReduceOp, world: int, postscale_factor: float):
    post = postscale_factor
    if op == ReduceOp.AVERAGE:
        post = post / world
    return _scale(shard, post)


def reduce_scatter(
    tensor,
    residual=None,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    name: Optional[str] = None,
    axes=None,
    quantized: Optional[bool] = None,
    block: Optional[int] = None,
    plan=None,
    _presummed: bool = False,
):
    """Reduce a flat buffer across all ranks and return this rank's
    contiguous ``1/world`` shard (rank-major: rank ``r`` owns elements
    ``[r*seg, (r+1)*seg)`` of the reduction).

    The ZeRO-1 gradient wire: where :func:`allreduce` moves
    ``2n(k-1)/k`` bytes per device, the reduce-scatter half moves
    ``n(k-1)/k`` and leaves each rank holding exactly the shard its
    optimizer partition updates. Only ``op=Average``/``Sum`` are defined
    (a scatter of min/max has no reference analogue and no user).

    ``quantized`` (default: the ``HOROVOD_QUANTIZED_ALLREDUCE`` knob)
    sends blockwise-int8 on the cross-host (DCN) leg of the hierarchical
    decomposition (the reduce half of the quantized-allreduce plan,
    plan/compiler.py); the ICI leg keeps
    the payload dtype. ``residual`` is the error-feedback accumulator for
    that leg, sized ``n / local_size`` (this rank's ICI-scattered shard —
    quantization error lives on what this rank *sends*, which is its
    post-ICI shard, not its final ``1/world`` segment); pass zeros
    initially and the call returns ``(shard, new_residual)``. Without
    ``residual`` the return is just ``shard``. On exact paths (quantized
    off, no cross axis, eager) a provided residual is consumed into the
    payload and returned as zeros.

    In-trace the input must divide evenly by the world size — pack it
    with ``plan_buckets(shard_multiple=world)`` (ops/fusion.py). Eagerly
    the reduction runs over the process world through the native core
    (allreduce + local slice; byte savings are a compiled-path feature).

    ``plan`` (a validated :class:`horovod_tpu.plan.WirePlan` for the
    ``reduce_scatter`` collective) overrides the knob-derived leg
    composition; the boolean knobs remain as aliases (docs/wire-plan.md).
    """
    tensor = jnp.asarray(tensor)
    if tensor.ndim != 1:
        raise ValueError(
            f"reduce_scatter operates on flat bucket buffers, got shape "
            f"{tensor.shape} — ravel and pad with plan_buckets/pack")
    if op not in (ReduceOp.AVERAGE, ReduceOp.SUM):
        raise ValueError(f"reduce_scatter supports Average/Sum, got {op}")
    axes_t = _resolve_axes(axes)
    if plan is not None and quantized is None:
        quantized = plan.is_quantized
    quantized = _resolve_quantized(quantized, Compression.none)
    quantized = quantized and jnp.issubdtype(tensor.dtype, jnp.floating)

    if not axes_t:
        return _eager_reduce_scatter(tensor, residual, op,
                                     prescale_factor, postscale_factor,
                                     name)

    world = _world_size(axes_t)
    n = int(tensor.shape[0])
    if n % world:
        raise ValueError(
            f"reduce_scatter buffer of {n} elements does not divide into "
            f"{world} shards — plan buckets with shard_multiple=world")
    seg = n // world

    if _is_replicated(tensor, axes_t):
        # No wire. presummed (gradient path): the value is already the
        # cross-rank sum — slice it (Average adds the /world). Otherwise
        # equal per-rank contributions: Sum scales by world, Average is
        # the identity — exactly what the wire would return.
        x = _scale(tensor, prescale_factor)
        rank = lax.axis_index(axes_t)
        shard = lax.dynamic_slice_in_dim(x, rank * seg, seg, 0)
        if _presummed:
            shard = _rs_postscale(shard, op, world, postscale_factor)
        else:
            if op == ReduceOp.SUM:
                shard = _scale(shard, float(world))
            shard = _scale(shard, postscale_factor)
        new_res = None if residual is None else jnp.zeros_like(residual)
        return shard if residual is None else (shard, new_res)

    flat = _scale(pvary_missing(tensor, axes_t), prescale_factor)
    eff_plan = _resolve_plan(
        plan, lambda: _planner.derive_reduce_scatter(
            levels=_planner.levels_of(axes_t), quantized=quantized,
            error_feedback=residual is not None, block=block))
    shard, new_res = _plan_compiler.lower_reduce_scatter(
        eff_plan, flat, residual=residual,
        block=_quant_block_size(block), axes=axes_t, world=world)
    shard = _rs_postscale(shard, op, world, postscale_factor)
    return shard if residual is None else (shard, new_res)


def all_gather(
    shard,
    residual=None,
    *,
    name: Optional[str] = None,
    axes=None,
    quantized: Optional[bool] = None,
    block: Optional[int] = None,
    plan=None,
):
    """Concatenate per-rank flat shards in rank-major order into the full
    replicated buffer — the inverse of :func:`reduce_scatter` and the
    second half of the ZeRO-1 step (broadcast of the updated parameter
    shards).

    The result is replicated BY CONSTRUCTION (the repo's masked-psum
    idiom: each rank contributes its shard into a zeroed buffer at its
    own offset, disjoint support makes the psum exact), so it feeds
    ``out_specs=P()`` consumers directly — a plain ``lax.all_gather``
    output carries a device-varying mark that would poison them.

    ``quantized`` sends blockwise-int8 on the cross-host (DCN) leg (the
    gather half of the quantized-allreduce plan, plan/compiler.py) —
    with optional error feedback:
    ``residual`` is the accumulator over this rank's OWNED segment
    (shape ``[seg]``); when given the return becomes
    ``(full, new_residual)``. Every rank (owner included) consumes the
    same dequantized value, so the buffer stays exactly replicated.

    Distinct from :func:`allgather` (the reference-parity op): this is
    the flat bucket primitive — replication by construction, quantized
    DCN leg, eager fallback over the process world.
    """
    shard = jnp.asarray(shard)
    if shard.ndim != 1:
        raise ValueError(
            f"all_gather operates on flat shard buffers, got shape "
            f"{shard.shape}")
    axes_t = _resolve_axes(axes)
    if plan is not None and quantized is None:
        quantized = plan.is_quantized
    quantized = _resolve_quantized(quantized, Compression.none)
    quantized = quantized and jnp.issubdtype(shard.dtype, jnp.floating)

    if not axes_t:
        return _eager_shard_all_gather(shard, residual, name)

    world = _world_size(axes_t)

    if _is_replicated(shard, axes_t):
        # Equal shard everywhere: the gather is a local tile.
        full = jnp.tile(shard, world)
        new_res = None if residual is None else jnp.zeros_like(residual)
        return full if residual is None else (full, new_res)

    use_quant = (quantized and set(axes_t) == set(HVD_AXES)
                 and _axis_size(CROSS_AXIS) > 1)
    eff_plan = _resolve_plan(
        plan, lambda: _planner.derive_all_gather(
            levels=_planner.levels_of(axes_t) if use_quant else None,
            quantized=use_quant, error_feedback=residual is not None,
            block=block))
    if eff_plan.is_quantized and not use_quant:
        # An explicit quantized plan on a mesh with no DCN hop (or
        # custom axes) has no int8 leg to lower — fall back exact.
        eff_plan = _planner.flat_plan("all_gather")
    full, new_res = _plan_compiler.lower_all_gather(
        eff_plan, shard, residual=residual,
        block=_quant_block_size(block), axes=axes_t, world=world,
        rank=lax.axis_index(axes_t))
    return full if residual is None else (full, new_res)


def _eager_reduce_scatter(tensor, residual, op: ReduceOp,
                          prescale_factor: float, postscale_factor: float,
                          name: Optional[str]):
    """Host-path reduce_scatter over the process world: native allreduce
    then the local rank-major slice (exact wire; the byte savings and the
    quantized leg are compiled-path features)."""
    ctrl, world = _eager_ctx()
    x = _scale(tensor, prescale_factor)
    if residual is not None:
        x = x + residual.astype(x.dtype)
    if tensor.shape[0] % world:
        raise ValueError(
            f"reduce_scatter buffer of {tensor.shape[0]} elements does "
            f"not divide into {world} shards")
    seg = tensor.shape[0] // world
    if world == 1:
        shard = x
    else:
        red = _eager_allreduce(x, ReduceOp.SUM,
                               _eager_name(name, "reduce_scatter"))
        r = basics.rank()
        shard = red[r * seg:(r + 1) * seg]
    shard = _rs_postscale(shard, op, world, postscale_factor)
    if residual is None:
        return shard
    return shard, jnp.zeros_like(residual)


def _eager_shard_all_gather(shard, residual, name: Optional[str]):
    """Host-path all_gather of flat shards (native allgather concatenates
    in rank order, which IS the rank-major layout)."""
    ctrl, world = _eager_ctx()
    x = shard
    new_res = None
    if residual is not None:
        x = x + residual.astype(x.dtype)
        new_res = jnp.zeros_like(residual)
    if world == 1:
        full = x
    else:
        full = _eager_allgather(x, _eager_name(name, "shard_all_gather"))
    return full if residual is None else (full, new_res)


# ---------------------------------------------------------------------------
# Overlap stream entry points (docs/overlap.md).
#
# One fused bucket per call, issued in the reverse-layer stream schedule
# (ops/fusion.py stream_order) so buckets whose leaves finish early in
# backprop launch first and XLA's latency-hiding scheduler can run them
# under the still-executing backward. The wrappers change NO numerics —
# they bracket the exact same collective with trace-time bookkeeping:
# per-bucket OVERLAP:* timeline spans and WireStats.overlap_bytes (the
# numerator of WireStats.hidden_fraction). The bracket itself
# (plan/accounting.py overlap_stream) lives with the plan compiler, so
# any plan-compiled collective is instrumented identically.
# ---------------------------------------------------------------------------

_overlap_stream = _accounting.overlap_stream


def allreduce_stream(tensor, residual=None, *, bucket_id=0, **kwargs):
    """Per-bucket streaming allreduce: :func:`allreduce` (or, with
    ``residual``, :func:`quantized_allreduce`) bracketed with
    ``OVERLAP:ALLREDUCE`` bookkeeping. Bit-identical to the wrapped call —
    the overlap comes from WHERE the scheduler (ops/fusion.py) issues it,
    not from different math. Returns what the wrapped op returns
    (``out``, or ``(out, new_residual)`` when ``residual`` is given)."""
    with _overlap_stream("ALLREDUCE", bucket_id):
        if residual is not None:
            return quantized_allreduce(tensor, residual, **kwargs)
        return allreduce(tensor, **kwargs)


def reduce_scatter_stream(tensor, residual=None, *, bucket_id=0, **kwargs):
    """Per-bucket streaming reduce-scatter (the ZeRO gradient wire under
    the overlap schedule): :func:`reduce_scatter` bracketed with
    ``OVERLAP:REDUCE_SCATTER`` bookkeeping; same contract."""
    with _overlap_stream("REDUCE_SCATTER", bucket_id):
        return reduce_scatter(tensor, residual, **kwargs)


def all_gather_stream(shard, residual=None, *, bucket_id=0, **kwargs):
    """Per-bucket streaming all-gather (the ZeRO update broadcast under
    the overlap schedule): :func:`all_gather` bracketed with
    ``OVERLAP:ALL_GATHER`` bookkeeping; same contract."""
    with _overlap_stream("ALL_GATHER", bucket_id):
        return all_gather(shard, residual, **kwargs)


def _reduce_replicated(x, op: ReduceOp, axes: Tuple[str, ...],
                       presummed: bool):
    """Allreduce semantics for an input that is provably identical on every
    rank (VMA-invariant) — no collective needed.

    Two interpretations exist and the caller picks via ``presummed``:

    * ``presummed=False`` (direct ``hvd.allreduce`` calls): every rank holds
      the same value, so Sum → N·x, Average/Min/Max → x, Product → x^N —
      exactly what the wire collective would return on equal inputs.
    * ``presummed=True`` (gradient paths: DistributedOptimizer, tape): under
      ``jax.shard_map``, autodiff *auto-psums* gradients of replicated
      parameters, so an invariant gradient is already the cross-rank SUM of
      local gradients. Horovod-Average then only needs the ÷N; Horovod-Sum
      is the identity. Without this, wrapping a plain ``jax.grad`` step in
      DistributedOptimizer would double-count by a factor of N.
    """
    n = _world_size(axes)
    if presummed:
        if op in (ReduceOp.SUM, ReduceOp.ADASUM):
            return x
        if op == ReduceOp.AVERAGE:
            if jnp.issubdtype(x.dtype, jnp.integer):
                return x // n
            return x / jnp.asarray(n, dtype=x.dtype)
        raise ValueError(
            f"op {op} is not meaningful for pre-reduced gradients")
    if op == ReduceOp.SUM:
        if jnp.issubdtype(x.dtype, jnp.integer):
            return x * n
        return x * jnp.asarray(n, dtype=x.dtype)
    if op in (ReduceOp.AVERAGE, ReduceOp.MIN, ReduceOp.MAX, ReduceOp.ADASUM):
        return x  # equal contributions: avg/min/max/adasum are the identity
    if op == ReduceOp.PRODUCT:
        return x ** n
    raise ValueError(f"unsupported reduce op {op}")


def _reduce_in_jit(x, op: ReduceOp, axes: Tuple[str, ...],
                   hierarchical: bool, plan=None):
    if op in (ReduceOp.AVERAGE, ReduceOp.SUM, ReduceOp.ADASUM):
        eff_plan = _resolve_plan(
            plan, lambda: _planner.derive_allreduce(
                levels=_planner.levels_of(axes), quantized=False,
                hierarchical=bool(hierarchical)))
        red = _plan_compiler.lower_psum(eff_plan, x, axes)
        if op == ReduceOp.AVERAGE:
            n = _world_size(axes)
            if jnp.issubdtype(x.dtype, jnp.integer):
                red = red // n
            else:
                red = red / jnp.asarray(n, dtype=red.dtype)
        return red
    if op == ReduceOp.MIN:
        return lax.pmin(x, axes)
    if op == ReduceOp.MAX:
        return lax.pmax(x, axes)
    if op == ReduceOp.PRODUCT:
        # XLA has no pprod; exp/log is lossy, so gather + local reduce. The
        # closing pmax over identical values re-establishes replication for
        # the sharding checker at negligible extra cost.
        g = lax.all_gather(x, axes, axis=0, tiled=False)
        return lax.pmax(jnp.prod(g, axis=0), axes)
    raise ValueError(f"unsupported reduce op {op}")


def _resolve_quantized(quantized: Optional[bool], compression) -> bool:
    """Per-call arg > quantized compressor > HOROVOD_QUANTIZED_ALLREDUCE."""
    if quantized is not None:
        return bool(quantized)
    if getattr(compression, "is_quantized", False):
        return True
    return basics.is_initialized() and basics.config().quantized_allreduce


def allreduce(
    tensor,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    compression=Compression.none,
    name: Optional[str] = None,
    axes=None,
    hierarchical: Optional[bool] = None,
    quantized: Optional[bool] = None,
    block: Optional[int] = None,
    plan=None,
    _presummed: bool = False,
):
    """Allreduce ``tensor`` across all ranks.

    Reference: hvd.allreduce (tensorflow/__init__.py:53-153,
    torch/mpi_ops.py:163-228). ``op=Average`` divides the sum by world size;
    ``op=Adasum`` uses the adaptive-summation reduction (see ops/adasum.py).
    ``compression`` casts to a 16-bit wire format around the reduction
    (prefer ``Compression.bf16`` on TPU).

    ``quantized`` (default: ``HOROVOD_QUANTIZED_ALLREDUCE``, or implied by
    ``compression=Compression.int8``) sends blockwise-scaled int8 on the
    DCN hop of the hierarchical reduce-scatter/all-gather decomposition —
    the ``[ici.rs > dcn.rs[int8] > dcn.ag[int8] > ici.ag]`` wire plan
    (plan/compiler.py lower_quantized_allreduce); ICI legs keep the
    payload dtype. For error-feedback accumulation use
    :func:`quantized_allreduce`. With the knob off (the default) this
    path is bit-identical to the unquantized implementation. ``block``
    overrides the ``HOROVOD_QUANT_BLOCK`` scale-block size for this call
    (the autotuner threads its tuned value through here).

    ``plan`` (a validated :class:`horovod_tpu.plan.WirePlan` for the
    ``allreduce`` collective) overrides the knob-derived leg composition
    outright; the ``hierarchical``/``quantized`` booleans remain as
    aliases that derive the same plans (docs/wire-plan.md).

    If ``tensor`` is provably replicated across the requested mesh axes
    (VMA-invariant), no collective is emitted — see
    :func:`_reduce_replicated`. ``_presummed`` is set by the gradient paths
    (optimizer/tape) to mark that an invariant input is an autodiff-summed
    gradient rather than an equal per-rank contribution.
    """
    out, _ = _allreduce_impl(
        tensor, op=op, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor, compression=compression,
        name=name, axes=axes, hierarchical=hierarchical,
        quantized=quantized, residual=None, block=block, plan=plan,
        _presummed=_presummed)
    return out


def quantized_allreduce(
    tensor,
    residual=None,
    *,
    op: ReduceOp = ReduceOp.AVERAGE,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    compression=Compression.none,
    name: Optional[str] = None,
    axes=None,
    block: Optional[int] = None,
    plan=None,
):
    """Quantized allreduce with explicit error-feedback state.

    Returns ``(reduced, new_residual)``. ``residual`` is the error-feedback
    accumulator from the previous step (same shape as ``tensor``; pass
    zeros initially): it is added to the payload before the wire and the
    returned residual carries this rank's quantization error into the next
    step, which keeps SGD/Adam convergence at full-precision quality while
    the wire moves ~4x fewer DCN bytes. With ``residual=None`` the error is
    dropped (stateless quantization) and the second return value is None.

    The residual lives in the *transmitted* space — post ``prescale``, post
    ``compression`` cast, pre reduction — so keep those settings constant
    across steps. On exact paths (no cross axis, non-shardable size, eager
    world of one) the residual is still consumed and returns as zeros.
    """
    return _allreduce_impl(
        tensor, op=op, prescale_factor=prescale_factor,
        postscale_factor=postscale_factor, compression=compression,
        name=name, axes=axes, hierarchical=None, quantized=True,
        residual=residual, block=block, plan=plan, _presummed=False)


def _allreduce_impl(
    tensor,
    *,
    op: ReduceOp,
    prescale_factor: float,
    postscale_factor: float,
    compression,
    name: Optional[str],
    axes,
    hierarchical: Optional[bool],
    quantized: Optional[bool],
    residual,
    block: Optional[int] = None,
    plan=None,
    _presummed: bool = False,
):
    tensor = jnp.asarray(tensor)
    axes_t = _resolve_axes(axes)
    if plan is not None:
        plan = plan.validate()
        if quantized is None:
            # Pod-only int8 legs (the quantized pod hop) lower through
            # the tree ladder, not the 2-level DCN-quantized path.
            quantized = plan.is_dcn_quantized
        if hierarchical is None:
            hierarchical = plan.is_tree and not plan.is_dcn_quantized
        if block is None:
            block = plan.quant_block
    quantized = _resolve_quantized(quantized, compression)
    # Quantization is defined for float sum/average reductions only; other
    # ops (min/max/product/adasum) always ride the exact wire.
    quantized = (quantized and jnp.issubdtype(tensor.dtype, jnp.floating)
                 and op in (ReduceOp.SUM, ReduceOp.AVERAGE))
    if op == ReduceOp.ADASUM and not (
            axes_t and _is_replicated(tensor, axes_t)):
        from . import adasum as _adasum

        return _adasum.adasum_allreduce(
            tensor, axes=axes, prescale_factor=prescale_factor,
            postscale_factor=postscale_factor,
            compression=compression), residual

    tensor = _scale(tensor, prescale_factor)
    # A quantized compressor is not a wire cast: the int8 layout happens
    # inside the collective (real path) or as a local fake-quant round trip
    # (fallback paths) — never through compress() on the real path, where
    # it would double-quantize.
    real_quant_cast = getattr(compression, "is_quantized", False)
    compressed, ctx = ((tensor, None) if real_quant_cast
                       else compression.compress(tensor))
    new_residual = residual
    if axes_t:
        if _is_replicated(compressed, axes_t):
            # No wire, no quantization error; the residual passes through
            # untouched (it is zero on this path by construction).
            red = _reduce_replicated(compressed, op, axes_t, _presummed)
        else:
            # Partially replicated (varying on a strict subset of the
            # requested axes, e.g. a TP-invariant loss allreduced over the
            # full DPxTP mesh): pvary the invariant axes so the collective
            # type-checks — each replicated copy then contributes, exactly
            # the wire semantics of equal inputs on those ranks.
            missing = tuple(sorted(set(axes_t) - _vma(compressed)))
            if missing and _vma(compressed):
                compressed = _pvary(compressed, missing)
            if (quantized and set(axes_t) == set(HVD_AXES)
                    and op in (ReduceOp.SUM, ReduceOp.AVERAGE)):
                eff_plan = _resolve_plan(
                    plan if (plan is not None and plan.is_dcn_quantized)
                    else None,
                    lambda: _planner.quantized_allreduce_plan(
                        block=block,
                        error_feedback=residual is not None))
                red, new_residual = \
                    _plan_compiler.lower_quantized_allreduce(
                        eff_plan, compressed, residual=residual,
                        block=_quant_block_size(block))
                if op == ReduceOp.AVERAGE:
                    n = _world_size(axes_t)
                    red = red / jnp.asarray(n, dtype=red.dtype)
            else:
                if quantized and real_quant_cast:
                    # Quantization requested but the reduction doesn't
                    # decompose over (cross, local): fake-quant the
                    # contribution so numerics still match the quantized
                    # semantics; the wire stays full-width.
                    if residual is not None:
                        compressed = compressed + residual.astype(
                            compressed.dtype)
                    wire = _compression.fake_quantize_int8(
                        compressed, _quant_block_size(block))
                    if residual is not None:
                        new_residual = (compressed - wire).astype(
                            residual.dtype)
                    compressed = wire
                elif residual is not None:
                    # Exact wire: consume the residual, nothing left over.
                    compressed = compressed + residual.astype(
                        compressed.dtype)
                    new_residual = jnp.zeros_like(residual)
                if hierarchical is None:
                    hierarchical = (
                        basics.is_initialized()
                        and basics.config().hierarchical_allreduce
                    )
                exact_plan = (plan if plan is not None
                              and plan.collective == "allreduce"
                              and not plan.is_dcn_quantized else None)
                red = _reduce_in_jit(compressed, op, axes_t,
                                     bool(hierarchical), plan=exact_plan)
    else:
        # hierarchical=False matches what the eager data plane does (flat
        # rings), so only an explicit True is an unsatisfiable request —
        # autotuner TunedParams overrides legitimately pass False here.
        if hierarchical:
            raise ValueError(
                "allreduce(hierarchical=True) is only supported in-jit; "
                "set HOROVOD_HIERARCHICAL_ALLREDUCE for the eager path")
        if quantized:
            # Eager path: the native core reduces full-width dtypes, so the
            # quantization is applied as a local fake-quant of this rank's
            # contribution — identical numerics to the compiled hop-2
            # contribution, full-width bytes (the byte savings are a
            # compiled-path feature).
            if residual is not None:
                compressed = compressed + residual.astype(compressed.dtype)
            wire = _compression.fake_quantize_int8(
                compressed, _quant_block_size(block))
            if residual is not None:
                new_residual = (compressed - wire).astype(residual.dtype)
            compressed = wire
        red = _eager_allreduce(compressed, op, name)
    red = compression.decompress(red, ctx)
    return _scale(red, postscale_factor), new_residual


def grouped_allreduce(tensors: Sequence, **kwargs):
    """Allreduce a list of tensors as one logical group (reference:
    grouped allreduce added for torch in mpi_ops.py; the fusion analogue).

    Under jit, XLA fuses the per-tensor psums; for stronger guarantees use
    :mod:`horovod_tpu.ops.fusion` which packs one flat buffer per dtype.
    On the eager path the group is packed host-side into one flat buffer
    per wire dtype and enqueued as ONE native collective per buffer — one
    controller negotiation per group instead of N (reference grouped-op
    semantics; like the reference's fusion buffer, Adasum then treats the
    packed buffer as a single logical vector)."""
    tensors = [jnp.asarray(t) for t in tensors]
    axes_t = _resolve_axes(kwargs.get("axes"))
    if axes_t or not tensors:
        return [allreduce(t, **kwargs) for t in tensors]
    return _eager_grouped_allreduce(tensors, **kwargs)


def _eager_grouped_allreduce(tensors, *, name: Optional[str] = None,
                             op: ReduceOp = ReduceOp.AVERAGE,
                             prescale_factor: float = 1.0,
                             postscale_factor: float = 1.0,
                             compression=None, axes=None,
                             hierarchical: Optional[bool] = None):
    if hierarchical:
        raise ValueError(
            "allreduce(hierarchical=True) is only supported in-jit; set "
            "HOROVOD_HIERARCHICAL_ALLREDUCE for the eager path")
    compression = compression or Compression.none
    ctrl, world = _eager_ctx()

    wires, ctxs = [], []
    for t in tensors:
        w, c = compression.compress(_scale(t, prescale_factor))
        wires.append(w)
        ctxs.append(c)
    if world == 1:
        return [_scale(compression.decompress(w, c), postscale_factor)
                for w, c in zip(wires, ctxs)]

    opmap = {ReduceOp.SUM: ctrl.SUM, ReduceOp.AVERAGE: ctrl.SUM,
             ReduceOp.MIN: ctrl.MIN, ReduceOp.MAX: ctrl.MAX,
             ReduceOp.PRODUCT: ctrl.PRODUCT, ReduceOp.ADASUM: ctrl.ADASUM}
    post = 1.0 / world if op == ReduceOp.AVERAGE else 1.0
    gname = _eager_name(name, "grouped_allreduce")

    # One flat buffer (and one negotiation) per wire dtype, in first-seen
    # order; results unpack back to the original shapes/positions.
    by_dtype: dict = {}
    for i, w in enumerate(wires):
        by_dtype.setdefault(jnp.dtype(w.dtype), []).append(i)
    out: list = [None] * len(tensors)
    handles = []
    for dt, idxs in by_dtype.items():
        flat = np.concatenate(
            [np.asarray(_to_numpy(wires[i])).ravel() for i in idxs])
        handles.append((dt, idxs, ctrl.allreduce_async(
            flat, f"{gname}.{dt.name}", op=opmap[op], postscale=post)))
    for dt, idxs, h in handles:
        buf = h.wait()
        offset = 0
        for i in idxs:
            n = wires[i].size
            piece = jnp.asarray(
                buf[offset:offset + n]).reshape(wires[i].shape)
            offset += n
            out[i] = _scale(compression.decompress(piece, ctxs[i]),
                            postscale_factor)
    return out


def allgather(tensor, *, name: Optional[str] = None, axes=None,
              hierarchical: Optional[bool] = None):
    """Gather tensors from all ranks, concatenated along dim 0.

    Reference: hvd.allgather (torch/mpi_ops.py:230-291). The reference
    supports ragged first dims via the coordinator's size exchange; under XLA
    shapes are static, so in-jit all shards must share a shape — ragged
    gathers belong on the eager path (allgather_object in
    parallel/functions.py covers the reference's ragged use cases).

    ``hierarchical`` (default: the ``HOROVOD_HIERARCHICAL_ALLGATHER`` knob,
    reference operations.cc:463-472 / MPIHierarchicalAllgather,
    mpi_operations.cc:180-280) decomposes the world gather into an intra-host
    gather over ICI followed by a cross-host gather of per-host superblocks
    over DCN. Host-major rank packing makes the two orderings identical, so
    numerics match the flat gather exactly. The eager path honors the same
    knob inside the native core (cc/src/collectives.cc
    HierarchicalAllgatherV).
    """
    tensor = jnp.asarray(tensor)
    axes_t = _resolve_axes(axes)
    if axes_t:
        if _is_replicated(tensor, axes_t):
            # Equal contribution from every rank: the gather is a local tile.
            reps = (_world_size(axes_t),) + (1,) * (tensor.ndim - 1)
            return jnp.tile(tensor, reps)
        if hierarchical is None:
            hierarchical = (basics.is_initialized()
                            and basics.config().hierarchical_allgather)
        # Exact tuple match: the two-stage decomposition reproduces the
        # cross-major concatenation of axes=(cross, local); a reversed axes
        # tuple means local-major order and must stay on the flat path.
        if hierarchical and axes_t == HVD_AXES:
            # Local (ICI) gather first, then cross (DCN) gather of the
            # per-host superblocks; rank order = (cross, local) lex order =
            # the flat gather's order.
            local = lax.all_gather(tensor, LOCAL_AXIS, axis=0, tiled=True)
            return lax.all_gather(local, CROSS_AXIS, axis=0, tiled=True)
        return lax.all_gather(tensor, axes_t, axis=0, tiled=True)
    if hierarchical is not None:
        # The eager data plane takes its hierarchical decision from the
        # process-wide HOROVOD_HIERARCHICAL_ALLGATHER knob inside the
        # native core; a per-call override cannot be honored there.
        raise ValueError(
            "allgather(hierarchical=...) is only supported in-jit; set "
            "HOROVOD_HIERARCHICAL_ALLGATHER for the eager path")
    return _eager_allgather(tensor, name)


def broadcast(tensor, root_rank: int = 0, *, name: Optional[str] = None,
              axes=None):
    """Broadcast ``tensor`` from ``root_rank`` to all ranks.

    Reference: hvd.broadcast (torch/mpi_ops.py:293-344). Lowers to a masked
    ``psum`` on every platform (one collective, no size× gather blow-up):
    every rank contributes zeros except the root. See the in-body comment
    for why the per-platform CollectiveBroadcast lowering was dropped.
    """
    tensor = jnp.asarray(tensor)
    axes_t = _resolve_axes(axes)
    if not axes_t:
        return _eager_broadcast(tensor, root_rank, name)
    if _is_replicated(tensor, axes_t):
        return tensor  # already equal everywhere: nothing to move
    wire = tensor
    bool_in = wire.dtype == jnp.bool_
    if bool_in:
        wire = wire.astype(jnp.uint8)

    # Masked psum on every platform: each rank contributes zeros except the
    # root, one collective, no size-x gather blow-up — and the result is
    # replicated BY CONSTRUCTION in JAX's VMA model. The per-platform
    # CollectiveBroadcast lowering (lax.pbroadcast) was dropped: its result
    # stays statically device-varying under jax 0.9, so selecting between
    # the two via lax.platform_dependent builds a switch with VMA-divergent
    # branches, which fails abstract evaluation under jit for any
    # device-varying operand (XLA on TPU still lowers the masked AllReduce
    # onto ICI).
    # Select, not multiply: NaN/Inf in a non-root payload (e.g. an elastic
    # rejoin whose own params diverged) would survive `wire * 0` and poison
    # the sum on every rank.
    is_root = lax.axis_index(axes_t) == root_rank
    out = lax.psum(jnp.where(is_root, wire, jnp.zeros_like(wire)), axes_t)
    if bool_in:
        out = out.astype(jnp.bool_)
    return out


def alltoall(tensor, splits=None, *, name: Optional[str] = None, axes=None):
    """Scatter slices of ``tensor`` along dim 0 to every rank and gather the
    received slices, concatenated along dim 0.

    Reference: hvd.alltoall (operations.cc:1031-1092,
    collective_operations.h:192-257). Returns ``(output, received_splits)``
    for parity with the reference's uneven-split API. In-jit, XLA requires
    static shapes, so only the even-split case (``splits=None`` with dim 0
    divisible by world size, or all-equal splits) is compiled; uneven splits
    are an eager/controller feature.
    """
    tensor = jnp.asarray(tensor)
    axes_t = _resolve_axes(axes)
    if not axes_t:
        out, recv = _eager_alltoall(tensor, splits, name)
        if recv is None:  # world of one
            n = tensor.shape[0] if tensor.ndim else 0
            recv = jnp.asarray([n], dtype=jnp.int32)
        return out, recv
    n = _world_size(axes_t)
    if splits is not None:
        s = np.asarray(splits)
        if not (s.ndim == 1 and len(s) == n and np.all(s == s[0])):
            raise NotImplementedError(
                "uneven alltoall splits require static shapes under XLA: "
                "use hvd.alltoall_ragged(tensor, splits, capacity=...) — "
                "the compiled static-capacity protocol for the reference's "
                "uneven path (operations.cc:1031-1092) — or equal splits "
                "here")
    if tensor.shape[0] % n != 0:
        raise ValueError(
            f"alltoall dim 0 ({tensor.shape[0]}) must be divisible by the "
            f"world size ({n})")
    if _is_replicated(tensor, axes_t):
        # Equal input on every rank: rank r receives its own block from each
        # sender — a local slice + tile, no wire traffic.
        blk = tensor.shape[0] // n
        mine = lax.dynamic_slice_in_dim(
            tensor, lax.axis_index(axes_t) * blk, blk, 0)
        out = jnp.tile(mine, (n,) + (1,) * (tensor.ndim - 1))
    else:
        out = lax.all_to_all(tensor, axes_t, split_axis=0, concat_axis=0,
                             tiled=True)
    recv = jnp.full((n,), tensor.shape[0] // n, dtype=jnp.int32)
    return out, recv


def alltoall_ragged(tensor, splits, *, capacity: int,
                    name: Optional[str] = None, axes=None,
                    recv_splits=None):
    """Uneven alltoall that compiles under ``jit`` via a static-capacity
    padded exchange.

    The reference negotiates per-pair receive counts at runtime and
    allocates an exactly-sized output (operations.cc:1031-1092;
    ``AlltoallGetRecvSplits``, controller.h:145).  XLA requires static
    shapes, so the TPU-native protocol trades exactness for a static
    per-pair bound:

    1. each pair block (the rows destined for rank ``i``) is padded to
       ``capacity`` rows into an ``[n, capacity, ...]`` send buffer
       (padding rows are zeroed so no garbage rides the wire);
    2. the per-pair counts ride a tiny int32 ``lax.all_to_all`` — the
       compiled analogue of the controller's recv-splits negotiation;
    3. one tiled ``lax.all_to_all`` moves the padded payload over ICI;
    4. received blocks are compacted to the front of the output with a
       drop-mode scatter on the padding rows.

    Args:
      tensor: ``[T, ...]`` laid out destination-major — rows
        ``[sum(splits[:i]), sum(splits[:i+1]))`` go to rank ``i``.
      splits: int32 ``[n]``; may be a *traced* array (dynamic values,
        static shape).  Entries are clamped to ``capacity``: rows beyond
        it are dropped at the sender and the clamped count is what the
        receiver sees in ``recv_splits`` (the Switch-MoE overflow
        contract; pick ``capacity >= max(splits)`` for losslessness).
      capacity: static per-pair row bound (python int).
      recv_splits: optional precomputed int32 ``[n]`` of incoming
        per-pair counts (e.g. from a prior ``alltoall_ragged`` with the
        same splits this step) — skips the counts negotiation
        collective.  Values are clamped to ``capacity``; they must match
        what peers actually send or rows will be mis-compacted.

    Returns ``(out, recv_splits)`` where ``out`` is
    ``[n * capacity, ...]`` with the received blocks compacted to the
    front (rows past ``sum(recv_splits)`` are zeros) and ``recv_splits``
    is int32 ``[n]`` — ``recv_splits[i]`` rows arrived from rank ``i``.

    Outside shard_map the same contract runs over the process world
    through the native controller's uneven path (clamp + compact on the
    host, then pad the exact-sized result up to the capacity layout).
    """
    tensor = jnp.asarray(tensor)
    if tensor.ndim == 0:
        raise ValueError("alltoall_ragged requires a tensor with ndim >= 1")
    capacity = int(capacity)
    if capacity < 1:
        raise ValueError(f"capacity must be >= 1, got {capacity}")
    axes_t = _resolve_axes(axes)

    if not axes_t:
        return _eager_alltoall_ragged(tensor, splits, capacity, name)

    n = _world_size(axes_t)
    if not isinstance(splits, jax.core.Tracer):
        if np.any(np.asarray(splits) < 0):
            raise ValueError(f"splits must be non-negative, got {splits}")
    splits = jnp.maximum(jnp.asarray(splits, dtype=jnp.int32), 0)
    if splits.shape != (n,):
        raise ValueError(
            f"splits must have shape ({n},) for a world of {n}, got "
            f"{splits.shape}")
    sp = jnp.minimum(splits, capacity)

    T = tensor.shape[0]
    rest = tensor.shape[1:]
    j = jnp.arange(capacity, dtype=jnp.int32)
    valid_send = j[None, :] < sp[:, None]                  # [n, capacity]
    if T == 0:
        send = jnp.zeros((n, capacity) + rest, tensor.dtype)
    else:
        # Block offsets follow the CALLER's layout (the original splits,
        # overflow rows included); only the first sp[i] rows of each
        # block are picked up.
        offs = jnp.cumsum(splits) - splits
        idx = jnp.clip(offs[:, None] + j[None, :], 0, T - 1)
        send = jnp.take(tensor, idx.reshape(-1), axis=0).reshape(
            (n, capacity) + rest)
        mask = valid_send.reshape((n, capacity) + (1,) * len(rest))
        send = jnp.where(mask, send, jnp.zeros((), tensor.dtype))

    if n > 1:
        # pvary replicated operands: all_to_all needs device-varying
        # inputs under jax 0.9's VMA model.
        if recv_splits is None:
            recv_splits = lax.all_to_all(
                pvary_missing(sp, axes_t), axes_t, split_axis=0,
                concat_axis=0, tiled=True)
        else:
            recv_splits = jnp.clip(
                jnp.asarray(recv_splits, jnp.int32), 0, capacity)
        recv = lax.all_to_all(
            pvary_missing(send, axes_t), axes_t, split_axis=0,
            concat_axis=0, tiled=True)
    else:
        recv_splits = sp if recv_splits is None else jnp.clip(
            jnp.asarray(recv_splits, jnp.int32), 0, capacity)
        recv = send

    # Compact: scatter valid rows to the front, padding rows off the end
    # (mode="drop" discards out-of-bounds destinations).
    roffs = jnp.cumsum(recv_splits) - recv_splits
    valid_recv = j[None, :] < recv_splits[:, None]
    dest = jnp.where(valid_recv, roffs[:, None] + j[None, :], n * capacity)
    flat = recv.reshape((n * capacity,) + rest)
    out = jnp.zeros_like(flat).at[dest.reshape(-1)].set(flat, mode="drop")
    return out, recv_splits


def _eager_alltoall_ragged(tensor, splits, capacity: int,
                           name: Optional[str] = None):
    """Host-path ``alltoall_ragged``: same padded-output contract, data
    moves through the native controller's uneven alltoall."""
    world = _eager_world()
    splits_np = np.asarray(splits, dtype=np.int64)
    if splits_np.shape != (world,):
        raise ValueError(
            f"splits must have shape ({world},) for a process world of "
            f"{world}, got {splits_np.shape}")
    if np.any(splits_np < 0):
        raise ValueError(f"splits must be non-negative, got {splits_np}")
    sp = np.minimum(splits_np, capacity)
    offs = np.cumsum(splits_np) - splits_np
    keep = np.concatenate(
        [offs[i] + np.arange(sp[i]) for i in range(world)]
    ).astype(np.int64) if world else np.zeros((0,), np.int64)
    compacted = jnp.take(tensor, keep, axis=0)
    out, recv = _eager_alltoall(compacted, sp.astype(np.int32), name)
    if recv is None:  # world of one: everything loops back locally
        recv = jnp.asarray(sp, dtype=jnp.int32)
    total = world * capacity
    pad = total - out.shape[0]
    if pad:
        out = jnp.concatenate(
            [out, jnp.zeros((pad,) + out.shape[1:], out.dtype)], axis=0)
    return out, jnp.asarray(recv, dtype=jnp.int32)


def join() -> int:
    """Signal that this process has exhausted its data (reference: JoinOp,
    collective_operations.cc:256-264; torch/mpi_ops.py:646).

    In the reference, joined ranks contribute zeros to subsequent collectives
    until all ranks join; the call returns the rank of the last rank to join.
    Single-controller SPMD has no per-rank data exhaustion inside the
    compiled step — handle ragged data by padding/masking the global batch.
    Eagerly the native core implements the full joined-rank protocol
    (identity contributions until all ranks join).
    """
    s = basics._require_init()
    s.joined = True
    ctrl, world = _eager_ctx()
    if world == 1:
        return basics.rank()
    h = ctrl.join_async()
    h.wait()
    return h.join_result()


def barrier() -> None:
    """Host-side barrier over processes (reference: controller Barrier,
    controller.h:145)."""
    ctrl, world = _eager_ctx()
    if ctrl is not None and world > 1:
        ctrl.barrier()


# ---------------------------------------------------------------------------
# Eager (host) path — process-world collectives through the native core.
#
# One participant per worker process (the reference's process model). Data
# crosses process boundaries through the C++ controller + TCP data plane
# (cc/): enqueue → rank-0 negotiation → fused ring collective → in-place
# result. Under a single process they reduce over a world of one, which
# still applies op semantics exactly (average of one tensor is the tensor).
# ---------------------------------------------------------------------------

_eager_name_lock = threading.Lock()
_eager_name_counter = [0]


def _eager_name(name: Optional[str], kind: str) -> str:
    """Stable auto-name: processes stay aligned because collectives are
    issued in identical program order on every rank (the same contract the
    reference's auto-generated op names rely on)."""
    if name is not None:
        return name
    with _eager_name_lock:
        n = _eager_name_counter[0]
        _eager_name_counter[0] += 1
    return f"eager.{kind}.{n}"


def _eager_world() -> int:
    s = basics._require_init()
    return s.controller.size() if s.controller is not None else s.process_count


def _controller():
    return basics._require_init().controller


def _eager_ctx():
    """(controller, world) for an eager collective. A multi-process job
    whose controller is missing (HOROVOD_CONTROLLER=none, or HOROVOD_SIZE
    unset under jax.distributed) must fail loudly: silently skipping the
    collective would let ranks diverge unreduced."""
    # Chaos gate for the eager path: 'crash' is a worker dying
    # mid-collective (peers see HorovodInternalError and the elastic
    # restore path engages); 'stall' is a straggler rank.
    from ..chaos import injector as _chaos

    _chaos.inject("collective.eager")
    s = basics._require_init()
    ctrl = s.controller
    world = ctrl.size() if ctrl is not None else s.process_count
    if ctrl is None and world > 1:
        raise RuntimeError(
            "eager collective in a multi-process job but the native "
            "controller is disabled (HOROVOD_CONTROLLER=none or launcher "
            "env contract missing) — cannot communicate between processes")
    return ctrl, world


def _reset_eager_state() -> None:
    """Called by basics.shutdown(): auto-generated collective names restart
    from 0 so ranks stay aligned across an elastic shutdown/init cycle."""
    with _eager_name_lock:
        _eager_name_counter[0] = 0
    with _handles._lock:
        _handles._results.clear()
        _handles._names.clear()
        _handles._next = 0


def _to_numpy(tensor) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(tensor))


@contextlib.contextmanager
def _eager_instrumented(kind: str, name: str):
    """Observability bracket for one eager (host-path) collective: the
    StallInspector tracks it in flight (so a straggler rank — or a chaos
    ``stall`` injected in ``_eager_ctx`` — surfaces as a rank-attributed
    ``STALL:*`` warning, docs/observability.md), and the wall time of a
    completed op feeds the ``comm.eager.latency_ms`` histogram."""
    from ..monitor import flight as _flight
    from ..monitor import stall as _stall
    from ..monitor import straggler as _straggler

    if _metrics.metrics_enabled():
        _metrics.counter("comm.eager.calls", kind=kind).inc()
    t0 = time.perf_counter()
    with _stall.track(name, kind=kind):
        yield
    ms = (time.perf_counter() - t0) * 1e3
    if _metrics.metrics_enabled():
        _metrics.histogram("comm.eager.latency_ms", kind=kind).observe(ms)
        # Straggler attribution (monitor/straggler.py): eager wall time
        # charges the wire.dcn phase — the process-world data plane is
        # host-to-host TCP, DCN-class wire. A rank whose eager
        # collectives drag (chaos delay, a sick NIC) shows up as a
        # (rank, wire.dcn) outlier after cross-rank aggregation.
        _straggler.record_phase("wire.dcn", ms)
    # The eager path has no timeline event of its own; the flight ring
    # records each completed call so a dump shows the collective trail.
    _flight.instant("FLIGHT:COLLECTIVE", tid="flight",
                    args={"name": name, "kind": kind,
                          "ms": round(ms, 3)})


def _eager_allreduce(tensor, op: ReduceOp, name: Optional[str] = None):
    name = _eager_name(name, "allreduce")
    with _eager_instrumented("allreduce", name):
        ctrl, world = _eager_ctx()
        if world == 1:
            return tensor  # sum/avg/min/max/product over a world of one
        arr = _to_numpy(tensor)
        opmap = {
            ReduceOp.SUM: ctrl.SUM,
            ReduceOp.AVERAGE: ctrl.SUM,
            ReduceOp.MIN: ctrl.MIN,
            ReduceOp.MAX: ctrl.MAX,
            ReduceOp.PRODUCT: ctrl.PRODUCT,
            ReduceOp.ADASUM: ctrl.ADASUM,
        }
        postscale = 1.0 / world if op == ReduceOp.AVERAGE else 1.0
        out = ctrl.allreduce_async(arr, name,
                                   op=opmap[op], postscale=postscale).wait()
        return jnp.asarray(out)


def _eager_allgather(tensor, name: Optional[str] = None):
    name = _eager_name(name, "allgather")
    with _eager_instrumented("allgather", name):
        ctrl, world = _eager_ctx()
        if world == 1:
            return tensor
        out = ctrl.allgather_async(_to_numpy(tensor), name).wait()
        return jnp.asarray(out)


def _eager_broadcast(tensor, root_rank: int, name: Optional[str] = None):
    name = _eager_name(name, "broadcast")
    with _eager_instrumented("broadcast", name):
        ctrl, world = _eager_ctx()
        if world == 1:
            return tensor
        out = ctrl.broadcast_async(_to_numpy(tensor), name,
                                   root=root_rank).wait()
        return jnp.asarray(out)


def _eager_alltoall(tensor, splits, name: Optional[str] = None):
    name = _eager_name(name, "alltoall")
    with _eager_instrumented("alltoall", name):
        ctrl, world = _eager_ctx()
        if world == 1:
            return tensor, None
        sp = None if splits is None else [int(x) for x in np.asarray(splits)]
        h = ctrl.alltoall_async(_to_numpy(tensor), name, splits=sp)
        out = h.wait()
        return jnp.asarray(out), jnp.asarray(h.recv_splits(),
                                             dtype=jnp.int32)


# ---------------------------------------------------------------------------
# Handle-based async API (reference: torch/mpi_ops.py:66-161 — allreduce_async
# returns an int handle; synchronize(handle) blocks; poll(handle) checks).
#
# JAX arrays are asynchronous futures by construction: dispatch returns
# immediately and block_until_ready() is the synchronize. The HandleManager
# preserves the reference contract (including duplicate-name rejection,
# common.h:163) on top of that.
# ---------------------------------------------------------------------------


class _HandleManager:
    """Reference: torch/handle_manager.{h,cc} + the name table in
    TensorQueue (tensor_queue.h:28)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._results = {}
        self._names = set()
        self._next = 0

    def allocate(self, value, name: Optional[str]):
        with self._lock:
            if name is not None:
                if name in self._names:
                    raise DuplicateTensorNameError(
                        f"Tensor name {name!r} already in an in-flight "
                        "collective (reference: DUPLICATE_NAME_ERROR, "
                        "common.h:163)")
                self._names.add(name)
            h = self._next
            self._next += 1
            self._results[h] = (value, name)
            return h

    def poll(self, handle: int) -> bool:
        with self._lock:
            if handle not in self._results:
                # Already synchronized/cleared: completed (the reference's
                # HandleManager reports finished handles as done).
                return True
            value, _ = self._results[handle]
        try:
            return bool(value.is_ready())
        except AttributeError:
            return True

    def wait_and_clear(self, handle: int):
        with self._lock:
            value, name = self._results.pop(handle)
            if name is not None:
                self._names.discard(name)
        return jax.block_until_ready(value)


_handles = _HandleManager()


def allreduce_async(tensor, *, name: Optional[str] = None, **kwargs) -> int:
    """Dispatch an allreduce, returning an integer handle
    (reference: torch/mpi_ops.py:119-127)."""
    return _handles.allocate(allreduce(tensor, name=name, **kwargs), name)


def allgather_async(tensor, *, name: Optional[str] = None, **kwargs) -> int:
    return _handles.allocate(allgather(tensor, name=name, **kwargs), name)


def broadcast_async(tensor, root_rank: int = 0, *,
                    name: Optional[str] = None, **kwargs) -> int:
    return _handles.allocate(
        broadcast(tensor, root_rank, name=name, **kwargs), name)


def alltoall_async(tensor, splits=None, *, name: Optional[str] = None,
                   **kwargs) -> int:
    return _handles.allocate(alltoall(tensor, splits, name=name, **kwargs),
                             name)


def poll(handle: int) -> bool:
    """True when the collective behind ``handle`` has completed
    (reference: torch/mpi_ops.py:88-99)."""
    return _handles.poll(handle)


def synchronize(handle: int):
    """Block until the collective completes and return its result
    (reference: torch/mpi_ops.py:101-127)."""
    return _handles.wait_and_clear(handle)
