"""Tensor fusion: pack many small tensors into few large collectives.

Reference: the 64 MiB fusion buffer (fusion_buffer_manager.{h,cc},
operations.cc:437) plus ``Controller::FuseResponses`` which bins ready
tensors under the threshold with look-ahead across mixed dtypes
(controller.cc:686-809). Fusion is Horovod's single most important
performance feature: it amortizes per-collective launch latency over many
gradients.

TPU-native redesign
-------------------
Under XLA, shapes are static at trace time, so fusion needs no runtime
negotiation at all: we pack the gradient pytree into flat per-dtype buckets
**once, during tracing**, and every compiled step reduces whole buckets. The
response-cache "learned schedule" of the reference (response_cache.cc — the
steady-state fast path) becomes simply the XLA compilation cache: the first
trace fixes the fused schedule, subsequent steps replay it at zero
negotiation cost.

Bucketing mirrors the reference policy: greedy first-fit in tree order,
per-dtype buffers (mixed dtypes can't share one XLA collective), capped at
``HOROVOD_FUSION_THRESHOLD`` bytes, and bucket lengths rounded up to a
multiple of 64 elements so hierarchical reduce-scatter shards evenly
(reference: FUSION_BUFFER_ATOMIC_UNIT, common.h:97; controller.cc:360-378).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..common import basics
from ..common.config import _env_bool
from . import collective_ops as C
from .compression import Compression

# Reference: FUSION_BUFFER_ATOMIC_UNIT = 64 (common.h:97) — keeps fused
# buffers divisible for hierarchical/Adasum sharding.
ATOMIC_UNIT = 64


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fused buffer: which flat leaves it holds and how to unpack them."""

    dtype: Any
    leaf_indices: Tuple[int, ...]
    sizes: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    padded_size: int  # total elements, rounded up to ATOMIC_UNIT


def plan_buckets(
    leaves: Sequence[jax.Array],
    threshold_bytes: Optional[int] = None,
    *,
    shard_multiple: int = 1,
) -> List[Bucket]:
    """Greedy first-fit bucketing in leaf order, one buffer per dtype run.

    Matches the reference's FuseResponses policy (controller.cc:686-809):
    walk tensors in order, open a new buffer when the current one would
    exceed the threshold or the dtype changes (the reference's look-ahead
    skips over mixed dtypes; leaf order here is pytree order, which is
    deterministic, so we simply group by dtype).

    Guarantees the autotuner's warm-start cache key relies on: the plan
    is a pure, deterministic function of (leaf order, shapes, dtypes,
    threshold) — identical pytrees always produce identical plans; a
    single leaf larger than the threshold becomes its own bucket (never
    an error, and never shared — a following small leaf must not ride a
    bucket that already blew past the cap); 0-d and zero-size leaves
    count as one element (the reference's min-1 slot).

    ``shard_multiple`` (the ZeRO-sharding hook) rounds every bucket's
    padded size up to a multiple of ``lcm(ATOMIC_UNIT, shard_multiple)``
    instead of plain ``ATOMIC_UNIT``, so the flat buffer reduce-scatters
    evenly into ``shard_multiple`` per-rank shards (pass the world size).
    It never changes WHICH leaves share a bucket — only the tail padding —
    so plans for different world sizes unpack identically (the elastic
    reshard path relies on this)."""
    if threshold_bytes is None:
        threshold_bytes = (
            basics.config().fusion_threshold_bytes
            if basics.is_initialized()
            else 64 * 1024 * 1024
        )
    unit = int(np.lcm(ATOMIC_UNIT, max(1, int(shard_multiple))))
    by_dtype: dict = {}
    for i, leaf in enumerate(leaves):
        by_dtype.setdefault(_leaf_dtype(leaf), []).append(i)

    buckets: List[Bucket] = []
    for dtype, idxs in by_dtype.items():
        itemsize = jnp.dtype(dtype).itemsize
        cur_idx: List[int] = []
        cur_elems = 0
        max_elems = max(ATOMIC_UNIT, threshold_bytes // itemsize)
        for i in idxs:
            n = int(np.prod(_leaf_shape(leaves[i]), dtype=np.int64)) or 1
            if cur_idx and cur_elems + n > max_elems:
                buckets.append(_close_bucket(dtype, cur_idx, leaves, unit))
                cur_idx, cur_elems = [], 0
            cur_idx.append(i)
            cur_elems += n
            if n > max_elems:
                # Oversized leaf: its own bucket, closed immediately.
                buckets.append(_close_bucket(dtype, cur_idx, leaves, unit))
                cur_idx, cur_elems = [], 0
        if cur_idx:
            buckets.append(_close_bucket(dtype, cur_idx, leaves, unit))
    return buckets


def _leaf_dtype(leaf):
    """Leaf dtype without materializing the value — abstract leaves
    (``jax.ShapeDtypeStruct`` templates, the ZeRO-3 gather path) plan
    identically to concrete arrays."""
    dt = getattr(leaf, "dtype", None)
    return jnp.dtype(dt) if dt is not None else jnp.asarray(leaf).dtype


def _leaf_shape(leaf) -> Tuple[int, ...]:
    s = getattr(leaf, "shape", None)
    return tuple(s) if s is not None else tuple(jnp.shape(leaf))


def _close_bucket(dtype, idxs: List[int], leaves,
                  unit: int = ATOMIC_UNIT) -> Bucket:
    shapes = tuple(_leaf_shape(leaves[i]) for i in idxs)
    sizes = tuple(int(np.prod(s, dtype=np.int64)) or 1 for s in shapes)
    total = sum(sizes)
    padded = ((total + unit - 1) // unit) * unit
    return Bucket(dtype=dtype, leaf_indices=tuple(idxs), sizes=sizes,
                  shapes=shapes, padded_size=padded)


def stream_order(buckets: Sequence[Bucket]) -> Tuple[int, ...]:
    """Reverse-layer bucket issue schedule (docs/overlap.md).

    Backprop produces gradients output-side first: for a forward-ordered
    parameter pytree that means the HIGHEST leaf indices become ready
    earliest. Issuing the bucket holding the highest leaf index first
    aligns collective program order with data readiness, so a streamed
    bucket can launch while the backward of earlier (input-side) layers
    is still running — the compiled-path analogue of Horovod's background
    coordinator starting reductions mid-backprop.

    Only the ISSUE order changes; leaf→bucket assignment comes unchanged
    from :func:`plan_buckets`, so every bucket carries identical contents
    (and, on the quantized wire, identical scale-block boundaries) to the
    in-order schedule — any collective sequence issued this way computes
    bit-identical values. Ties (impossible within one dtype group, since
    leaf indices are unique) break by bucket index for determinism."""
    return tuple(sorted(range(len(buckets)),
                        key=lambda j: (-max(buckets[j].leaf_indices), j)))


def gather_order(buckets: Sequence[Bucket]) -> Tuple[int, ...]:
    """Forward-order bucket issue schedule — :func:`stream_order`'s
    mirror for the ZeRO-3 just-in-time parameter gather (docs/zero.md).

    The forward pass consumes parameters input-side first: for a
    forward-ordered pytree the LOWEST leaf indices are needed earliest.
    Issuing the bucket holding the lowest leaf index first lets the
    latency-hiding scheduler run the gathers of deeper layers' buckets
    under the compute of the layers already gathered — T3's fine-grained
    prologue overlap at bucket granularity. Contents are untouched
    (leaf→bucket assignment comes from :func:`plan_buckets`), so any
    issue order computes bit-identical values; ties break by bucket
    index for determinism."""
    return tuple(sorted(range(len(buckets)),
                        key=lambda j: (min(buckets[j].leaf_indices), j)))


def _resolve_overlap(overlap, num_comm_streams, tuned_params):
    """(overlap_on, streams): explicit args > TunedParams override >
    HOROVOD_OVERLAP / HOROVOD_NUM_COMM_STREAMS config."""
    if tuned_params is not None:
        if overlap is None:
            overlap = tuned_params.overlap
        if num_comm_streams is None:
            num_comm_streams = tuned_params.num_comm_streams
    if overlap is None:
        overlap = (basics.config().overlap if basics.is_initialized()
                   else _env_bool("HOROVOD_OVERLAP", False))
    if num_comm_streams is None:
        num_comm_streams = (basics.config().num_comm_streams
                            if basics.is_initialized() else 1)
    return bool(overlap), max(1, int(num_comm_streams))


def pack(bucket: Bucket, leaves: Sequence[jax.Array]) -> jax.Array:
    """Concatenate the bucket's leaves into one flat padded buffer (the
    MemcpyInFusionBuffer analogue, collective_operations.cc:34-59 — here a
    traced concatenate that XLA fuses). A zero-size leaf still owns its
    min-1 slot in the plan (plan_buckets), so it packs as slot padding."""
    flat = []
    with jax.named_scope("hvd.bucket_pack"):
        for i, size in zip(bucket.leaf_indices, bucket.sizes):
            v = jnp.ravel(jnp.asarray(leaves[i]))
            if v.shape[0] < size:  # zero-size leaf: fill its min-1 slot
                v = jnp.zeros((size,), dtype=v.dtype)
            flat.append(v)
        buf = jnp.concatenate(flat) if len(flat) > 1 else flat[0]
        pad = bucket.padded_size - buf.shape[0]
        if pad:
            buf = jnp.concatenate([buf,
                                   jnp.zeros((pad,), dtype=buf.dtype)])
    return buf


def unpack(bucket: Bucket, buf: jax.Array) -> List[jax.Array]:
    """Split a fused buffer back into leaves (MemcpyOutFusionBuffer)."""
    out = []
    off = 0
    with jax.named_scope("hvd.bucket_unpack"):
        for size, shape in zip(bucket.sizes, bucket.shapes):
            n = int(np.prod(shape, dtype=np.int64))  # real elems (slot >= 1)
            out.append(jnp.reshape(buf[off:off + n], shape))
            off += size
    return out


# ---------------------------------------------------------------------------
# ZeRO shard layout: a bucket planned with ``shard_multiple=world`` divides
# into ``world`` equal contiguous shards in RANK-MAJOR order — rank
# ``r = cross_rank * local_size + local_rank`` owns elements
# ``[r * seg, (r + 1) * seg)`` of the flat buffer (``seg = padded // world``).
# The compiled reduce-scatter/all-gather (ops/collective_ops.py) produce and
# consume exactly this layout, and because it matches how ``P(HVD_AXES)``
# splits a leading dim, a ZeRO optimizer-state leaf outside the trace is
# simply the flat bucket itself, sharded — no permutation to undo when
# checkpointing or elastically resharding.
# ---------------------------------------------------------------------------


def shard_size(bucket: Bucket, world: int) -> int:
    """Per-rank shard elements of a bucket planned with
    ``shard_multiple=world``."""
    if bucket.padded_size % world:
        raise ValueError(
            f"bucket padded_size {bucket.padded_size} does not divide into "
            f"{world} shards — plan with plan_buckets(shard_multiple=world)")
    return bucket.padded_size // world


def shard_slice(buf: jax.Array, world: int, rank) -> jax.Array:
    """This rank's contiguous flat shard of a packed bucket buffer.
    ``rank`` may be a traced per-device index (``hvd.rank()`` inside
    shard_map) or a python int (host-side slicing for elastic reshard)."""
    if buf.shape[0] % world:
        raise ValueError(
            f"buffer of {buf.shape[0]} elements does not divide into "
            f"{world} shards")
    seg = buf.shape[0] // world
    import jax.lax as lax

    return lax.dynamic_slice_in_dim(buf, rank * seg, seg, 0)


def shard_unslice(shards: Sequence[jax.Array]) -> jax.Array:
    """Reassemble a flat bucket buffer from its per-rank shards in rank
    order (the host-side inverse of :func:`shard_slice`; in-trace the
    all-gather collective does this on the wire)."""
    shards = [jnp.ravel(jnp.asarray(s)) for s in shards]
    return jnp.concatenate(shards) if len(shards) > 1 else shards[0]


def allreduce_pytree(
    tree,
    *,
    op: C.ReduceOp = C.ReduceOp.AVERAGE,
    compression=Compression.none,
    threshold_bytes: Optional[int] = None,
    axes=None,
    hierarchical: Optional[bool] = None,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    presummed: bool = False,
    quantized: Optional[bool] = None,
    error_feedback=None,
    block: Optional[int] = None,
    tuned_params=None,
    overlap: Optional[bool] = None,
    num_comm_streams: Optional[int] = None,
    plan=None,
):
    """Allreduce every leaf of a pytree with tensor fusion.

    This is what :class:`horovod_tpu.DistributedOptimizer` runs on the
    gradient tree — the analogue of the reference's per-step fused
    NCCL allreduce cycle (RunLoopOnce → FuseResponses → NCCLAllreduce,
    operations.cc:571-624).

    Leaves that are already replicated across the mesh axes (VMA-invariant)
    are handled without a collective; ``presummed`` controls their
    interpretation (see :func:`collective_ops._reduce_replicated`). The
    default ``presummed=False`` gives plain collective semantics (equal
    contributions); the gradient paths (DistributedOptimizer, tape) pass
    ``presummed=True`` because shard_map autodiff auto-psums gradients of
    replicated parameters. Only genuinely per-rank leaves are packed into
    fused buffers and reduced on the wire.

    ``quantized`` routes each fused bucket through the blockwise-int8 DCN
    wire (the quantized allreduce plan, plan/compiler.py); bucket padding to
    ``ATOMIC_UNIT`` keeps the per-block scales aligned with the shard
    layout. ``error_feedback`` is a pytree of per-rank residual
    accumulators matching ``tree`` (zeros initially); when given, the
    return value becomes ``(reduced_tree, new_error_feedback)`` — residuals
    are packed with the same bucket plan as the gradients, so each bucket
    carries its quantization error into the next step (EF-SGD). Non-float
    and replicated leaves pass their residual through unchanged (it stays
    zero).

    ``tuned_params`` (an ``autotune.TunedParams``) applies an autotuner
    override: it fills ``threshold_bytes``, ``hierarchical``, the int8
    scale-``block``, and the ``overlap``/``num_comm_streams`` pair
    wherever the caller left them unset, so a tuning session (or its
    frozen winner) steers the trace without touching the process-wide env
    config. Explicit per-call arguments still win.

    ``overlap`` (default: the ``HOROVOD_OVERLAP`` knob) issues the bucket
    collectives through the reverse-layer stream schedule
    (:func:`stream_order` + per-bucket
    :func:`~horovod_tpu.ops.collective_ops.allreduce_stream`), in flights
    of ``num_comm_streams`` buckets whose unpacking is deferred until the
    flight is issued — so up to that many collectives sit in the program
    with no consumer between them and the latency-hiding scheduler can
    run them under backward compute. Bucket contents and per-bucket math
    are untouched, so overlap mode is bit-identical to off
    (docs/overlap.md).

    ``plan`` (a :class:`horovod_tpu.plan.WirePlan` for the gradient
    allreduce) threads the wire composition explicitly instead of the
    boolean knobs, which remain as aliases: wherever a knob is unset it
    derives from the plan (``quantized`` from its int8 legs,
    ``hierarchical`` from its tree shape, ``overlap``/``num_comm_streams``
    from its stream placement), and the per-bucket collectives lower
    through exactly this plan (docs/wire-plan.md)."""
    if plan is not None:
        plan = plan.validate()
        if quantized is None:
            quantized = plan.is_quantized
        if hierarchical is None:
            hierarchical = plan.is_tree and not plan.is_quantized
        if block is None:
            block = plan.quant_block
        if overlap is None:
            overlap = plan.overlap
        if num_comm_streams is None:
            num_comm_streams = plan.streams
    if tuned_params is not None:
        if threshold_bytes is None:
            threshold_bytes = tuned_params.fusion_threshold_bytes
        if hierarchical is None:
            hierarchical = tuned_params.hierarchical_allreduce
        if block is None:
            block = tuned_params.quant_block
    leaves, treedef = jax.tree.flatten(tree)
    if error_feedback is not None:
        quantized = True if quantized is None else quantized
        ef_leaves = jax.tree.flatten(error_feedback)[0]
        if len(ef_leaves) != len(leaves):
            raise ValueError(
                "error_feedback tree structure does not match the gradient "
                f"tree ({len(ef_leaves)} vs {len(leaves)} leaves)")
    if not leaves:
        return tree if error_feedback is None else (tree, error_feedback)
    axes_t = C._resolve_axes(axes)
    out: List[Optional[jax.Array]] = [None] * len(leaves)
    new_ef: List[Optional[jax.Array]] = (
        None if error_feedback is None else list(ef_leaves))

    varying_idx: List[int] = []
    for i, leaf in enumerate(leaves):
        if axes_t and C._is_replicated(leaf, axes_t):
            out[i] = C.allreduce(
                leaf, op=op, compression=compression, axes=axes,
                hierarchical=hierarchical, prescale_factor=prescale_factor,
                postscale_factor=postscale_factor, quantized=quantized,
                block=block, plan=plan, _presummed=presummed)
        else:
            varying_idx.append(i)

    if varying_idx:
        vleaves = [leaves[i] for i in varying_idx]
        v_ef = (None if new_ef is None
                else [ef_leaves[i] for i in varying_idx])
        buckets = plan_buckets(vleaves, threshold_bytes)
        overlap_on, n_streams = _resolve_overlap(overlap, num_comm_streams,
                                                 tuned_params)
        order = (stream_order(buckets) if overlap_on
                 else tuple(range(len(buckets))))
        flight = n_streams if overlap_on else 1
        for s in range(0, len(order), flight):
            issued = []
            for j in order[s:s + flight]:
                bucket = buckets[j]
                buf = pack(bucket, vleaves)
                use_ef = (new_ef is not None
                          and jnp.issubdtype(bucket.dtype, jnp.floating))
                rbuf = pack(bucket, v_ef) if use_ef else None
                with jax.named_scope("hvd.bucket_allreduce"):
                    if use_ef:
                        if overlap_on:
                            red, rnew = C.allreduce_stream(
                                buf, rbuf, bucket_id=j, op=op,
                                compression=compression, axes=axes,
                                prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor,
                                block=block, plan=plan)
                        else:
                            red, rnew = C.quantized_allreduce(
                                buf, rbuf, op=op, compression=compression,
                                axes=axes, prescale_factor=prescale_factor,
                                postscale_factor=postscale_factor,
                                block=block, plan=plan)
                    else:
                        rnew = None
                        kw = dict(op=op, compression=compression, axes=axes,
                                  hierarchical=hierarchical,
                                  prescale_factor=prescale_factor,
                                  postscale_factor=postscale_factor,
                                  quantized=quantized, block=block,
                                  plan=plan)
                        red = (C.allreduce_stream(buf, bucket_id=j, **kw)
                               if overlap_on else C.allreduce(buf, **kw))
                issued.append((j, red, rnew))
            # Unpack AFTER the whole flight is issued: no consumer sits
            # between in-flight collectives, so the scheduler may run
            # them concurrently (flight == 1 reproduces the serial
            # issue→unpack order of overlap-off exactly).
            for j, red, rnew in issued:
                bucket = buckets[j]
                if rnew is not None:
                    for i, r in zip(bucket.leaf_indices,
                                    unpack(bucket, rnew)):
                        new_ef[varying_idx[i]] = r
                for i, leaf in zip(bucket.leaf_indices, unpack(bucket, red)):
                    out[varying_idx[i]] = leaf
    result = jax.tree.unflatten(treedef, out)
    if error_feedback is None:
        return result
    return result, jax.tree.unflatten(treedef, new_ef)
