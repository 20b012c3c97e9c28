"""The wire-plan compiler: lower a validated :class:`~.ir.WirePlan` to
the existing jax primitives, leg by leg.

This file is the single home of every collective leg-composition in the
repo — the bodies that used to live as bespoke paths in
``ops/collective_ops.py`` (the quantized hierarchical allreduce, the
quantized DCN reduce-scatter/all-gather legs of the ZeRO wire, the
hierarchical psum) are now **leg lowering rules** invoked by plan family:

======================  ==============================================
lowering rule            composition it implements
======================  ==============================================
:func:`_leg_flat_psum`   one XLA-decomposed psum over the axis tuple
:func:`_lower_tree_psum` ici reduce-scatter → dcn psum [→ pod psum] →
                         ici all-gather (NCCLHierarchicalAllreduce
                         shape, nccl_operations.cc:190-380)
:func:`_leg_quant_rs`    quantized DCN reduce-scatter: blockwise int8 +
                         fp32 scales over a tiled all_to_all,
                         dequantize-accumulate at the receiver
:func:`_leg_quant_ag`    quantized DCN all-gather: requantize the owned
                         segment, masked int8 psum (disjoint support ⇒
                         exact sum, replicated BY CONSTRUCTION)
:func:`_leg_ici_gather`  ici gather as a psum of disjointly-placed
                         shards (the repo's replication-by-construction
                         idiom)
======================  ==============================================

Every rule accounts its wire bytes through
:mod:`horovod_tpu.plan.accounting` at trace time, so every plan is
instrumented for free. The compiler works on the WIRE composition only:
op semantics (Average scaling, pre/post scale, compression casts,
replicated short-circuits, eager fallbacks) stay in the public entry
points of ``ops/collective_ops.py``, which derive a plan
(:mod:`horovod_tpu.plan.planner`) and call in here.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..common import basics
from ..common.basics import CROSS_AXIS, LOCAL_AXIS, POD_AXIS
from ..ops import compression as _compression
from . import ir
from .accounting import (_acct, _acct_a2a, _acct_enabled, _acct_kv,
                         _acct_pp, moe_span, pp_span)

# Mesh axis carried by each plan level.
LEVEL_AXIS = {ir.ICI: LOCAL_AXIS, ir.DCN: CROSS_AXIS, ir.POD: POD_AXIS}


def _axis_size(name) -> int:
    return basics._axis_size(name)


def quant_wire_bytes(seg: int, blk: int) -> float:
    """Bytes of one quantized segment on the wire: int8 payload plus one
    fp32 scale per ``blk`` elements, after padding ``seg`` up to a block
    multiple (the unit every quantized-leg cost formula is built from)."""
    pad_seg = (-seg) % blk + seg
    return pad_seg + (pad_seg // blk) * 4.0


# ---------------------------------------------------------------------------
# Flat legs (one XLA-decomposed collective over the whole axis tuple).
# ---------------------------------------------------------------------------


def _acct_psum_flat(x, axes) -> None:
    """Account a flat psum over ``axes`` with the topology-aware model:
    ICI leg on the full payload, DCN leg on the 1/local shard, pod leg on
    the 1/(local*cross) shard (DCN-class wire physically, charged to its
    own ``pod`` link class so 3-level meshes can model an asymmetric
    HOROVOD_BENCH_POD_GBPS bandwidth)."""
    if not _acct_enabled():
        return
    n = float(np.prod(x.shape)) if x.ndim else 1.0
    isz = jnp.dtype(x.dtype).itemsize
    if LOCAL_AXIS in axes:
        nl = _axis_size(LOCAL_AXIS)
        _acct("ici", 2.0 * n * (nl - 1) / nl * isz)
        n /= nl
    if CROSS_AXIS in axes:
        nc = _axis_size(CROSS_AXIS)
        _acct("dcn", 2.0 * n * (nc - 1) / nc * isz)
        n /= nc
    if POD_AXIS in axes:
        npod = _axis_size(POD_AXIS)
        _acct("pod", 2.0 * n * (npod - 1) / npod * isz)


def _leg_flat_psum(x, axes):
    _acct_psum_flat(x, axes)
    return lax.psum(x, axes)


# ---------------------------------------------------------------------------
# Tree (hierarchical) psum: per-level reduction ladder in the payload
# dtype. Lowering rule for the [ici.rs > dcn.psum (> pod.psum) > ici.ag]
# plan (reference algorithm: NCCLHierarchicalAllreduce,
# nccl_operations.cc:190-380, including the non-divisible remainder
# handled separately — here via the flat-psum fallback, matching the
# reference's root reduce/bcast remainder leg).
# ---------------------------------------------------------------------------


def _lower_tree_psum(plan: ir.WirePlan, x, axes: Tuple[str, ...]):
    local_axis, cross_axis = LOCAL_AXIS, CROSS_AXIS
    cross_levels = [l.level for l in plan.legs
                    if l.primitive == ir.PSUM and l.level != ir.FLAT]
    # Quantized pod hop (docs/wire-plan.md): the pod level spelled as
    # the rs[int8] > ag[int8] pair instead of the exact psum.
    qpod = [l for l in plan.legs
            if l.level == ir.POD and l.wire_dtype == ir.INT8]
    nl = _axis_size(local_axis)
    npod = _axis_size(POD_AXIS) if qpod else 1
    if x.ndim >= 1 and x.shape[0] % nl == 0 and x.shape[0] > 0:
        n_elems = int(np.prod(x.shape, dtype=np.int64))
        sn = n_elems // nl
        # The quantized pod pair needs the post-ICI shard to split into
        # whole per-pod segments; otherwise it falls back to the exact
        # pod psum (the same remainder contract as the tree plan itself).
        use_qpod = bool(qpod) and npod > 1 and sn % npod == 0
        if _acct_enabled():
            n = float(n_elems)
            isz = jnp.dtype(x.dtype).itemsize
            _acct("ici", n * (nl - 1) / nl * isz)        # psum_scatter
            for lvl in cross_levels:                      # cross psum(s)
                k = _axis_size(LEVEL_AXIS[lvl])
                _acct("pod" if lvl == ir.POD else "dcn",
                      2.0 * (n / nl) * (k - 1) / k * isz)
            if use_qpod:
                blk = int(qpod[0].block or 256)
                seg = sn // npod
                q_unit = quant_wire_bytes(seg, blk) * npod
                _acct("pod", q_unit * (npod - 1) / npod,   # rs[int8]
                      float(sn) * (npod - 1) / npod * isz)
                _acct("pod", 2.0 * q_unit * (npod - 1) / npod,  # ag[int8]
                      2.0 * float(sn) * (npod - 1) / npod * isz)
            elif qpod:
                _acct("pod", 2.0 * (n / nl) * (npod - 1) / npod * isz)
            _acct("ici", 2.0 * n * (nl - 1) / nl * isz)  # gather-leg psum
        shard = lax.psum_scatter(x, local_axis, scatter_dimension=0,
                                 tiled=True)
        for lvl in cross_levels:
            shard = lax.psum(shard, LEVEL_AXIS[lvl])
        if qpod:
            if use_qpod:
                blk = int(qpod[0].block or 256)
                seg = sn // npod
                shape = shard.shape
                segs = shard.reshape(npod, seg).astype(jnp.float32)
                red, _ = _leg_quant_rs(segs, blk, POD_AXIS)
                vals, _ = _leg_quant_ag(red, blk, POD_AXIS)
                shard = vals.reshape(shape).astype(x.dtype)
            else:
                shard = lax.psum(shard, POD_AXIS)
        # Final allgather leg, expressed as a psum of disjointly-placed
        # shards: numerically identical to lax.all_gather but the result is
        # provably replicated for the sharding checker (all_gather output is
        # conservatively treated as device-varying). Note the flat psum
        # below is usually optimal on TPU — XLA already decomposes a global
        # AllReduce over ICI/DCN — so the tree plan is a tuning knob for
        # multi-slice topologies, as in the reference (operations.cc:475-487).
        li = lax.axis_index(local_axis)
        # Fresh zeros (not zeros_like(x)) so the buffer doesn't inherit x's
        # cross-axis varying mark — shard is already cross-reduced.
        full = jnp.zeros(x.shape, x.dtype)
        full = lax.dynamic_update_slice_in_dim(
            full, shard, li * shard.shape[0], 0)
        return lax.psum(full, local_axis)
    return _leg_flat_psum(x, axes)


# ---------------------------------------------------------------------------
# Quantized DCN legs — the EQuARX decomposition placed per HiCCL's rule
# (compress the slow cross-host hop only, never the fast ICI one). These
# two rules are the int8 wire: ``_leg_quant_rs`` is the reduce half,
# ``_leg_quant_ag`` the gather half; the ZeRO wire runs the optimizer
# update between them, the quantized allreduce runs them back-to-back.
# ---------------------------------------------------------------------------


def _quantize_blocks(blocks):
    """Blockwise int8 quantize of ``blocks [rows, nb, blk]`` →
    ``(q, scales, err)``."""
    scales = _compression._block_scales(blocks)
    q = jnp.clip(jnp.round(blocks / scales[..., None]),
                 -127, 127).astype(jnp.int8)
    err = blocks - q.astype(jnp.float32) * scales[..., None]
    return q, scales, err


def _dequant_accumulate(qT, sT):
    """``sum_r qT[r] * sT[r]`` over the contributor axis."""
    return jnp.sum(qT.astype(jnp.float32) * sT[..., None], axis=0)


def _leg_quant_rs(segs, blk: int, cross_axis):
    """Quantized DCN reduce-scatter leg: ``segs`` is this rank's
    ICI-scattered shard viewed ``[nc, seg]`` in fp32, row ``j`` destined
    to cross rank ``j``. Each row quantizes to int8 with one fp32 scale
    per ``blk`` elements, a tiled ``all_to_all`` moves int8 + scales,
    receivers dequantize-accumulate in fp32. Returns
    ``(reduced_seg [seg] fp32, err [nc, seg] fp32)`` where ``err`` is
    this rank's quantization error on everything it sent."""
    nc, seg = segs.shape
    pad = (-seg) % blk
    if pad:
        segs = jnp.concatenate(
            [segs, jnp.zeros((nc, pad), jnp.float32)], axis=1)
    nb = segs.shape[1] // blk
    blocks = segs.reshape(nc, nb, blk)
    q, scales, err = _quantize_blocks(blocks)
    qT = lax.all_to_all(q, cross_axis, split_axis=0, concat_axis=0,
                        tiled=True)
    sT = lax.all_to_all(scales, cross_axis, split_axis=0, concat_axis=0,
                        tiled=True)
    acc = _dequant_accumulate(qT, sT)
    return (acc.reshape(nb * blk)[:seg],
            err.reshape(nc, nb * blk)[:, :seg])


def _leg_quant_ag(seg_vals, blk: int, cross_axis):
    """Quantized DCN all-gather leg: quantize this rank's owned segment
    ``[seg]`` (fp32) and rebroadcast it as a masked int8 psum — disjoint
    support makes the sum exact and the result replicated over
    ``cross_axis`` BY CONSTRUCTION. Returns
    ``(vals [nc, seg] fp32, err [seg] fp32)``."""
    nc = _axis_size(cross_axis)
    seg = seg_vals.shape[0]
    pad = (-seg) % blk
    padded = (jnp.concatenate([seg_vals, jnp.zeros((pad,), jnp.float32)])
              if pad else seg_vals)
    nb = padded.shape[0] // blk
    q3, s2, e3 = _quantize_blocks(padded.reshape(1, nb, blk))
    q2, s2, err = q3[0], s2[0], e3[0]
    err = err.reshape(nb * blk)[:seg]
    ci = lax.axis_index(cross_axis)
    qfull = lax.dynamic_update_slice_in_dim(
        jnp.zeros((nc, nb, blk), jnp.int8), q2[None], ci, 0)
    sfull = lax.dynamic_update_slice_in_dim(
        jnp.zeros((nc, nb), jnp.float32), s2[None], ci, 0)
    qg = lax.psum(qfull, cross_axis)
    sg = lax.psum(sfull, cross_axis)
    vals = (qg.astype(jnp.float32) * sg[..., None]).reshape(
        nc, nb * blk)[:, :seg]
    return vals, err


def _leg_ici_gather(shard_flat, n: int, offset, local_axis=LOCAL_AXIS):
    """ICI all-gather leg as a psum of disjointly-placed flat shards —
    the replication-by-construction gather every tree plan closes with."""
    full = jnp.zeros((n,), shard_flat.dtype)
    full = lax.dynamic_update_slice_in_dim(full, shard_flat, offset, 0)
    return lax.psum(full, local_axis)


# ---------------------------------------------------------------------------
# Send leg — the pipeline wire (docs/pipeline.md). One point-to-point
# ``lax.ppermute`` hop along ``axis`` (the hvd_pp axis), charged to the
# link class the leg's level names. The int8 wire dtype quantizes the
# payload blockwise before the hop and dequantizes after — the EQuARX
# per-hop rule applied to the activation wire — with an optional
# error-feedback residual (the quantization error of what THIS rank
# sent, re-injected into its next send).
# ---------------------------------------------------------------------------


def lower_send(plan: ir.WirePlan, x, *, axis, perm, residual=None,
               repeats: int = 1):
    """Lower a validated send plan over payload ``x``; returns
    ``(received, new_residual)`` (``new_residual`` is None without EF).

    ``perm`` is the ``lax.ppermute`` permutation (pairs); ``repeats`` is
    the number of times the caller's schedule issues this hop per traced
    program (a ``lax.scan`` body traces ONCE — the pipeline passes its
    tick count so the trace-time accounting charges the true per-step
    wire bytes, garbage bubble sends included: masked SPMD sends move
    real bytes)."""
    (leg,) = plan.legs
    hop = ir.LEVEL_HOP[leg.level]
    k = 1
    for a in ((axis,) if isinstance(axis, str) else tuple(axis)):
        k *= _axis_size(a)
    n = int(np.prod(x.shape, dtype=np.int64)) if x.ndim else 1
    isz = jnp.dtype(x.dtype).itemsize
    frac = len(perm) / max(1, k)  # fraction of ranks sending per issue
    if leg.wire_dtype != ir.INT8:
        if _acct_enabled():
            _acct_pp(hop, float(n) * isz * frac * repeats,
                     sends=repeats)
        with pp_span("SEND"):
            out = lax.ppermute(x, axis, perm)
        return out, (None if residual is None
                     else jnp.zeros_like(residual))

    blk = int(leg.block or 256)
    corrected = (x if residual is None
                 else x + residual.reshape(x.shape).astype(x.dtype))
    flat = jnp.ravel(corrected).astype(jnp.float32)
    pad = (-n) % blk
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    nb = flat.shape[0] // blk
    q, scales, err = _quantize_blocks(flat.reshape(1, nb, blk))
    if _acct_enabled():
        wire = quant_wire_bytes(n, blk)
        _acct_pp(hop, wire * frac * repeats,
                 float(n) * isz * frac * repeats, sends=repeats)
    with pp_span("SEND"):
        qg = lax.ppermute(q, axis, perm)
        sg = lax.ppermute(scales, axis, perm)
    out = (qg.astype(jnp.float32) * sg[..., None]).reshape(
        nb * blk)[:n].reshape(x.shape).astype(x.dtype)
    if residual is None:
        return out, None
    new_res = err.reshape(nb * blk)[:n].reshape(residual.shape)
    return out, new_res.astype(residual.dtype)


# ---------------------------------------------------------------------------
# kv_migrate leg — the serving KV handoff wire (docs/serving.md). Unlike
# every other lowering here this one runs HOST-side: a prefill replica
# and its decode replica are two separate engine meshes with no shared
# program, so the migrator gathers a finished slot's KV pages on the
# source, pushes them through this wire (the encode→transfer→decode
# composition the plan names), and scatters the received pages on the
# destination between its decode steps. The wire composition is the
# plan's, exactly like the in-program legs: payload dtype passes
# through; int8 quantizes blockwise with one fp32 scale per block, and
# the error-feedback slot means the RESIDUAL pass — a second int8
# payload over the first pass's quantization error on the same hop
# (one-shot transfers have no next step to feed the error into), which
# collapses the reconstruction error to ~(absmax/127)^2.
# ---------------------------------------------------------------------------


def _host_quant_blocks(flat: np.ndarray, blk: int):
    """Host-side mirror of :func:`_quantize_blocks` over a flat fp32
    payload: ``(dequantized, err)`` after one blockwise int8
    round-trip. Same scale rule (absmax/127 per block, floored away
    from zero) so the wire format matches the device kernels."""
    n = flat.shape[0]
    pad = (-n) % blk
    if pad:
        flat = np.concatenate([flat, np.zeros((pad,), np.float32)])
    blocks = flat.reshape(-1, blk)
    scales = np.abs(blocks).max(axis=1) / 127.0
    scales = np.maximum(scales, 1e-12).astype(np.float32)
    q = np.clip(np.round(blocks / scales[:, None]), -127, 127)
    deq = (q.astype(np.float32) * scales[:, None]).reshape(-1)[:n]
    return deq, flat.reshape(-1)[:n] - deq


def lower_kv_migrate(plan: ir.WirePlan, x: np.ndarray, *,
                     transfers: int = 0) -> Tuple[np.ndarray, float]:
    """Lower a validated kv_migrate plan over host payload ``x`` (one
    chunk of a slot's gathered KV pages, any shape/float dtype);
    returns ``(received, wire_bytes)`` — the array the decode replica
    scatters into its pools, plus the bytes this chunk put on the
    plan's hop (charged to ``comm.kv.bytes{hop}`` and the per-hop
    totals via :func:`~horovod_tpu.plan.accounting._acct_kv`).
    ``transfers=1`` on the LAST chunk of a slot marks the whole-slot
    migration complete in the transfer counter."""
    (leg,) = plan.legs
    hop = ir.LEVEL_HOP[leg.level]
    n = int(x.size)
    isz = np.dtype(x.dtype).itemsize
    if leg.wire_dtype != ir.INT8:
        wire = float(n) * isz
        if _acct_enabled():
            _acct_kv(hop, wire, transfers=transfers)
        return np.array(x, copy=True), wire
    blk = int(leg.block or 256)
    flat = np.asarray(x, np.float32).reshape(-1)
    deq, err = _host_quant_blocks(flat, blk)
    wire = quant_wire_bytes(n, blk)
    if leg.error_feedback:
        # Residual pass: quantize the first pass's error and ship it on
        # the same wire — 2x the quantized bytes, argmax-safe decode.
        deq_err, _ = _host_quant_blocks(err, blk)
        deq = deq + deq_err
        wire *= 2.0
    if _acct_enabled():
        _acct_kv(hop, wire, float(n) * isz, transfers=transfers)
    return deq.reshape(x.shape).astype(x.dtype), wire


# ---------------------------------------------------------------------------
# a2a leg — the MoE wire (docs/moe.md). One tiled ``lax.all_to_all`` row
# exchange along ``axis`` (the hvd_ep axis): ``x`` is ``[k*m, ...]`` with
# row block ``j`` (of ``m`` rows) destined to ep rank ``j``; the output
# has the same shape, block ``j`` holding what rank ``j`` sent this
# rank. The int8 wire dtype quantizes the k-1 foreign row blocks
# blockwise before the exchange and dequantizes after — the EQuARX
# per-hop rule applied to the expert dispatch/combine traffic — with an
# optional error-feedback residual (this rank's quantization error on
# everything it sent, re-injected into its next exchange).
# ---------------------------------------------------------------------------


def lower_a2a(plan: ir.WirePlan, x, *, axis, residual=None,
              kind: str = "DISPATCH"):
    """Lower a validated a2a plan over buffer ``x [k*m, ...]``; returns
    ``(received, new_residual)`` (``new_residual`` is None without EF).

    The exchange is the canonical row form (``split_axis=0,
    concat_axis=0, tiled=True``); callers reshape dispatch semantics
    around it (horovod_tpu/moe/layer.py). ``kind`` names the
    ``MOE:<kind>`` span bracketing the exchange."""
    (leg,) = plan.legs
    hop = ir.LEVEL_HOP[leg.level]
    k = 1
    for a in ((axis,) if isinstance(axis, str) else tuple(axis)):
        k *= _axis_size(a)
    if x.shape[0] % k:
        raise ValueError(
            f"a2a buffer leading dim {x.shape[0]} does not divide by "
            f"the {k}-rank exchange axis {axis!r}")
    n = int(np.prod(x.shape, dtype=np.int64))
    seg = n // k                       # elements per destination row
    isz = jnp.dtype(x.dtype).itemsize
    if k == 1:
        # Degenerate world: nothing moves; still consume the residual so
        # the EF state threading is world-size independent.
        return x, (None if residual is None
                   else jnp.zeros_like(residual))
    if leg.wire_dtype != ir.INT8:
        if _acct_enabled():
            _acct_a2a(hop, float(seg) * (k - 1) * isz)
        with moe_span(kind):
            out = lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                 tiled=True)
        return out, (None if residual is None
                     else jnp.zeros_like(residual))

    blk = int(leg.block or 256)
    corrected = (x if residual is None
                 else x + residual.reshape(x.shape).astype(x.dtype))
    rows = jnp.reshape(corrected, (k, seg)).astype(jnp.float32)
    pad = (-seg) % blk
    if pad:
        rows = jnp.concatenate(
            [rows, jnp.zeros((k, pad), jnp.float32)], axis=1)
    nb = rows.shape[1] // blk
    def _exchange_int8(blocks):
        """One int8 row exchange of ``blocks [k, nb, blk]``; returns
        ``(vals, err)`` — dequantized received blocks (a permutation,
        not a reduction: each block scales back independently) and this
        rank's quantization error on what it sent."""
        q, scales, err = _quantize_blocks(blocks)
        if _acct_enabled():
            _acct_a2a(hop, quant_wire_bytes(seg, blk) * (k - 1),
                      float(seg) * (k - 1) * isz)
        with moe_span(kind):
            qT = lax.all_to_all(q, axis, split_axis=0, concat_axis=0,
                                tiled=True)
            sT = lax.all_to_all(scales, axis, split_axis=0,
                                concat_axis=0, tiled=True)
        return (qT.astype(jnp.float32) * sT[..., None]), err

    # The transpose of the tiled (split 0, concat 0) row exchange is
    # ITSELF — (sender r, block j) swaps with (sender j, block r) — so
    # the backward pass rides the SAME int8 wire instead of a silent
    # fp fallback (and instead of autodiff's zero-gradient round):
    # cotangents quantize blockwise, exchange, dequantize. The EF
    # residual is forward-only state (no cotangent).
    @jax.custom_vjp
    def quantized_a2a(blocks):
        vals, err = _exchange_int8(blocks)
        return vals, err

    def _fwd(blocks):
        return _exchange_int8(blocks), None

    def _bwd(_, cots):
        g_vals, _g_err = cots
        g_back, _ = _exchange_int8(g_vals)
        return (g_back,)

    quantized_a2a.defvjp(_fwd, _bwd)

    vals3, err = quantized_a2a(rows.reshape(k, nb, blk))
    vals = vals3.reshape(k, nb * blk)[:, :seg]
    out = vals.reshape(x.shape).astype(x.dtype)
    if residual is None:
        return out, None
    new_res = err.reshape(k, nb * blk)[:, :seg].reshape(residual.shape)
    return out, jax.lax.stop_gradient(new_res).astype(residual.dtype)


# ---------------------------------------------------------------------------
# Allreduce lowerings.
# ---------------------------------------------------------------------------


def lower_psum(plan: ir.WirePlan, x, axes: Tuple[str, ...]):
    """Lower an exact (payload-dtype) allreduce-SUM plan."""
    if plan.is_flat:
        return _leg_flat_psum(x, axes)
    return _lower_tree_psum(plan, x, axes)


def lower_quantized_allreduce(plan: ir.WirePlan, x, *, residual=None,
                              block: int,
                              local_axis=LOCAL_AXIS,
                              cross_axis=CROSS_AXIS):
    """Lower the quantized allreduce-SUM plan
    ``[ici.rs > dcn.rs[int8] > dcn.ag[int8] > ici.ag]`` with optional
    error feedback.

    1. intra-host reduce-scatter (ICI, payload dtype);
    2. :func:`_leg_quant_rs` — cross-host quantized reduce-scatter;
    3. :func:`_leg_quant_ag` — cross-host quantized all-gather;
    4. :func:`_leg_ici_gather` — intra-host gather, payload dtype.

    Returns ``(sum, new_residual)``. With ``residual`` (error feedback),
    the residual is added to ``x`` before hop 1 and the returned residual
    holds this rank's quantization error — hop 2's error on the whole
    shard it contributed plus hop 3's requantization error on the segment
    it owns — written at the exact buffer positions where the next step's
    reduce-scatter re-collects each component exactly once.

    Falls back to an exact flat psum (consuming the residual, returning it
    as zeros) when there is no cross axis or the flattened size does not
    shard evenly over ``local_size * cross_size``.
    """
    nl = _axis_size(local_axis)
    nc = _axis_size(cross_axis)
    blk = int(block)
    corrected = x if residual is None else x + residual.astype(x.dtype)
    n = int(np.prod(x.shape, dtype=np.int64)) if x.ndim else 0
    if nc == 1 or n == 0 or n % nl or (n // nl) % nc:
        axes = (cross_axis, local_axis)
        out = _leg_flat_psum(corrected, axes)
        return out, (None if residual is None else jnp.zeros_like(residual))

    flat = jnp.ravel(corrected)
    sn = n // nl        # shard elements per device after the ICI leg
    seg = sn // nc      # segment elements per cross rank within a shard
    isz = jnp.dtype(x.dtype).itemsize
    if _acct_enabled():
        q_unit = quant_wire_bytes(seg, blk) * nc  # padded shard wire bytes
        _acct("ici", n * (nl - 1) / nl * isz)              # psum_scatter
        _acct("dcn", q_unit * (nc - 1) / nc,               # hop-2 all_to_all
              float(sn) * (nc - 1) / nc * isz)
        _acct("dcn", 2.0 * q_unit * (nc - 1) / nc,         # hop-3 masked psum
              2.0 * float(sn) * (nc - 1) / nc * isz)
        _acct("ici", 2.0 * n * (nl - 1) / nl * isz)        # ICI gather leg

    # Leg 1 — ICI reduce-scatter in the payload dtype.
    shard = lax.psum_scatter(flat, local_axis, scatter_dimension=0,
                             tiled=True)

    # Leg 2 — quantized DCN reduce-scatter (all_to_all of int8 + scales).
    segs = shard.reshape(nc, seg).astype(jnp.float32)
    red_seg, err1 = _leg_quant_rs(segs, blk, cross_axis)  # [seg], [nc, seg]

    # Leg 3 — requantize the reduced segment; masked int8 psum gathers the
    # shard with replication by construction (disjoint segment support).
    vals, err2 = _leg_quant_ag(red_seg, blk, cross_axis)  # [nc, seg], [seg]
    shard_red = vals.reshape(sn).astype(x.dtype)

    # Leg 4 — ICI gather (psum of disjointly-placed shards).
    li = lax.axis_index(local_axis)
    out = _leg_ici_gather(shard_red, n, li * sn,
                          local_axis).reshape(x.shape)
    if residual is None:
        return out, None

    # Error feedback: leg-2 error on every segment this rank contributed,
    # plus leg-3's requantization error on the one segment it owns.
    ci = lax.axis_index(cross_axis)
    rows = jnp.arange(nc)[:, None]
    err_sh = (err1 + jnp.where(rows == ci, err2[None], 0.0)).reshape(sn)
    res_full = lax.dynamic_update_slice_in_dim(
        jnp.zeros((n,), jnp.float32), err_sh, li * sn, 0)
    return out, res_full.reshape(x.shape).astype(residual.dtype)


# ---------------------------------------------------------------------------
# Reduce-scatter / all-gather lowerings — the ZeRO wire pair. Rank-major
# layout: the bucket viewed [nc, nl, seg] so rank r = cross*local + local
# owns contiguous flat elements [r*seg, (r+1)*seg) — how P(HVD_AXES)
# splits a leading dim.
# ---------------------------------------------------------------------------


def lower_reduce_scatter(plan: ir.WirePlan, flat, *, residual=None,
                         block: int, axes: Tuple[str, ...], world: int):
    """Lower a reduce-scatter plan over a flat [n] bucket; returns
    ``(shard [n/world], new_residual)``.

    Flat plan: one ``lax.psum_scatter`` over the axis tuple (XLA
    decomposes it topology-aware; piece order over an axis tuple is lex
    = rank-major order). Tree plan (``[ici.rs > dcn.rs[int8|payload]]``):
    rank-major ICI scatter, then the DCN leg in the plan's wire dtype —
    ``residual`` is the error-feedback accumulator of the int8 leg,
    sized ``[n / local_size]`` (this rank's post-ICI shard)."""
    n = int(flat.shape[0])
    seg = n // world
    isz = jnp.dtype(flat.dtype).itemsize
    if plan.is_flat:
        if _acct_enabled():
            rem = float(n)
            if LOCAL_AXIS in axes:
                nl = _axis_size(LOCAL_AXIS)
                _acct("ici", rem * (nl - 1) / nl * isz)
                rem /= nl
            if CROSS_AXIS in axes:
                nc = _axis_size(CROSS_AXIS)
                _acct("dcn", rem * (nc - 1) / nc * isz)
                rem /= nc
            if POD_AXIS in axes:
                npod = _axis_size(POD_AXIS)
                _acct("pod", rem * (npod - 1) / npod * isz)
        shard = lax.psum_scatter(flat, axes, scatter_dimension=0,
                                 tiled=True)
        new_res = None if residual is None else jnp.zeros_like(residual)
        return shard, new_res

    quantized = plan.is_quantized
    nl = _axis_size(LOCAL_AXIS)
    nc = _axis_size(CROSS_AXIS)
    sn = n // nl
    blk = int(block)
    if _acct_enabled():
        _acct("ici", n * (nl - 1) / nl * isz)          # ICI psum_scatter
        if nc > 1:
            if quantized:
                q_unit = quant_wire_bytes(seg, blk) * nc
                _acct("dcn", q_unit * (nc - 1) / nc,
                      float(sn) * (nc - 1) / nc * isz)
            else:
                _acct("dcn", sn * (nc - 1) / nc * isz)
    # ICI leg, rank-major: view [nc, nl, seg], scatter the nl dim.
    h = lax.psum_scatter(flat.reshape(nc, nl, seg), LOCAL_AXIS,
                         scatter_dimension=1, tiled=True)
    h = h.reshape(nc, seg)
    new_res = None
    if residual is not None:
        if residual.shape != (sn,):
            raise ValueError(
                f"reduce_scatter residual must be the post-ICI shard "
                f"[{sn}] (= n/local_size), got {residual.shape}")
        h = h + residual.reshape(nc, seg).astype(h.dtype)
    if nc == 1:
        shard = h.reshape(seg)
        if residual is not None:
            new_res = jnp.zeros_like(residual)
    elif quantized:
        red, err = _leg_quant_rs(h.astype(jnp.float32), blk, CROSS_AXIS)
        shard = red.astype(flat.dtype)
        if residual is not None:
            new_res = err.reshape(sn).astype(residual.dtype)
    else:
        shard = lax.psum_scatter(h, CROSS_AXIS, scatter_dimension=0,
                                 tiled=True).reshape(seg)
        if residual is not None:
            new_res = jnp.zeros_like(residual)
    return shard, new_res


def lower_all_gather(plan: ir.WirePlan, shard, *, residual=None,
                     block: int, axes: Tuple[str, ...], world: int,
                     rank):
    """Lower an all-gather plan over a flat [seg] shard; returns
    ``(full [seg*world], new_residual)`` — replicated BY CONSTRUCTION
    (masked-psum idiom on every path).

    Flat plan: one masked psum over the axis tuple. Quantized plan
    (``[dcn.ag[int8] > ici.ag]``): the DCN leg re-broadcasts this rank's
    owned segment as blockwise int8 (``residual`` is the EF accumulator
    over that segment), then the ICI leg places the cross-gathered
    column at this rank's local index of the rank-major
    ``[nc, nl, seg]`` layout and psums the disjoint contributions."""
    seg = int(shard.shape[0])
    n = seg * world
    if plan.is_quantized:
        nl = _axis_size(LOCAL_AXIS)
        nc = _axis_size(CROSS_AXIS)
        blk = int(block)
        isz = jnp.dtype(shard.dtype).itemsize
        if _acct_enabled():
            q_unit = quant_wire_bytes(seg, blk)
            _acct("dcn", 2.0 * q_unit * nc * (nc - 1) / nc,
                  2.0 * float(seg) * nc * (nc - 1) / nc * isz)
            _acct("ici", 2.0 * n * (nl - 1) / nl * isz)
        x = shard.astype(jnp.float32)
        new_res = None
        if residual is not None:
            if residual.shape != (seg,):
                raise ValueError(
                    f"all_gather residual must match the shard [{seg}], "
                    f"got {residual.shape}")
            x = x + residual.astype(jnp.float32)
        vals, err = _leg_quant_ag(x, blk, CROSS_AXIS)  # [nc, seg]
        if residual is not None:
            new_res = err.astype(residual.dtype)
        # ICI leg: place this rank's cross-gathered column at local index
        # li of the rank-major [nc, nl, seg] layout, psum-of-disjoint.
        li = lax.axis_index(LOCAL_AXIS)
        fullb = jnp.zeros((nc, nl, seg), jnp.float32)
        fullb = lax.dynamic_update_slice(fullb, vals[:, None, :], (0, li, 0))
        full = lax.psum(fullb, LOCAL_AXIS).reshape(n).astype(shard.dtype)
        return full, new_res

    # Exact path: one masked psum over all axes (disjoint contributions;
    # XLA decomposes it over ICI/DCN topology-aware).
    x = shard
    new_res = None
    if residual is not None:
        x = x + residual.astype(x.dtype)  # exact wire: consume the residual
        new_res = jnp.zeros_like(residual)
    buf = jnp.zeros((n,), x.dtype)
    buf = lax.dynamic_update_slice_in_dim(buf, x, rank * seg, 0)
    _acct_psum_flat(buf, axes)
    return lax.psum(buf, axes), new_res
