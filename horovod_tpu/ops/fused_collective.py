"""Fused compute-collective Pallas TPU kernels (docs/fused-kernels.md).

Scheduling-level overlap (docs/overlap.md) hides communication *between*
XLA ops; the remaining exposed cost is the HBM round-trip at the
compute/collective boundary itself — the full matmul product written out
just to be reduce-scattered, the gathered weight buffer written out just
to be matmul'd, the int8 payload + scales written out between the
quantize op and the wire. Following "Fused Computation-Collective
Operations" (arXiv:2305.06942) and T3 (arXiv:2401.16677), this module
fuses the three hot pairs into Pallas kernels so the boundary tensor
never materializes:

* :func:`fused_matmul_reduce_scatter` — **matmul → reduce-scatter
  epilogue** (ZeRO stage-2/3 gradient shards, TP row-parallel outputs):
  a ring of ``world`` steps where each step's Pallas kernel computes the
  output tile destined for one owner and accumulates it INTO the
  traveling partial-sum buffer; only a ``[M/world, N]`` tile ever exists
  per rank instead of the full ``[M, N]`` product. The ring hop
  (``lax.ppermute`` riding ICI/DCN neighbours) overlaps the next tile's
  MXU work under XLA's async collective scheduling — the same
  composition idiom as ``flash_ring_attention`` (ops/flash_attention.py).
* :func:`fused_all_gather_matmul` — **all-gather → matmul prologue**
  (ZeRO-3 JIT param gather, TP column-parallel inputs): weight shards
  rotate around the ring and each arriving shard feeds the next partial
  matmul while the previous one computes; the full ``[K, N]`` gathered
  weight never exists in HBM.
* :func:`quantize_blockwise` / :func:`dequantize_accumulate` —
  **in-kernel blockwise int8 quantize / dequant-accumulate** for the DCN
  legs of the quantized wire plans (EQuARX, arXiv:2506.17615: the
  quantization rides inside the collective): absmax, scales, rounding,
  and the error-feedback residual are produced in ONE VMEM pass, and the
  receiver's dequant-multiply-accumulate never expands the int8 payload
  to fp32 in HBM. The plan compiler invokes these when a leg carries
  ``backend="pallas"`` (``Leg(..., backend="pallas")``, plan/ir.py).

Wire bytes are IDENTICAL to the unfused lowerings (the ring moves the
same ``(n-1)/n`` payload the XLA collective would); the win is the
avoided HBM round-trip, which every kernel call credits to the trace-time
accounting (:func:`horovod_tpu.plan.accounting.fused_span` →
``FUSED:*`` timeline spans, ``comm.fused.*`` metrics,
``WireStats.fused_hbm_saved_bytes``).

Off-TPU every kernel runs in Pallas interpreter mode
(``pallas_call(interpret=True)``), so the CPU tier-1 suite exercises the
identical code path on the 8-device emulated mesh; the fused-vs-unfused
parity matrix lives in tests/test_fused_collective.py.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from . import flash_attention as _flash
from ..plan.accounting import _acct, _acct_enabled, fused_span

_interpret = _flash._interpret
_out_struct = _flash._out_struct


def _block_k_knob() -> int:
    from ..common.config import _env_int

    v = _env_int("HOROVOD_FUSED_BLOCK_K", 512)
    if v < 128:
        raise ValueError(
            f"HOROVOD_FUSED_BLOCK_K={v}: Pallas kernel blocks must be "
            f">= 128 (MXU/lane tile)")
    return v


# These kernels hold whole operands in VMEM (no row/column grid), so the
# TPU compiler refuses them above toy sizes (ROADMAP S7: run or remove).
# Each caller prices its footprint from shapes with a model fitted to
# what the v5e compiler accepted and refused (tests/test_tpu_lowering.py)
# and fails here, by name, instead of minutes into a compile.
_VMEM_LIMIT_BYTES = 16 * 2 ** 20


def _check_vmem(kernel: str, shape_desc: str, nbytes: float) -> None:
    if not _interpret() and nbytes > _VMEM_LIMIT_BYTES:
        raise ValueError(
            f"fused_collective.{kernel}: {shape_desc} needs about "
            f"{nbytes / 2 ** 20:.1f} MiB of VMEM against the "
            f"{_VMEM_LIMIT_BYTES // 2 ** 20} MiB the TPU compiler "
            f"grants one kernel; the opt-in fused= kernels are not "
            f"tiled for real sizes (ROADMAP S7) — run this size "
            f"without fused=")


def _resolve_axes(axes) -> Tuple[str, ...]:
    from .collective_ops import _resolve_axes as _ra

    return _ra(axes)


def _vary(x, axes_t, *others):
    from .collective_ops import _vma, pvary_missing

    union = set(axes_t) | frozenset().union(*[_vma(t) for t in others])
    return pvary_missing(x, tuple(sorted(union)))


# ---------------------------------------------------------------------------
# HBM-traffic model: bytes the fusion avoids round-tripping vs the
# separate-op lowering. ONE definition shared by the kernels' trace-time
# accounting, the plan table's ``fused:`` delta line, and the tests'
# assertions (docs/fused-kernels.md, "HBM model").
# ---------------------------------------------------------------------------


def matmul_rs_hbm_saved(m: int, n: int, world: int, itemsize: int) -> float:
    """Unfused: the full [m, n] partial product writes to HBM and the
    reduce-scatter reads it back; fused keeps all but this rank's final
    [m/world, n] tile in VMEM → 2 * (1 - 1/world) * m*n*itemsize."""
    return 2.0 * (m - m // max(1, world)) * n * float(itemsize)


def ag_matmul_hbm_saved(k: int, n: int, world: int, itemsize: int) -> float:
    """Unfused: the gathered [k, n] weight writes to HBM (all-gather) and
    the matmul reads it back; fused streams each arriving shard straight
    into the MXU → 2 * (1 - 1/world) * k*n*itemsize (this rank's own
    shard lives in HBM either way)."""
    return 2.0 * (k - k // max(1, world)) * n * float(itemsize)


def quant_hbm_saved(rows: int, nb: int, blk: int) -> float:
    """Unfused: the int8 payload and fp32 scales materialize in HBM
    between the quantize op and the wire (write + read); fused produces
    them in the VMEM pass that already holds the blocks →
    2 * (rows*nb*blk * 1B + rows*nb * 4B)."""
    return 2.0 * (rows * nb * blk * 1.0 + rows * nb * 4.0)


def dequant_hbm_saved(rows: int, nb: int, blk: int) -> float:
    """Unfused: the dequantized fp32 expansion [rows, nb, blk]
    materializes before the sum; fused multiply-accumulates in VMEM →
    2 * rows*nb*blk * 4B."""
    return 2.0 * rows * nb * blk * 4.0


# ---------------------------------------------------------------------------
# Kernel bodies.
# ---------------------------------------------------------------------------


def _mm_acc_kernel(x_ref, w_ref, acc_ref, o_ref, acc_scr, *, nk):
    """o = acc + x @ w, K-blocked: grid axis 0 walks the contraction in
    ``bk`` slabs with the fp32 accumulator resident in VMEM scratch — the
    ring-step tile matmul of both fusion pairs."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = acc_ref[...].astype(jnp.float32)

    acc_scr[:] += lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(j == nk - 1)
    def _finish():
        o_ref[...] = acc_scr[:].astype(o_ref.dtype)


def _matmul_accumulate(x, w, acc, *, block_k: Optional[int] = None):
    """acc + x @ w through the Pallas tile kernel (fp32 accumulate).

    x [m, K], w [K, N], acc [m, N] → [m, N] in acc.dtype. The contraction
    is ``block_k``-blocked (HOROVOD_FUSED_BLOCK_K, default 512, snapped
    to a 128-aligned divisor of K like the flash kernels; whole-K when
    nothing divides)."""
    m, K = x.shape
    N = w.shape[1]
    bk = _flash._pick_block(K, block_k or _block_k_knob()) or K
    nk = K // bk
    isz, asz = x.dtype.itemsize, acc.dtype.itemsize
    # resident [m, N] acc in + out + fp32 scratch, double-buffered slabs
    _check_vmem("_matmul_accumulate", f"[{m},{K}] @ [{K},{N}]",
                m * N * (2 * asz + 4) + 2 * (m * bk + bk * N) * isz)
    return pl.pallas_call(
        functools.partial(_mm_acc_kernel, nk=nk),
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((m, bk), lambda j: (0, j)),
            pl.BlockSpec((bk, N), lambda j: (j, 0)),
            pl.BlockSpec((m, N), lambda j: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, N), lambda j: (0, 0)),
        out_shape=_out_struct((m, N), acc.dtype, x, w, acc),
        scratch_shapes=[pltpu.VMEM((m, N), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
    )(x, w, acc)


def _quant_kernel(b_ref, q_ref, s_ref, e_ref):
    """Blockwise int8 quantize, one VMEM pass: absmax → scales → rounded
    payload → error residual. The math is byte-for-byte the
    ``_block_scales`` + clip/round composition of ops/compression.py, so
    the wire FORMAT is identical to the XLA lowering (values agree to
    the last ulp of the scale division; tests ulp-bound it)."""
    blocks = b_ref[...]
    absmax = jnp.max(jnp.abs(blocks), axis=-1)
    scales = jnp.where(absmax > 0, absmax / 127.0, jnp.ones_like(absmax))
    q = jnp.clip(jnp.round(blocks / scales[..., None]), -127, 127)
    qi = q.astype(jnp.int8)
    e_ref[...] = blocks - qi.astype(jnp.float32) * scales[..., None]
    q_ref[...] = qi
    s_ref[...] = scales


def quantize_blockwise(blocks):
    """Fused blockwise int8 quantization of fp32 ``blocks``
    ``[rows, nb, blk]`` → ``(q int8 [rows, nb, blk], scales fp32
    [rows, nb], err fp32 [rows, nb, blk])`` — the kernel behind
    ``backend="pallas"`` on an int8 reduce-scatter/all-gather leg."""
    rows, nb, blk = blocks.shape
    # fp32 in + err, int8 out, and one fp32 working copy
    _check_vmem("quantize_blockwise", f"blocks {list(blocks.shape)}",
                13.0 * rows * nb * blk)
    with fused_span("QUANT", quant_hbm_saved(rows, nb, blk)):
        return pl.pallas_call(
            _quant_kernel,
            out_shape=[
                _out_struct((rows, nb, blk), jnp.int8, blocks),
                _out_struct((rows, nb), jnp.float32, blocks),
                _out_struct((rows, nb, blk), jnp.float32, blocks),
            ],
            interpret=_interpret(),
        )(blocks)


def _dequant_acc_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = jnp.sum(
        q_ref[...].astype(jnp.float32) * s_ref[...][..., None], axis=0)


def dequantize_accumulate(qT, sT):
    """Fused dequant-multiply-accumulate: ``sum_r qT[r] * sT[r]`` over
    the leading (contributor) axis without expanding the int8 payload to
    fp32 in HBM. qT ``[rows, nb, blk]`` int8, sT ``[rows, nb]`` fp32 →
    ``[nb, blk]`` fp32."""
    rows, nb, blk = qT.shape
    # int8 in, a half-width working copy, fp32 [nb, blk] out
    _check_vmem("dequantize_accumulate", f"payload {list(qT.shape)}",
                3.0 * rows * nb * blk + 4.0 * nb * blk)
    with fused_span("DEQUANT", dequant_hbm_saved(rows, nb, blk)):
        return pl.pallas_call(
            _dequant_acc_kernel,
            out_shape=_out_struct((nb, blk), jnp.float32, qT, sT),
            interpret=_interpret(),
        )(qT, sT)


# ---------------------------------------------------------------------------
# Ring wire accounting: the fused rings move exactly the bytes the
# unfused collective would — (n-1) hops of the tile/shard — charged with
# the same per-device model as plan/accounting.py. Rank-major over the
# (pod, cross, local) axis tuple, nc of every n ring sends cross a host
# boundary, so that fraction is DCN-class.
# ---------------------------------------------------------------------------


def _acct_ring(axes_t, hop_bytes: float, hops: int) -> None:
    if not _acct_enabled():
        return
    from ..common import basics
    from .collective_ops import _axis_size

    sizes = {a: _axis_size(a) for a in axes_t}
    total = hop_bytes * hops
    if set(axes_t) == {basics.LOCAL_AXIS}:
        _acct("ici", total)
        return
    if basics.LOCAL_AXIS not in sizes:
        _acct("dcn", total)  # cross/pod-only ring: every hop is slow wire
        return
    # Of the n directed ring links (rank-major order), n/nl cross a host
    # boundary (the wrap from local index nl-1 to 0 of the next host).
    nl = max(1, sizes[basics.LOCAL_AXIS])
    _acct("dcn", total / nl)
    _acct("ici", total * (1.0 - 1.0 / nl))


# ---------------------------------------------------------------------------
# Fusion pair (a): matmul → reduce-scatter epilogue.
# ---------------------------------------------------------------------------


def fused_matmul_reduce_scatter(x, w, *, axes=None,
                                block_k: Optional[int] = None):
    """Reduce-scattered matmul: rank-major ``[M/world, N]`` shard of
    ``sum_r x_r @ w_r`` without materializing any rank's full ``[M, N]``
    partial product.

    The TP row-parallel / ZeRO gradient epilogue: each rank holds a
    per-rank ``x [M, K]`` and ``w [K, N]`` (e.g. activations × local
    weight rows, or ``h^T × dh`` for a data-parallel weight gradient
    whose reduce-scattered rows are exactly the ZeRO stage-2/3 gradient
    shard). A ``world``-step ring runs: at step ``i`` the Pallas tile
    kernel (:func:`_matmul_accumulate`) computes the row tile destined
    for rank ``(my + world - 1 - i) % world`` and accumulates it into
    the traveling partial-sum buffer, which then hops to the next rank
    (``lax.ppermute``); after the last step each rank holds its own
    fully-summed tile. Wire bytes equal the unfused reduce-scatter's
    ``(n-1)/n * M*N``; the saved HBM round-trip is
    :func:`matmul_rs_hbm_saved`.

    Must run inside ``hvd.shard_map``; ``M`` must divide by the world
    size (pad like ``plan_buckets(shard_multiple=world)``)."""
    axes_t = _resolve_axes(axes)
    M, K = x.shape
    N = w.shape[1]
    out_dtype = jnp.promote_types(x.dtype, w.dtype)
    if not axes_t:
        # Eager/world-of-one: the epilogue degenerates to the local tile.
        return jnp.dot(x, w).astype(out_dtype)
    from .collective_ops import _world_size

    n = _world_size(axes_t)
    if M % n:
        raise ValueError(
            f"fused_matmul_reduce_scatter: M={M} does not divide into "
            f"{n} row tiles — pad the leading dim to a world multiple "
            f"(plan_buckets(shard_multiple=world) idiom)")
    seg = M // n
    isz = jnp.dtype(out_dtype).itemsize
    _acct_ring(axes_t, float(seg) * N * isz, n - 1)
    my = lax.axis_index(axes_t)
    perm = [(r, (r + 1) % n) for r in range(n)]
    with fused_span("MATMUL_RS", matmul_rs_hbm_saved(M, N, n, isz)):
        x = _vary(x, axes_t, w)
        w = _vary(w, axes_t, x)
        acc = _vary(jnp.zeros((seg, N), out_dtype), axes_t, x, w)
        for i in range(n):
            dst = (my + n - 1 - i) % n
            xt = lax.dynamic_slice_in_dim(x, dst * seg, seg, 0)
            acc = _matmul_accumulate(xt, w, acc, block_k=block_k)
            if i < n - 1:
                acc = lax.ppermute(acc, axes_t, perm)
    return acc


# ---------------------------------------------------------------------------
# Fusion pair (b): all-gather → matmul prologue.
# ---------------------------------------------------------------------------


def fused_all_gather_matmul(x, w_shard, *, axes=None,
                            block_k: Optional[int] = None):
    """``x @ W`` where ``W`` lives as rank-major row shards
    (``w_shard [K/world, N]`` — the ZeRO-3 parameter layout), without
    materializing the gathered ``[K, N]`` weight.

    The ring all-gather is fused into the contraction: after ``i`` hops
    this rank holds shard ``(my - i) % world``, the Pallas tile kernel
    contracts it against the matching ``K``-column slab of ``x`` and
    accumulates into the local output while the shard hops onward — the
    arriving weight rows feed the next tile's matmul under the current
    tile's compute (T3's fine-grained prologue overlap). Wire bytes
    equal the unfused all-gather's ``(n-1)/n * K*N``; the saved HBM
    round-trip is :func:`ag_matmul_hbm_saved`.

    Returns ``[M, N]`` in the promoted dtype — device-varying (it feeds
    this rank's forward compute, like ``zero3_gather_params`` output).
    Must run inside ``hvd.shard_map`` with ``x.shape[1] ==
    w_shard.shape[0] * world``."""
    axes_t = _resolve_axes(axes)
    M, K = x.shape
    kseg, N = w_shard.shape
    out_dtype = jnp.promote_types(x.dtype, w_shard.dtype)
    if not axes_t:
        return jnp.dot(x, w_shard).astype(out_dtype)
    from .collective_ops import _world_size

    n = _world_size(axes_t)
    if K != kseg * n:
        raise ValueError(
            f"fused_all_gather_matmul: x has K={K} columns but the "
            f"shard ring gathers {kseg} x {n} = {kseg * n} weight rows "
            f"— w_shard must be the rank-major [K/world, N] row shard")
    isz = jnp.dtype(out_dtype).itemsize
    _acct_ring(axes_t, float(kseg) * N * isz, n - 1)
    my = lax.axis_index(axes_t)
    perm = [(r, (r + 1) % n) for r in range(n)]
    with fused_span("AG_MATMUL", ag_matmul_hbm_saved(K, N, n, isz)):
        x = _vary(x, axes_t, w_shard)
        w = _vary(w_shard, axes_t, x)
        acc = _vary(jnp.zeros((M, N), out_dtype), axes_t, x, w)
        for i in range(n):
            src = (my - i) % n  # whose rows we hold after i hops
            xt = lax.dynamic_slice_in_dim(x, src * kseg, kseg, 1)
            acc = _matmul_accumulate(xt, w, acc, block_k=block_k)
            if i < n - 1:
                w = lax.ppermute(w, axes_t, perm)
    return acc
