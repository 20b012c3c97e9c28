"""Pallas TPU flash attention: exact attention without the [T, T] round-trip.

The reference framework has no attention kernels at all (it is a CNN-era
data-parallel framework, SURVEY §5.7); its GPU analogue would be a fused
CUDA kernel. On TPU the hot op is the attention score/softmax/value chain:
``dense_attention`` (parallel/sequence.py) materializes a [B, H, T, T] fp32
score tensor twice (scores + probabilities) — at GPT-124M bench shapes
(B=16, H=12, T=1024) that is ~1.6 GB of HBM round-trip per layer, which
dwarfs the matmul time on a bandwidth-limited chip.

This module implements the standard flash-attention schedule as Pallas TPU
kernels (guide: /opt/skills/guides/pallas_guide.md):

* forward: grid (B·H, Tq/bq, Tk/bk); the k-block axis is innermost, so the
  per-q-block running max ``m``, normalizer ``l`` and output accumulator
  live in VMEM scratch across k-steps; scores never leave VMEM. Emits the
  logsumexp residual for the backward pass.
* backward: the split-kernel formulation — one kernel accumulates dQ over
  k-blocks, a second accumulates dK/dV over q-blocks — with the
  ``delta = rowsum(dO ⊙ O)`` precomputed as a cheap fused elementwise op
  in plain XLA. Both kernels recompute probabilities from q, k and the
  saved logsumexp (recompute-over-store: O(T·D) residuals instead of
  O(T²)).
* causal masking skips fully-masked k-blocks via ``pl.when`` (upper
  triangle costs nothing) when block positions are static; with runtime
  offsets (ring partials) the mask runs with global positions instead.
* :func:`flash_ring_attention` composes the kernels with sequence
  parallelism: K/V blocks rotate around the mesh axis via
  ``lax.ppermute`` while each ring step runs the flash kernel with
  global causal positions and partial outputs merge by logsumexp; the
  backward replays the ring with dk/dv accumulators traveling alongside
  their blocks (they arrive home after n rotations).

Everything is static-shaped; block sizes adapt to divide the sequence
(see ``_pick_block`` — a whole-sequence block covers anything <= the
preferred block, and long sequences with no 128-aligned divisor fall back
to the dense path). Off-TPU the kernels run in Pallas interpreter mode so
the CPU test suite exercises the identical code path.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (TPU backend)

_NEG_INF = -1e30  # finite: keeps running-max arithmetic NaN-free

# Large blocks amortize Mosaic's per-grid-cell overhead and give the MXU
# deep work per cell: a [1024, 1024] f32 score tile is 4 MB of VMEM —
# comfortably under the ~16 MB budget next to the q/k/v/o blocks and
# scratch — and measured on v5e (GPT-124M, seq 1024) block size is worth
# 2x end-to-end: 512-blocks beat the dense path by 28%, 1024-blocks add
# another ~9% (117.2k vs 107.7k tok/s). Tunable like the other HOROVOD_*
# knobs (e.g. for other chip generations' VMEM sizes).


def _block_knob(name: str, default: int) -> int:
    from ..common.config import _env_int

    v = _env_int(name, default)
    if v < 128:
        raise ValueError(
            f"{name}={v}: Pallas kernel blocks must be >= 128 "
            f"(MXU/lane tile)")
    return v


_DEF_BLOCK_Q = _block_knob("HOROVOD_FLASH_BLOCK_Q", 1024)
_DEF_BLOCK_K = _block_knob("HOROVOD_FLASH_BLOCK_K", 1024)


def _resolve_blocks(B, Tq, Tk, H, D, dtype, causal):
    """Block sizes for a flash call that pinned neither block: env knobs
    win; otherwise the kernel autotuner's cached/swept choice (TPU,
    single-process); otherwise the hand-tuned defaults. Multi-process
    SPMD only READS the autotune cache (a sweep could pick different
    blocks on different hosts → divergent programs); ship the cache file
    to every host to use tuned blocks there."""
    import os

    # `or` (not `in`): an empty string means unset, the shell idiom
    # _env_int also honors — consistent with the xent knobs.
    if (os.environ.get("HOROVOD_FLASH_BLOCK_Q")
            or os.environ.get("HOROVOD_FLASH_BLOCK_K")):
        return (_block_knob("HOROVOD_FLASH_BLOCK_Q", 1024),
                _block_knob("HOROVOD_FLASH_BLOCK_K", 1024))
    from . import kernel_autotune

    if not kernel_autotune.enabled():
        return _DEF_BLOCK_Q, _DEF_BLOCK_K
    return kernel_autotune.flash_blocks(
        B, Tq, Tk, H, D, dtype, causal,
        (_DEF_BLOCK_Q, _DEF_BLOCK_K), _pick_block)


def _interpret() -> bool:
    """Run in interpreter mode off-TPU (CPU test suite)."""
    return jax.default_backend() != "tpu"


def _out_struct(shape, dtype, *operands):
    """ShapeDtypeStruct whose varying-manual-axes are the union of the
    operands' — required inside ``jax.shard_map`` (check_vma), harmless
    outside (vma=frozenset())."""
    from .collective_ops import _vma

    vma = frozenset().union(*[_vma(x) for x in operands])
    return jax.ShapeDtypeStruct(shape, dtype, vma=vma)


def _harmonize_vma(*arrays):
    """pcast every array to the union of the group's varying-manual-axes.

    Inside ``shard_map``, kernel operands must agree on vma (standard XLA
    primitives get automatic ``pvary`` insertion; pallas kernel jaxprs do
    not). The pcast is a type-level broadcast — free forward, and its
    transpose is the psum a replicated operand's cotangent needs anyway
    (identical to what autodiff inserts for the dense formulation).
    No-op outside shard_map."""
    from .collective_ops import _vma, pvary_missing

    union = frozenset().union(*[_vma(a) for a in arrays])
    if not union:
        return arrays
    axes = tuple(sorted(union))
    return tuple(pvary_missing(a, axes) for a in arrays)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _scalar_spec():
    """Offset operand: one (8, 128) int32 tile, same block every grid step.

    A (1, 1) SMEM scalar would be the idiomatic choice, but jax 0.9's HLO
    interpreter (the CPU test path) rejects pallas calls mixing SMEM scalar
    operands with sharded tensor operands under shard_map's vma checking —
    a tile-aligned VMEM operand behaves identically on both backends and
    costs 4 KB."""
    return pl.BlockSpec((1, 8, 128), lambda b, i, j: (0, 0, 0))


def _as_scalar(x):
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32), (1, 8, 128))


def _causal_mask(s, qoff, koff, i, j, bq, bk):
    """Mask with GLOBAL positions: local block position + runtime offset.
    Offsets arrive as operands (see ``_scalar_spec``) so ring/sharded
    callers can pass traced values (e.g. ``axis_index * T_local``)."""
    qpos = qoff + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = koff + j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(qpos >= kpos, s, _NEG_INF)


def _run_pred(causal, static_skip, i, j, bq, bk):
    """Should block (i, j) compute? A k block contributes iff its first key
    position <= the q block's last query position — decidable statically
    only when offsets are zero (static_skip). With runtime offsets every
    block runs and the global-position mask does the work (callers skip
    whole fully-masked PARTIALS host-side instead: see _ring_fwd_impl;
    mixing the varying offset operands with program-id arithmetic in a
    pl.when predicate trips vma checking). The always-run case returns a
    traced truth (a literal ``True`` would inline the body, which equally
    trips the HLO interpreter's vma checks under shard_map)."""
    if causal and static_skip:
        return j * bk <= i * bq + bq - 1
    return j >= 0


def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, bq, bk, nk, static_skip):
    i = pl.program_id(1)   # q block
    j = pl.program_id(2)   # k block (innermost: scratch carries across j)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _run_pred(causal, static_skip, i, j, bq, bk)

    @pl.when(run)
    def _body():
        q = q_ref[0]                                    # [bq, D]
        k = k_ref[0]                                    # [bk, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            s = _causal_mask(s, qoff_ref[...][0, 0, 0], koff_ref[...][0, 0, 0], i, j, bq, bk)

        m_prev = m_scr[:, 0:1]                          # [bq, 1]
        l_prev = l_scr[:, 0:1]
        m_cur = jnp.max(s, axis=1, keepdims=True)       # [bq, 1]
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        # Fully-masked rows (possible when a ring partial sees a k block
        # entirely in its causal future): m_new stays at _NEG_INF and
        # s - m_new == 0 would wrongly give p = 1 — zero those rows.
        p = jnp.where(m_new > _NEG_INF / 2, jnp.exp(s - m_new), 0.0)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    j_last = jnp.minimum(nk - 1, (i * bq + bq - 1) // bk) \
        if (causal and static_skip) else nk - 1

    @pl.when(j == j_last)
    def _finish():
        m = m_scr[:, 0:1]
        l = l_scr[:, 0:1]
        # Fully-masked rows have l == 0: emit o = 0 and lse = -inf-like so
        # a ring merge weights them out. Visible rows always have l > 0
        # (a causal row sees at least its own token).
        safe_l = jnp.maximum(l, 1e-30)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        # lse carries a sublane dim of 8 (Mosaic block-mapping minimum for
        # the trailing-two dims); value broadcast across it.
        lse_ref[0] = jnp.broadcast_to(
            jnp.where(l > 0, m + jnp.log(safe_l), _NEG_INF),
            lse_ref.shape[1:])


def _flash_fwd(q, k, v, scale, causal, bq, bk, q_off=0, k_off=0,
               static_skip=True):
    """q,k,v: [BH, T, D] → (o [BH, Tq, D], lse [BH, Tq, 8] f32).

    ``q_off``/``k_off`` are global positions of the first query/key token
    (may be traced, e.g. ``lax.axis_index(...) * T_local`` under a ring);
    pass ``static_skip=False`` whenever they can be nonzero."""
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // bq, Tk // bk
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nk=nk, static_skip=static_skip)
    return pl.pallas_call(
        kernel,
        grid=(BH, nq, nk),
        in_specs=[
            _scalar_spec(),
            _scalar_spec(),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _out_struct((BH, Tq, D), q.dtype, q, k, v, q_off, k_off),
            _out_struct((BH, Tq, 8), jnp.float32, q, k, v, q_off, k_off),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max m
            pltpu.VMEM((bq, 128), jnp.float32),   # running normalizer l
            pltpu.VMEM((bq, D), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="hvd_flash_fwd",
    )(_as_scalar(q_off), _as_scalar(k_off), q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, acc_scr,
                   *, scale, causal, bq, bk, nk, static_skip):
    i = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = _run_pred(causal, static_skip, i, j, bq, bk)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qoff_ref[...][0, 0, 0], koff_ref[...][0, 0, 0], i, j, bq, bk)
        # Masked entries: s = -1e30 and finite lse → p = 0 automatically;
        # fully-masked rows have lse = -1e30 from the forward, giving
        # exp(-1e30 - (-1e30)) = 1 on masked entries — zero them.
        p = jnp.where(lse_ref[0, :, 0:1] > _NEG_INF / 2,
                      jnp.exp(s - lse_ref[0, :, 0:1]), 0.0)  # [bq, bk]
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = p * (dp - delta_ref[0, :, 0:1])
        acc_scr[:] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    j_last = jnp.minimum(nk - 1, (i * bq + bq - 1) // bk) \
        if (causal and static_skip) else nk - 1

    @pl.when(j == j_last)
    def _finish():
        dq_ref[0] = (acc_scr[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, bq, bk, nq, static_skip):
    j = pl.program_id(1)   # k block
    i = pl.program_id(2)   # q block (innermost: scratch carries across i)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _run_pred(causal, static_skip, i, j, bq, bk)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qoff_ref[...][0, 0, 0], koff_ref[...][0, 0, 0], i, j, bq, bk)
        p = jnp.where(lse_ref[0, :, 0:1] > _NEG_INF / 2,
                      jnp.exp(s - lse_ref[0, :, 0:1]), 0.0)  # [bq, bk]
        do = do_ref[0]                                   # [bq, D]
        dv_scr[:] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]
        dp = jax.lax.dot_general(
            do, v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = p * (dp - delta_ref[0, :, 0:1])
        dk_scr[:] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bk, D]

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = (dk_scr[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _prep_residuals(o, do):
    """delta = rowsum(dO ⊙ O) with the broadcast sublane dim."""
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                             # [BH, Tq]
    return jnp.broadcast_to(delta[..., None], (*delta.shape, 8))


def _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, bq, bk,
                  q_off=0, k_off=0, static_skip=True):
    BH, Tq, D = q.shape
    nq, nk = Tq // bq, k.shape[1] // bk
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, static_skip=static_skip),
        grid=(BH, nq, nk),
        in_specs=[
            _scalar_spec(),
            _scalar_spec(),
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),   # v
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),   # do
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),   # lse
            pl.BlockSpec((1, bq, 8), lambda b, i, j: (b, i, 0)),   # delta
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=_out_struct((BH, Tq, D), q.dtype, q, k, v, do, lse,
                              delta, q_off, k_off),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="hvd_flash_bwd_dq",
    )(_as_scalar(q_off), _as_scalar(k_off), q, k, v, do, lse, delta)


def _flash_bwd_dkv(q, k, v, do, lse, delta, scale, causal, bq, bk,
                   q_off=0, k_off=0, static_skip=True):
    BH, Tq, D = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // bq, Tk // bk
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, static_skip=static_skip),
        grid=(BH, nk, nq),
        in_specs=[
            _scalar_spec(),
            _scalar_spec(),
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),   # q
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),   # k
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),   # v
            pl.BlockSpec((1, bq, D), lambda b, j, i: (b, i, 0)),   # do
            pl.BlockSpec((1, bq, 8), lambda b, j, i: (b, i, 0)),   # lse
            pl.BlockSpec((1, bq, 8), lambda b, j, i: (b, i, 0)),   # delta
        ],
        out_specs=[
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            _out_struct((BH, Tk, D), k.dtype, q, k, v, do, lse, delta,
                        q_off, k_off),
            _out_struct((BH, Tk, D), v.dtype, q, k, v, do, lse, delta,
                        q_off, k_off),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, D), jnp.float32),
            pltpu.VMEM((bk, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="hvd_flash_bwd_dkv",
    )(_as_scalar(q_off), _as_scalar(k_off), q, k, v, do, lse, delta)


def _flash_bwd(q, k, v, o, lse, do, scale, causal, bq, bk):
    delta = _prep_residuals(o, do)
    dq = _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, bq, bk)
    dk, dv = _flash_bwd_dkv(q, k, v, do, lse, delta, scale, causal, bq, bk)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------


def _pick_block(T: int, preferred: int) -> Optional[int]:
    """Largest legal block size for a sequence of length T.

    T <= preferred: the whole sequence is one block (block dims equal to
    the array dims are always accepted by Mosaic, aligned or not).
    Otherwise the largest multiple of 128 <= preferred that divides T.
    None -> no legal blocking; caller falls back to the dense path.
    """
    if T <= preferred:
        return T
    for b in range(preferred - preferred % 128, 127, -128):
        if T % b == 0:
            return b
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, bq, bk):
    o, _ = _flash_fwd(q, k, v, scale, causal, bq, bk)
    return o


def _flash_vjp_fwd(q, k, v, scale, causal, bq, bk):
    o, lse = _flash_fwd(q, k, v, scale, causal, bq, bk)
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(scale, causal, bq, bk, res, g):
    q, k, v, o, lse = res
    dq, dk, dv = _flash_bwd(q, k, v, o, lse, g, scale, causal, bq, bk)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


# ---------------------------------------------------------------------------
# ring composition: sequence-parallel flash attention
# ---------------------------------------------------------------------------


def _pack(x):
    """[B, T, H, D] → [B·H, T, D]."""
    B, T, H, D = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, T, D)


def _unpack(x, B, H):
    BH, T, D = x.shape
    return jnp.transpose(x.reshape(B, H, T, D), (0, 2, 1, 3))


def _ring_axes(axis, *tensors):
    from .collective_ops import _vma

    ring = {axis} if isinstance(axis, str) else set(axis)
    extra = frozenset().union(*[_vma(t) for t in tensors])
    return tuple(sorted(ring | extra))


def _ring_fwd_impl(q, k, v, axis, scale, causal, bq, bk):
    """Packed [BH, T_local, D] ring forward → (o f32, merged lse [BH, T])."""
    from jax import lax

    from ..parallel.sequence import _axis_size
    from .collective_ops import pvary_missing

    n = _axis_size(axis)
    my = lax.axis_index(axis)
    T_local = q.shape[1]
    perm = [(r, (r + 1) % n) for r in range(n)]
    axes_t = _ring_axes(axis, q, k, v)

    def _vary(x):
        return pvary_missing(x, axes_t)

    def merge(o, lse, k_blk, v_blk, i):
        # Blocks travel +1 per rotation: after i steps we hold (my - i)'s.
        src = (my - i) % n

        def compute(k_blk, v_blk):
            o_i, lse_i = _flash_fwd(
                q, k_blk, v_blk, scale, causal, bq, bk,
                q_off=my * T_local, k_off=src * T_local, static_skip=False)
            return o_i.astype(jnp.float32), lse_i[:, :, 0]  # [BH,T,D],[BH,T]

        if causal:
            # A block from a later shard (src > my) is entirely in the
            # causal future: skip the whole kernel call on this chip —
            # roughly half the ring steps cost nothing.
            def empty(k_blk, v_blk):
                return (_vary(jnp.zeros(q.shape, jnp.float32)),
                        _vary(jnp.full(q.shape[:2], _NEG_INF, jnp.float32)))

            o_i, lse_i = lax.cond(src > my, empty, compute, k_blk, v_blk)
        else:
            o_i, lse_i = compute(k_blk, v_blk)
        lse_new = jnp.logaddexp(lse, lse_i)
        w_old = jnp.exp(lse - lse_new)[..., None]
        w_new = jnp.exp(lse_i - lse_new)[..., None]
        return o * w_old + o_i * w_new, lse_new

    def step(carry, i):
        o, lse, k_blk, v_blk = carry
        o, lse = merge(o, lse, k_blk, v_blk, i)
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (o, lse, k_blk, v_blk), None

    o0 = _vary(jnp.zeros(q.shape, jnp.float32))
    lse0 = _vary(jnp.full(q.shape[:2], _NEG_INF, jnp.float32))
    # Last iteration peeled: its rotation result would be discarded, and
    # for n=1 the scan is empty and no ppermute is emitted at all.
    (o, lse, k_blk, v_blk), _ = jax.lax.scan(
        step, (o0, lse0, k, v), jnp.arange(n - 1))
    o, lse = merge(o, lse, k_blk, v_blk, n - 1)
    return o, lse


def _ring_bwd_impl(q, k, v, o, lse, do, axis, scale, causal, bq, bk):
    """Ring backward: dq accumulates locally; dk/dv accumulators travel the
    ring WITH their k/v blocks and arrive home after n rotations."""
    from jax import lax

    from ..parallel.sequence import _axis_size
    from .collective_ops import pvary_missing

    n = _axis_size(axis)
    my = lax.axis_index(axis)
    T_local = q.shape[1]
    perm = [(r, (r + 1) % n) for r in range(n)]
    axes_t = _ring_axes(axis, q, k, v, o, lse, do)

    def _vary(x):
        return pvary_missing(x, axes_t)

    lse8 = jnp.broadcast_to(lse[..., None], (*lse.shape, 8))
    delta = _prep_residuals(o, do)

    def contrib(dq, k_blk, v_blk, dk_blk, dv_blk, i):
        src = (my - i) % n

        def compute(k_blk, v_blk):
            q_off, k_off = my * T_local, src * T_local
            dq_i = _flash_bwd_dq(q, k_blk, v_blk, do, lse8, delta, scale,
                                 causal, bq, bk, q_off=q_off, k_off=k_off,
                                 static_skip=False)
            dk_i, dv_i = _flash_bwd_dkv(q, k_blk, v_blk, do, lse8, delta,
                                        scale, causal, bq, bk, q_off=q_off,
                                        k_off=k_off, static_skip=False)
            return (dq_i.astype(jnp.float32), dk_i.astype(jnp.float32),
                    dv_i.astype(jnp.float32))

        if causal:
            # Fully-future block: no gradient flows either way — skip both
            # kernels on this chip (mirrors the forward's host-side skip).
            def empty(k_blk, v_blk):
                zero = lambda x: _vary(jnp.zeros(x.shape, jnp.float32))
                return zero(q), zero(k_blk), zero(v_blk)

            dq_i, dk_i, dv_i = lax.cond(src > my, empty, compute,
                                        k_blk, v_blk)
        else:
            dq_i, dk_i, dv_i = compute(k_blk, v_blk)
        return dq + dq_i, dk_blk + dk_i, dv_blk + dv_i

    def step(carry, i):
        dq, k_blk, v_blk, dk_blk, dv_blk = carry
        dq, dk_blk, dv_blk = contrib(dq, k_blk, v_blk, dk_blk, dv_blk, i)
        # dk/dv accumulators travel with their blocks; k/v feed the next
        # step's kernels.
        dk_blk = lax.ppermute(dk_blk, axis, perm)
        dv_blk = lax.ppermute(dv_blk, axis, perm)
        k_blk = lax.ppermute(k_blk, axis, perm)
        v_blk = lax.ppermute(v_blk, axis, perm)
        return (dq, k_blk, v_blk, dk_blk, dv_blk), None

    zeros = lambda x: _vary(jnp.zeros(x.shape, jnp.float32))
    # Last iteration peeled: dk/dv still need their final hop home, but
    # the k/v rotation result would be discarded.
    (dq, k_blk, v_blk, dk_blk, dv_blk), _ = jax.lax.scan(
        step, (zeros(q), k, v, zeros(k), zeros(v)), jnp.arange(n - 1))
    dq, dk_blk, dv_blk = contrib(dq, k_blk, v_blk, dk_blk, dv_blk, n - 1)
    dk = lax.ppermute(dk_blk, axis, perm)
    dv = lax.ppermute(dv_blk, axis, perm)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring(q, k, v, axis, scale, causal, bq, bk):
    o, _ = _ring_fwd_impl(q, k, v, axis, scale, causal, bq, bk)
    return o.astype(q.dtype)


def _ring_vjp_fwd(q, k, v, axis, scale, causal, bq, bk):
    o, lse = _ring_fwd_impl(q, k, v, axis, scale, causal, bq, bk)
    o = o.astype(q.dtype)
    return o, (q, k, v, o, lse)


def _ring_vjp_bwd(axis, scale, causal, bq, bk, res, g):
    q, k, v, o, lse = res
    return _ring_bwd_impl(q, k, v, o, lse, g, axis, scale, causal, bq, bk)


_ring.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def flash_ring_attention(q, k, v, *, axis, causal: bool = True,
                         scale: Optional[float] = None,
                         block_q: int = _DEF_BLOCK_Q,
                         block_k: int = _DEF_BLOCK_K):
    """Sequence-parallel exact attention: flash kernels on a ppermute ring.

    The fused long-context path — each chip holds a contiguous
    [B, T/n, H, D] sequence shard; K/V blocks rotate around the mesh axis
    (``lax.ppermute`` riding ICI neighbours) and every ring step runs the
    Pallas flash kernel with GLOBAL causal positions, merging partial
    outputs by logsumexp. Backward replays the ring with the dq/dk/dv
    kernels; dk/dv accumulators travel with their blocks and arrive home
    after n rotations. Combines :func:`ring_attention`'s O(T/n) per-chip
    sequence memory with the flash kernel's VMEM-resident scores (the XLA
    ring materializes [T/n, T/n] f32 score tiles in HBM each step).

    Same layout/semantics as :func:`ring_attention`; must run inside
    ``jax.shard_map`` with the sequence sharded on ``axis``.
    """
    from ..parallel.sequence import _axis_size

    B, T_local, H, D = q.shape
    n = _axis_size(axis)
    if n == 1:
        return flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k)
    if isinstance(axis, list):
        axis = tuple(axis)  # hashable for the custom_vjp nondiff arg
    if block_q < 128 or block_k < 128:
        raise ValueError(
            f"block_q/block_k must be >= 128 (MXU/lane tile), got "
            f"{block_q}/{block_k}")
    bq, bk = _pick_block(T_local, block_q), _pick_block(T_local, block_k)
    if bq is None or bk is None:
        from ..parallel.sequence import ring_attention

        return ring_attention(q, k, v, axis=axis, causal=causal,
                              scale=scale)
    scale_f = float(scale) if scale is not None else D ** -0.5
    with jax.named_scope("hvd.flash_attention"):
        o = _ring(_pack(q), _pack(k), _pack(v), axis, scale_f, causal,
                  bq, bk)
        return _unpack(o, B, H)


def flash_attention(q, k, v, *, causal: bool = True,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Exact attention with the flash schedule. Layout [B, T, H, D].

    Differentiable (custom VJP with Pallas backward kernels). Block sizes
    shrink to a divisor of the sequence when needed (a single whole-sequence
    block is always legal — Mosaic accepts block dims equal to the array
    dim); only a long sequence with no 128-aligned divisor falls back to
    the dense path — numerics are identical either way.

    ``block_q``/``block_k`` default to the kernel autotuner's choice for
    this (shape, chip) — swept once, cached on disk
    (ops/kernel_autotune.py) — unless the ``HOROVOD_FLASH_BLOCK_Q/K``
    knobs pin them or the caller passes explicit values.
    """
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if causal and Tq != Tk:
        raise ValueError(
            f"causal flash attention needs Tq == Tk, got {Tq} != {Tk}")
    if block_q is None and block_k is None:
        block_q, block_k = _resolve_blocks(B, Tq, Tk, H, D, q.dtype,
                                           causal)
    else:
        block_q = _DEF_BLOCK_Q if block_q is None else block_q
        block_k = _DEF_BLOCK_K if block_k is None else block_k
    if block_q < 128 or block_k < 128:
        raise ValueError(
            f"block_q/block_k must be >= 128 (MXU/lane tile), got "
            f"{block_q}/{block_k}")
    bq, bk = _pick_block(Tq, block_q), _pick_block(Tk, block_k)
    if bq is None or bk is None:
        from ..parallel.sequence import dense_attention

        return dense_attention(q, k, v, causal=causal, scale=scale)
    scale = float(scale) if scale is not None else D ** -0.5

    # Outside the custom_vjp call, so that the backward kernels and the
    # [B, T, H, D] <-> [BH, T, D] layout traffic carry the scope too.
    with jax.named_scope("hvd.flash_attention"):
        qp, kp, vp = _harmonize_vma(_pack(q), _pack(k), _pack(v))
        o = _flash(qp, kp, vp, scale, causal, bq, bk)
        return jnp.transpose(o.reshape(B, H, Tq, D), (0, 2, 1, 3))
