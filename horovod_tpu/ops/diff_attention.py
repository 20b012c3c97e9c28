"""The elementwise halves of differential attention, each one pass over
row-major ``[B, T, C]`` with a custom VJP (docs/diff_attention.md).

Differential attention (``models/sambay.py`` ``_DiffAttention``) pairs the
heads up, ``(2p, 2p + 1)``, runs both softmax maps of a pair as ONE flash
call at head width ``W = 2 D``, and subtracts. Around that call stand:

* :func:`lay_in_halves`: q ``[B, T, H * D]`` -> ``[B, T, H * W]``, head
  ``2p + e`` holding its ``D`` values in half ``e`` of a ``W``-wide head and
  zeros in the other half. The pair's ``W`` input lanes
  ``[q_2p | q_2p+1]`` become the ``2 W`` output lanes
  ``[q_2p | 0 | 0 | q_2p+1]``: two copies of the same lanes under a lane
  mask, no value changes its lane.
* :func:`diff_combine`: o ``[B, T, H * W]`` -> ``[B, T, H * D]``,
  ``a = o[2p] - lam * o[2p+1]`` over the pair's two ``W``-wide heads,
  ``a * rsqrt(mean(a^2) + eps) * scale`` over the ``W`` lanes, all in
  float32, rounded once to o's type.

On a TPU, where ``W`` is a multiple of 128 lanes, each direction of each is
one Pallas kernel (``hvd_diff_lay_fwd`` / ``_bwd``, ``hvd_diff_combine_fwd``
/ ``_bwd``) over blocks of rows of the arrays as they lie: a pair is whole
128-lane columns of a row, so no view with a dimension of 2 or of ``H / 2``
reaches XLA, no float32 array reaches HBM and every operand moves once.
The backward of :func:`diff_combine` keeps nothing but its operands: it
makes ``a`` and the row statistics again in VMEM, writes ``do`` once and a
block's float32 sums for ``dlam`` and ``dscale`` (eight rows of ``W`` lanes
a block, added outside). Elsewhere (off the TPU, or ``W`` no multiple of
128) the same functions run the same arithmetic as ``jax.numpy`` over a
``[B, T, H / 2, 2, W]`` view, the hand-written backward included. Trace-time
counter: ``diff_attention.path{path=kernel|xla}``, once a call of either.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _flash
from .flash_attention import _harmonize_vma, _out_struct

_LANES, _SUBLANES = 128, 8
#: Rows of ``[B, T, C]`` a grid step takes; rows of them, and at most so many
#: pairs of heads (a divisor of their number), that a trip of the kernels'
#: loops works on: enough independent work to hide a pair's chain of a lane
#: sum and an rsqrt, five pairs as fast as all twenty written out. Timed
#: on the chip beside other choices by ``scripts/diff_kernel_times.py
#: --blocks``.
BLOCK_ROWS, CHUNK_ROWS, PAIR_UNROLL = 512, 64, 5
_VMEM_LIMIT = 64 * 2 ** 20
_SEMANTICS = ("parallel", "parallel")


def _interpret() -> bool:
    """The flash kernels' answer, asked each time: a compile for a described
    chip (benchmarks/rehearse_compile.py) sets theirs, and the step's
    kernels follow together."""
    return _flash._interpret()


def _runs_kernels(W: int) -> bool:
    return not _interpret() and W % _LANES == 0


# -- the arithmetic, on a pair's two W-wide heads ------------------------------

def _pair_forward(o0, o1, lam, scale, eps):
    """(y, a / rms, 1 / rms) in float32 from the pair's heads ``[..., W]``."""
    f32 = jnp.float32
    a = o0.astype(f32) - lam * o1.astype(f32)
    r = lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + eps)
    n = a * r
    return n * scale, n, r


def _pair_backward(o0, o1, g, lam, scale, eps):
    """(do0, do1, dlam's terms, dscale's terms) in float32 ``[..., W]``:
    the statistics are made again from the operands."""
    _, n, r = _pair_forward(o0, o1, lam, scale, eps)
    g = g.astype(jnp.float32)
    dn = g * scale
    da = r * (dn - n * jnp.mean(dn * n, -1, keepdims=True))
    return da, -(lam * da), -(o1.astype(jnp.float32) * da), g * n


def _half_mask(shape, D):
    """Whether a lane of a ``2 D``-wide head lies in its first half."""
    return lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) < D


# -- plain jax.numpy -----------------------------------------------------------

def _pairs(x, W):
    """``[B, T, n * 2 W]`` seen as ``[B, T, n, 2, W]``."""
    return x.reshape(x.shape[:2] + (-1, 2, W))


def _lay_fwd_xla(q, D):
    first = _half_mask((2 * D,), D)
    zero = jnp.zeros((), q.dtype)
    x = q.reshape(q.shape[:2] + (-1, 1, 2 * D))
    both = jnp.stack([first, ~first])                      # [2, W]
    return jnp.where(both, x, zero).reshape(q.shape[:2] + (-1,))


def _lay_bwd_xla(g, D):
    g = _pairs(g, 2 * D)
    return jnp.where(_half_mask((2 * D,), D), g[..., 0, :],
                     g[..., 1, :]).reshape(g.shape[:2] + (-1,))


def _combine_fwd_xla(o, lam, scale, eps):
    o5 = _pairs(o, scale.shape[0])
    y, _, _ = _pair_forward(o5[..., 0, :], o5[..., 1, :], lam, scale, eps)
    return y.astype(o.dtype).reshape(o.shape[:2] + (-1,))


def _combine_bwd_xla(o, g, lam, scale, eps):
    W = scale.shape[0]
    o5 = _pairs(o, W)
    do0, do1, dlam, dscale = _pair_backward(
        o5[..., 0, :], o5[..., 1, :], g.reshape(o5.shape[:3] + (W,)), lam,
        scale, eps)
    do = jnp.stack([do0, do1], -2).astype(o.dtype).reshape(o.shape)
    return do, dlam.sum(), dscale.sum((0, 1, 2))


# -- the kernels ---------------------------------------------------------------

def _chunk_rows(block_rows):
    """Rows a trip of a kernel's loop over its block's rows."""
    return CHUNK_ROWS if block_rows % CHUNK_ROWS == 0 else block_rows


def _over_block(block_rows, pairs, body, carry=0):
    """``body(rows, first row, pair, carry) -> carry`` for every chunk of
    the block's rows and, inside it, every pair of heads: two
    ``fori_loop``s, up to ``PAIR_UNROLL`` pairs a trip of the inner one. Loops
    and not a body written out over the pairs, because a kernel's body is
    traced and lowered in every process, a warm compile cache or not:
    written out over 20 pairs the four bodies cost the cell 5.5 s of its
    35 s warm set-up (docs/diff_attention.md)."""
    rows = _chunk_rows(block_rows)
    group = max(g for g in range(1, PAIR_UNROLL + 1) if pairs % g == 0)

    def over_rows(c, carry):
        start = pl.multiple_of(c * rows, rows)
        at = pl.ds(start, rows)

        def over_pairs(t, carry):
            for j in range(group):
                carry = body(at, start, t * group + j, carry)
            return carry

        return lax.fori_loop(0, pairs // group, over_pairs, carry)

    return lax.fori_loop(0, block_rows // rows, over_rows, carry)


def _head(p, W):
    """The columns of head ``p`` among ``W``-wide heads."""
    return pl.ds(pl.multiple_of(p * W, W), W)


def _lay_fwd_kernel(q_ref, out_ref, *, pairs, D):
    W = 2 * D
    rows = _chunk_rows(q_ref.shape[0])
    first = _half_mask((rows, W), D)
    zero = jnp.zeros((rows, W), q_ref.dtype)

    def body(at, start, p, carry):
        x = q_ref[at, _head(p, W)]
        out_ref[at, _head(2 * p, W)] = jnp.where(first, x, zero)
        out_ref[at, _head(2 * p + 1, W)] = jnp.where(first, zero, x)
        return carry

    _over_block(q_ref.shape[0], pairs, body)


def _lay_bwd_kernel(g_ref, dq_ref, *, pairs, D):
    W = 2 * D
    first = _half_mask((_chunk_rows(g_ref.shape[0]), W), D)

    def body(at, start, p, carry):
        dq_ref[at, _head(p, W)] = jnp.where(
            first, g_ref[at, _head(2 * p, W)], g_ref[at, _head(2 * p + 1, W)])
        return carry

    _over_block(g_ref.shape[0], pairs, body)


def _combine_fwd_kernel(lam_ref, scale_ref, o_ref, out_ref, *, pairs, eps):
    W = scale_ref.shape[1]
    lam, scale = lam_ref[0], scale_ref[...]

    def body(at, start, p, carry):
        y, _, _ = _pair_forward(o_ref[at, _head(2 * p, W)],
                                o_ref[at, _head(2 * p + 1, W)], lam, scale,
                                eps)
        out_ref[at, _head(p, W)] = y.astype(out_ref.dtype)
        return carry

    _over_block(o_ref.shape[0], pairs, body)


def _combine_bwd_kernel(lam_ref, scale_ref, o_ref, g_ref, do_ref, dlam_ref,
                        dscale_ref, *, pairs, eps, T):
    W = scale_ref.shape[1]
    block_rows = o_ref.shape[0]
    rows = _chunk_rows(block_rows)
    lam, scale = lam_ref[0], scale_ref[...]
    first_row = pl.program_id(1) * block_rows
    row = lax.broadcasted_iota(jnp.int32, (rows, W), 0)

    def body(at, start, p, sums):
        do0, do1, lam_terms, scale_terms = _pair_backward(
            o_ref[at, _head(2 * p, W)], o_ref[at, _head(2 * p + 1, W)],
            g_ref[at, _head(p, W)], lam, scale, eps)
        do_ref[at, _head(2 * p, W)] = do0.astype(do_ref.dtype)
        do_ref[at, _head(2 * p + 1, W)] = do1.astype(do_ref.dtype)
        if T % block_rows:
            # The rows of the last block past the array's end hold anything.
            inside = row + (first_row + start) < T
            lam_terms = jnp.where(inside, lam_terms, 0.0)
            scale_terms = jnp.where(inside, scale_terms, 0.0)
        return sums[0] + lam_terms, sums[1] + scale_terms

    zeros = jnp.zeros((rows, W), jnp.float32)
    dlam, dscale = _over_block(block_rows, pairs, body, (zeros, zeros))
    # Eight rows of lanes a block: register adds, no reduction in a register.
    fold = (lambda s: s.reshape(-1, _SUBLANES, W).sum(0)) \
        if rows % _SUBLANES == 0 else (
            lambda s: jnp.pad(s.sum(0, keepdims=True),
                              ((0, _SUBLANES - 1), (0, 0))))
    dlam_ref[...] = fold(dlam)
    dscale_ref[...] = fold(dscale)


def _call(kernel, name, operands, row_operands, outs, T, block_rows,
          interpret):
    """``pallas_call`` over (batch, blocks of rows): ``operands`` whole
    (``lam`` in SMEM, ``scale`` in VMEM), then ``row_operands``
    ``[B, T, C]`` a block of rows a step; ``outs`` are ``(C, dtype)`` of
    outputs ``[B, T, C]``, or None for a block's float32 sums over
    ``scale``'s lanes."""
    B = row_operands[0].shape[0]
    block_rows = min(block_rows or BLOCK_ROWS, T)
    blocks = pl.cdiv(T, block_rows)

    def rows_spec(C):
        return pl.BlockSpec((None, block_rows, C), lambda b, i: (b, i, 0))

    whole = [pl.BlockSpec(memory_space=pltpu.SMEM),
             pl.BlockSpec(operands[1].shape, lambda b, i: (0, 0))
             ] if operands else []
    everything = (*operands, *row_operands)
    out_shape, out_specs = [], []
    for out in outs:
        if out is None:
            W = operands[1].shape[1]
            out_shape.append(_out_struct((B, blocks, _SUBLANES, W),
                                         jnp.float32, *everything))
            out_specs.append(pl.BlockSpec((None, None, _SUBLANES, W),
                                          lambda b, i: (b, i, 0, 0)))
        else:
            out_shape.append(_out_struct((B, T, out[0]), out[1],
                                         *everything))
            out_specs.append(rows_spec(out[0]))
    return pl.pallas_call(
        kernel, grid=(B, blocks),
        in_specs=whole + [rows_spec(x.shape[2]) for x in row_operands],
        out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret, name=name)(*everything)


def _lay_fwd_call(q, D, block_rows=None, interpret=False):
    B, T, C = q.shape
    kernel = functools.partial(_lay_fwd_kernel, pairs=C // (2 * D), D=D)
    return _call(kernel, "hvd_diff_lay_fwd", (), (q,), [(2 * C, q.dtype)],
                 T, block_rows, interpret)[0]


def _lay_bwd_call(g, D, block_rows=None, interpret=False):
    B, T, C = g.shape
    kernel = functools.partial(_lay_bwd_kernel, pairs=C // (4 * D), D=D)
    return _call(kernel, "hvd_diff_lay_bwd", (), (g,), [(C // 2, g.dtype)],
                 T, block_rows, interpret)[0]


def _whole_operands(lam, scale):
    f32 = jnp.float32
    return lam.astype(f32).reshape(1), scale.astype(f32).reshape(1, -1)


def _combine_fwd_call(o, lam, scale, eps, block_rows=None,
                      interpret=False):
    B, T, C = o.shape
    kernel = functools.partial(_combine_fwd_kernel,
                               pairs=C // (2 * scale.shape[0]), eps=eps)
    return _call(kernel, "hvd_diff_combine_fwd", _whole_operands(lam, scale),
                 (o,), [(C // 2, o.dtype)], T, block_rows, interpret)[0]


def _combine_bwd_call(o, g, lam, scale, eps, block_rows=None,
                      interpret=False):
    B, T, C = o.shape
    W = scale.shape[0]
    kernel = functools.partial(_combine_bwd_kernel, pairs=C // (2 * W),
                               eps=eps, T=T)
    do, dlam, dscale = _call(
        kernel, "hvd_diff_combine_bwd", _whole_operands(lam, scale), (o, g),
        [(C, o.dtype), None, None], T, block_rows, interpret)
    return do, dlam.sum(), dscale.sum((0, 1, 2))


# -- the differentiable calls --------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def _lay(q, D, kernels):
    return _lay_fwd_call(q, D) if kernels else _lay_fwd_xla(q, D)


def _lay_fwd(q, D, kernels):
    return _lay(q, D, kernels), None


def _lay_bwd(D, kernels, _, g):
    return (_lay_bwd_call(g, D) if kernels else _lay_bwd_xla(g, D),)


_lay.defvjp(_lay_fwd, _lay_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _combine(o, lam, scale, eps, kernels):
    fwd = _combine_fwd_call if kernels else _combine_fwd_xla
    return fwd(o, lam, scale, eps)


def _combine_fwd(o, lam, scale, eps, kernels):
    return _combine(o, lam, scale, eps, kernels), (o, lam, scale)


def _combine_bwd(eps, kernels, res, g):
    o, lam, scale = res
    bwd = _combine_bwd_call if kernels else _combine_bwd_xla
    do, dlam, dscale = bwd(o, g, lam, scale, eps)
    return do, dlam.astype(lam.dtype), dscale.astype(scale.dtype)


_combine.defvjp(_combine_fwd, _combine_bwd)


def _counted(W: int) -> bool:
    from ..monitor.registry import counter

    kernels = _runs_kernels(W)
    counter("diff_attention.path", path="kernel" if kernels else "xla").inc()
    return kernels


def lay_in_halves(q, head_dim: int):
    """q ``[B, T, H * D]`` (``H`` even) -> ``[B, T, H * 2 D]``: head
    ``2p + e``'s ``D`` values in half ``e`` of a ``2 D``-wide head, zeros in
    the other half. The cotangent picks each head's half."""
    if q.shape[2] % (2 * head_dim):
        raise ValueError(f"{q.shape[2]} columns are no even number of "
                         f"{head_dim}-wide heads")
    return _lay(q, head_dim, _counted(2 * head_dim))


def diff_combine(o, lam, scale, eps: float):
    """o ``[B, T, H * W]`` (``H`` even, ``W = scale.shape[0]``), ``lam`` a
    float32 scalar, ``scale`` ``[W]`` float32 -> ``[B, T, H / 2 * W]`` of
    o's type: ``a = o[2p] - lam * o[2p+1]``, then ``a * rsqrt(mean(a^2) +
    eps) * scale`` over the ``W`` lanes, in float32. Differentiable in o,
    ``lam`` and ``scale``."""
    W = scale.shape[0]
    if o.shape[2] % (2 * W):
        raise ValueError(f"{o.shape[2]} columns are no even number of "
                         f"{W}-wide heads")
    # Outside the custom VJP, as ops/flash_attention.py has it: a replicated
    # operand's cotangent is summed by the cast's transpose.
    o, lam, scale = _harmonize_vma(o, lam, scale)
    return _combine(o, lam, scale, float(eps), _counted(W))
