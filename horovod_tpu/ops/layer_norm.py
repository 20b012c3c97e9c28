"""Pallas TPU fused residual-add + LayerNorm.

The reference framework has no normalization kernels (CNN-era data
parallelism; its BN analogue is SyncBatchNorm's CUDA path). On TPU the
transformer's residual stream is pure HBM traffic: the pre-LN block
pattern

    h = x + sublayer_out        # one [N, C] write
    y = LN(h) * gamma + beta    # one [N, C] read + write

round-trips the stream an extra time whenever XLA does not fuse the add
into the LayerNorm's reductions. This kernel computes both in one pass:
one read of x and sublayer_out, one write of h (the stream continues
through it) and y — an MFU lever built so the TPU A/B is one bench flag
(``--fused-ln``).

Forward grid: row blocks of the flattened [N, C] stream; per-row mean /
rstd live only in VMEM. The backward recomputes the row statistics from
the saved ``h`` (recompute-over-store: no stats residual, no awkward
[N, 1] outputs) and emits per-row-block partial dgamma/dbeta that a
cheap XLA sum folds.

Numerics: statistics and the normalized value are fp32 regardless of the
stream dtype (same policy as flax ``nn.LayerNorm(dtype=...)`` with fp32
params); ``h`` is materialized in the stream dtype — identical to what
the unfused pattern stores.

Off-TPU the kernel runs in Pallas interpreter mode so the CPU test suite
exercises the identical code path (tests/test_layer_norm.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401

from .flash_attention import _harmonize_vma, _interpret, _out_struct

_DEF_BLOCK_ROWS = 256


def _pad_rows(n: int, preferred: int):
    """(block_rows, padded_n): rows pad up to a block multiple instead of
    hunting for an exact divisor — a prime N must not degrade to 1-row
    blocks (a sublane-1 tile per grid step, far slower than unfused)."""
    br = min(preferred, n)
    return br, ((n + br - 1) // br) * br


def _padded(a, n_pad):
    if not n_pad:
        return a
    return jnp.concatenate(
        [a, jnp.zeros((n_pad,) + a.shape[1:], a.dtype)], axis=0)


def _fwd_kernel(x_ref, r_ref, g_ref, b_ref, y_ref, h_ref, *, eps, inv_c):
    # The whole body lives in a pl.when with a TRACED truth predicate:
    # scalar constants (1/C, eps) mixed with varying blocks trip the HLO
    # interpreter's vma checking under shard_map outside when-bodies
    # (same idiom as flash_attention._run_pred's always-run case).
    i = pl.program_id(0)

    @pl.when(i >= 0)
    def _():
        h = x_ref[...].astype(jnp.float32) + r_ref[...].astype(jnp.float32)
        mean = jnp.sum(h, axis=-1, keepdims=True) * inv_c
        var = jnp.sum(jnp.square(h - mean), axis=-1, keepdims=True) * inv_c
        rstd = jax.lax.rsqrt(var + eps)
        y = (h - mean) * rstd * g_ref[...].astype(jnp.float32) + \
            b_ref[...].astype(jnp.float32)
        h_ref[...] = h.astype(h_ref.dtype)
        y_ref[...] = y.astype(y_ref.dtype)


def _bwd_kernel(h_ref, g_ref, dy_ref, dh_ref, dx_ref, dg_ref, db_ref,
                dg_scr, db_scr, *, eps, inv_c, nb):
    # dgamma/dbeta partials accumulate in VMEM scratch across the
    # (sequential) row-block grid and are written once at the last step:
    # a per-block (1, C) output block would violate Mosaic's (8, 128)
    # block-shape minimum (the r5 TPU bring-up failure — interpreter mode
    # never checks it), while the (8, C) full-array output below is
    # always legal.
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dg_scr[...] = jnp.zeros_like(dg_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    @pl.when(i >= 0)  # traced truth: see _fwd_kernel
    def _():
        h = h_ref[...].astype(jnp.float32)
        dy = dy_ref[...].astype(jnp.float32)
        g = g_ref[...].astype(jnp.float32)
        mean = jnp.sum(h, axis=-1, keepdims=True) * inv_c
        var = jnp.sum(jnp.square(h - mean), axis=-1, keepdims=True) * inv_c
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (h - mean) * rstd
        dyg = dy * g
        c1 = jnp.sum(dyg, axis=-1, keepdims=True) * inv_c
        c2 = jnp.sum(dyg * xhat, axis=-1, keepdims=True) * inv_c
        dln = rstd * (dyg - c1 - xhat * c2)
        dx_ref[...] = (dln + dh_ref[...].astype(jnp.float32)).astype(
            dx_ref.dtype)
        # Full-tile broadcast accumulate (all 8 sublanes carry the same
        # value) — avoids single-sublane scatter writes; row 0 is read out.
        dg_scr[...] += jnp.broadcast_to(
            jnp.sum(dy * xhat, axis=0, keepdims=True), dg_scr.shape)
        db_scr[...] += jnp.broadcast_to(
            jnp.sum(dy, axis=0, keepdims=True), db_scr.shape)

    @pl.when(i == nb - 1)
    def _finish():
        dg_ref[...] = dg_scr[...]
        db_ref[...] = db_scr[...]


def ln_residual(x, res, gamma, beta, eps: float = 1e-5,
                block_rows: int = _DEF_BLOCK_ROWS):
    """``h = x + res;  y = LN(h) * gamma + beta`` in one fused pass.

    Args:
      x, res: ``[..., C]`` stream and sublayer output (same shape/dtype).
      gamma, beta: ``[C]`` scale/shift (fp32 params as in flax).

    Returns ``(y, h)`` — ``y`` in the stream dtype, ``h`` the updated
    residual stream (what the unfused pattern's add produces).
    """
    # Outside the custom_vjp call, so that the backward carries the scope.
    with jax.named_scope("hvd.layer_norm"):
        return _ln_residual(x, res, gamma, beta, eps, block_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _ln_residual(x, res, gamma, beta, eps, block_rows):
    return _fwd_impl(x, res, gamma, beta, eps, block_rows)


def _flatten(a):
    return a.reshape(-1, a.shape[-1])


def _fwd_impl(x, res, gamma, beta, eps, block_rows):
    if x.shape != res.shape:
        raise ValueError(f"x/res shape mismatch: {x.shape} vs {res.shape}")
    C = x.shape[-1]
    if gamma.shape != (C,) or beta.shape != (C,):
        raise ValueError(
            f"gamma/beta must be [{C}], got {gamma.shape}/{beta.shape}")
    orig_shape = x.shape
    x2, r2 = _flatten(x), _flatten(res)
    N = x2.shape[0]
    if N == 0:  # empty stream (e.g. a zero-row microbatch slice)
        h = x + res
        return jnp.zeros_like(h), h
    br, Np = _pad_rows(N, block_rows)
    x2, r2 = _padded(x2, Np - N), _padded(r2, Np - N)
    g2, b2 = gamma.reshape(1, C), beta.reshape(1, C)
    x2, r2, g2, b2 = _harmonize_vma(x2, r2, g2, b2)
    row_spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    par_spec = pl.BlockSpec((1, C), lambda i: (0, 0))
    y, h = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, inv_c=1.0 / C),
        grid=(Np // br,),
        in_specs=[row_spec, row_spec, par_spec, par_spec],
        out_specs=[row_spec, row_spec],
        out_shape=[_out_struct((Np, C), x.dtype, x2, r2),
                   _out_struct((Np, C), x.dtype, x2, r2)],
        interpret=_interpret(),
        name="hvd_ln_fwd",
    )(x2, r2, g2, b2)
    return y[:N].reshape(orig_shape), h[:N].reshape(orig_shape)


def _vjp_fwd(x, res, gamma, beta, eps, block_rows):
    y, h = _fwd_impl(x, res, gamma, beta, eps, block_rows)
    return (y, h), (h, gamma)


def _vjp_bwd(eps, block_rows, residuals, cts):
    h, gamma = residuals
    dy, dh = cts
    C = h.shape[-1]
    orig_shape = h.shape
    h2, dy2, dh2 = _flatten(h), _flatten(dy), _flatten(dh)
    N = h2.shape[0]
    if N == 0:
        z = jnp.zeros_like(gamma)
        return jnp.zeros_like(h), jnp.zeros_like(h), z, z
    br, Np = _pad_rows(N, block_rows)
    h2 = _padded(h2, Np - N)
    dy2 = _padded(dy2, Np - N)  # zero rows: no dgamma/dbeta pollution
    dh2 = _padded(dh2, Np - N)
    nb = Np // br
    g2 = gamma.reshape(1, C)
    h2, g2, dy2, dh2 = _harmonize_vma(h2, g2, dy2, dh2)
    row_spec = pl.BlockSpec((br, C), lambda i: (i, 0))
    par_spec = pl.BlockSpec((1, C), lambda i: (0, 0))
    acc_spec = pl.BlockSpec((8, C), lambda i: (0, 0))
    dx, dgp, dbp = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, inv_c=1.0 / C, nb=nb),
        grid=(nb,),
        in_specs=[row_spec, par_spec, row_spec, row_spec],
        out_specs=[row_spec, acc_spec, acc_spec],
        out_shape=[_out_struct((Np, C), h.dtype, h2, dy2, dh2),
                   _out_struct((8, C), jnp.float32, h2, dy2, dh2),
                   _out_struct((8, C), jnp.float32, h2, dy2, dh2)],
        scratch_shapes=[pltpu.VMEM((8, C), jnp.float32),
                        pltpu.VMEM((8, C), jnp.float32)],
        # The scratch accumulators carry across row blocks: the grid dim
        # must stay sequential.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="hvd_ln_bwd",
    )(h2, g2, dy2, dh2)
    dx = dx[:N].reshape(orig_shape)
    dgamma = dgp[0].astype(gamma.dtype)
    dbeta = dbp[0].astype(gamma.dtype)
    # h = x + res: both inputs receive the same cotangent.
    return dx, dx, dgamma, dbeta


_ln_residual.defvjp(_vjp_fwd, _vjp_bwd)
