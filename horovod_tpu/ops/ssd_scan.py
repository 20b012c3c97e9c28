"""The state-space recurrence of a Mamba-2 layer (the "state-space dual"
form, arXiv 2405.21060) in chunks: matmuls on the MXU, with a backward of
the same shape (docs/ssd_scan.md).

For head ``i`` of ``h`` (``P`` channels, reading group ``i // (h / G)`` of
``G``) and ``N`` states, token by token::

    a[t, i]    = exp(-exp(A_log[i]) * dt[t, i])            ONE decay a head
    S[t, i]    = a[t, i] * S[t-1, i] + (dt[t, i] * xs[t, i]) (x) B[t, g]
    y[t, i]    = S[t, i] C[t, g] + Dskip[i] * xs[t, i]     S [P, N], S[-1] = 0

Because the decay is a scalar a head, a chunk of ``L`` tokens is two masked
matmuls and the ``[T, h, P, N]`` states are never made. With
``cs[t] = sum_{r <= t} log a[r]`` inside a chunk and
``Lm[t, s] = exp(cs[t] - cs[s])`` for ``s <= t`` (0 above the diagonal)::

    Y      = ((C B^T) * Lm) (dt * xs)  +  exp(cs) * (C S_in^T)  +  Dskip * xs
    S_out  = exp(cs[L-1]) * S_in  +  sum_s exp(cs[L-1] - cs[s]) (dt xs)[s] (x) B[s]

and the states ENTERING the chunks follow from the chunks' own sums by a
recurrence over ``T / L`` chunk states, written as one small matmul with
the ``[T/L, T/L]`` matrix of the decays between chunks.

:func:`ssd_scan` is ONE op with its own backward (``jax.custom_vjp``): it
keeps its operands and the chunk-entering states (``[B, T/L, h, P, N]``
float32, 33.5 MB at T = 8192, h = 16, P = 64, N = 128) and nothing of a
chunk's inside; the backward makes the ``[L, L]`` pieces again and is the
transpose of the same three steps. The matmuls take their operands in
``xs``'s dtype with float32 accumulation; the decays, their cumulative
sums, the chunk states and their recurrence are float32 whatever the
operands'. ``T`` is padded to whole chunks with ``dt = 0`` tokens (a decay
of one and no input: they change no state and are cut off the output).
Under ``jax.checkpoint`` with ``save_only_these_names(OUT_NAME)`` the
output and the chunk states are kept, and a rematerialised block's
recomputed forward runs no scan. :func:`ssd_scan_reference` is the same
recurrence as a ``lax.scan`` over tokens. Trace-time counter:
``ssd.chunks`` (chunks walked a call, over the batch).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from .flash_attention import _harmonize_vma

#: Tokens a chunk where the caller gives none (``chunk_size`` of the
#: published Mamba-2 configurations).
DEFAULT_CHUNK = 128
OUT_NAME = "hvd_ssd_scan_out"
_HIGHEST = lax.Precision.HIGHEST


def ssd_scan_reference(xs, dt, A_log, Bm, Cm, Dskip):
    """The recurrence as a ``lax.scan`` over tokens in float32: xs
    [B, T, h, P]; dt [B, T, h] (after its softplus); A_log, Dskip [h]; Bm,
    Cm [B, T, G, N] -> y [B, T, h, P] float32. Differentiated by JAX (it
    keeps a state a token)."""
    f32 = jnp.float32
    xs, dt, A_log, Bm, Cm, Dskip = (a.astype(f32) for a in
                                    (xs, dt, A_log, Bm, Cm, Dskip))
    h, G = xs.shape[2], Bm.shape[2]
    Bm, Cm = (jnp.repeat(a, h // G, axis=2) for a in (Bm, Cm))   # [B,T,h,N]
    decay = jnp.exp(-jnp.exp(A_log) * dt)                        # [B, T, h]

    def step(S, args):
        x_t, dt_t, a_t, b_t, c_t = args
        S = a_t[..., None, None] * S + jnp.einsum(
            "bhp,bhn->bhpn", dt_t[..., None] * x_t, b_t, precision=_HIGHEST)
        y = jnp.einsum("bhpn,bhn->bhp", S, c_t, precision=_HIGHEST)
        return S, y + Dskip[:, None] * x_t

    # Zeros of the operands' own type: inside ``shard_map`` a carry varies
    # over the mesh axes its updates do.
    S0 = jnp.zeros_like(xs[:, 0, :, :, None] * Bm[:, 0, :, None, :])
    _, y = lax.scan(step, S0, tuple(jnp.moveaxis(a, 1, 0)
                                    for a in (xs, dt, decay, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


# -- the three steps ----------------------------------------------------------
# Shapes inside: b batch, c chunks, t / s tokens of a chunk, g groups, r heads
# of a group, p channels of a head, n states.

def _chunks(a, L):
    return a.reshape(a.shape[0], a.shape[1] // L, L, *a.shape[2:])


def _heads(a, G):
    """[..., h, *rest] at axis 3 -> [..., G, h / G, *rest]."""
    return a.reshape(*a.shape[:3], G, a.shape[3] // G, *a.shape[4:])


def _log_decay_sums(dt, A_log, L, G):
    """cs [b, c, t, g, r] float32: the cumulative log decay inside each
    chunk."""
    dA = -jnp.exp(A_log.astype(jnp.float32)) * dt.astype(jnp.float32)
    return _heads(jnp.cumsum(_chunks(dA, L), axis=2), G)


def _chunk_states(xs, dt, A_log, Bm, L):
    """(what each chunk adds to the state [b, c, g, r, p, n] float32, each
    chunk's whole log decay [b, c, g, r])."""
    G = Bm.shape[2]
    cs = _log_decay_sums(dt, A_log, L, G)
    to_end = jnp.exp(cs[:, :, -1:] - cs)                     # [b,c,s,g,r]
    xdt = _heads(_chunks(xs, L), G) * (
        to_end * _heads(_chunks(dt.astype(jnp.float32), L), G))[..., None]
    local = jnp.einsum("bcsgrp,bcsgn->bcgrpn", xdt.astype(xs.dtype),
                       _chunks(Bm, L), preferred_element_type=jnp.float32)
    return local, cs[:, :, -1]


def _entering_states(local, total):
    """The state ENTERING each chunk, [b, c, g, r, p, n] float32:
    ``S_in[c] = sum_{d < c} exp(sum_{d < k < c} total[k]) local[d]``. The sums
    between chunks are taken of the terms themselves (a masked cumulative
    sum), not as a difference of two long sums."""
    nc = total.shape[1]
    c, d, k = (jnp.arange(nc).reshape(shape) for shape in
               ((nc, 1, 1), (1, nc, 1), (1, 1, nc)))
    # between[.., c, d] = sum_{d < k < c} total[k], for d < c.
    terms = jnp.where((k > d) & (k < c),
                      jnp.moveaxis(total, 1, -1)[..., None, None, :], 0.0)
    between = jnp.sum(terms, axis=-1)                        # [b,g,r,c,d]
    decay = jnp.where(d[..., 0] < c[..., 0], jnp.exp(between), 0.0)
    return jnp.einsum("bgrcd,bdgrpn->bcgrpn", decay, local,
                      precision=_HIGHEST)


def _chunk_outputs(xs, dt, A_log, Bm, Cm, Dskip, entering, L):
    """y [b, T, h, p] float32 from the operands and the entering states."""
    f32 = jnp.float32
    G = Bm.shape[2]
    cs = _log_decay_sums(dt, A_log, L, G)                    # [b,c,t,g,r]
    x = _heads(_chunks(xs, L), G)                            # [b,c,t,g,r,p]
    Bc, Cc = _chunks(Bm, L), _chunks(Cm, L)
    cb = jnp.einsum("bctgn,bcsgn->bcgts", Cc, Bc, preferred_element_type=f32)
    # Lm[t, s] = exp(cs[t] - cs[s]) under the diagonal: the difference is
    # masked before the exponential, which above the diagonal would overflow.
    at = jnp.moveaxis(cs, 2, -1)                             # [b,c,g,r,t]
    tri = jnp.tril(jnp.ones((L, L), bool))
    lm = jnp.exp(jnp.where(tri, at[..., :, None] - at[..., None, :],
                           -jnp.inf))                        # [b,c,g,r,t,s]
    xdt = x * _heads(_chunks(dt.astype(f32), L), G)[..., None]
    y = jnp.einsum("bcgrts,bcsgrp->bctgrp",
                   (cb[:, :, :, None] * lm).astype(xs.dtype),
                   xdt.astype(xs.dtype), preferred_element_type=f32)
    carried = jnp.einsum("bctgn,bcgrpn->bctgrp", Cc,
                         entering.astype(xs.dtype), preferred_element_type=f32)
    y = y + jnp.exp(cs)[..., None] * carried
    y = y + _heads(Dskip.astype(f32)[None, None, None], G)[..., None] * x
    return y.reshape(xs.shape)


# -- the op -------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ssd(L, xs, dt, A_log, Bm, Cm, Dskip):
    return _ssd_fwd(L, xs, dt, A_log, Bm, Cm, Dskip)[0]


def _ssd_fwd(L, xs, dt, A_log, Bm, Cm, Dskip):
    local, total = _chunk_states(xs, dt, A_log, Bm, L)
    entering = checkpoint_name(_entering_states(local, total), OUT_NAME)
    y = _chunk_outputs(xs, dt, A_log, Bm, Cm, Dskip, entering, L)
    return y, (xs, dt, A_log, Bm, Cm, Dskip, entering)


def _ssd_bwd(L, res, dy):
    xs, dt, A_log, Bm, Cm, Dskip, entering = res
    # The transpose of the three steps, last first; a chunk's inside is made
    # again from the operands, the entering states are read.
    _, pull_out = jax.vjp(functools.partial(_chunk_outputs, L=L),
                          xs, dt, A_log, Bm, Cm, Dskip, entering)
    *d_out, d_entering = pull_out(dy)
    (local, total), pull_states = jax.vjp(
        functools.partial(_chunk_states, L=L), xs, dt, A_log, Bm)
    _, pull_rec = jax.vjp(_entering_states, local, total)
    d_states = pull_states(pull_rec(d_entering))
    d_xs, d_dt, d_A, d_B = (a + b for a, b in zip(d_out[:4], d_states))
    return d_xs, d_dt, d_A, d_B, d_out[4], d_out[5]


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd_scan(xs, dt, A_log, Bm, Cm, Dskip, *, chunk: int = DEFAULT_CHUNK):
    """The chunked scan of the module's head: xs [B, T, h, P]; dt [B, T, h]
    (``dt`` after its softplus, >= 0); A_log [h] (the decay a token is
    ``exp(-exp(A_log) * dt)``); Bm, Cm [B, T, G, N] with ``G`` dividing
    ``h`` (head ``i`` reads group ``i // (h / G)``); Dskip [h] -> y
    [B, T, h, P] in ``xs``'s dtype (computed in float32 and rounded once).
    Differentiable in all six (a gradient has its operand's type);
    ``chunk`` tokens a chunk,
    any ``T`` (padded to whole chunks with tokens that change nothing).
    Under the scope ``hvd.ssd_scan`` in both directions."""
    from ..monitor.registry import counter

    B, T, h, P = xs.shape
    G = Bm.shape[2]
    if h % G or Bm.shape != Cm.shape or dt.shape != (B, T, h):
        raise ValueError(f"ssd_scan: {h} heads over {G} groups, dt "
                         f"{dt.shape}, B {Bm.shape}, C {Cm.shape}")
    L = int(chunk)
    if L < 1:
        raise ValueError(f"chunk must be positive, got {chunk}")
    pad = -T % L
    counter("ssd.chunks").inc(B * ((T + pad) // L))
    # Outside the custom VJP, as ops/flash_attention.py has it: a
    # replicated operand's cotangent is summed by the cast's transpose.
    ops = _harmonize_vma(xs, dt, A_log, Bm, Cm, Dskip)
    with jax.named_scope("hvd.ssd_scan"):
        if pad:
            ops = tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (
                a.ndim - 2)) if a.ndim > 1 else a for a in ops)
        y = _ssd(L, *ops)[:, :T]
        return checkpoint_name(y.astype(xs.dtype), OUT_NAME)
