"""Selective scan (the state-space recurrence of a Mamba-1 layer) as Pallas
TPU kernels with a custom VJP (docs/selective_scan.md).

For channel ``d`` of ``Dn`` and state ``n`` of ``N``, token by token::

    h[t, d, n] = exp(dt[t, d] * A[d, n]) * h[t-1, d, n]
                 + dt[t, d] * x[t, d] * B[t, n]          (h[-1] = 0)
    y[t, d]    = sum_n h[t, d, n] * C[t, n] + Dskip[d] * x[t, d]

The decay is per (channel, state) pair, so the recurrence is no matmul: it
is elementwise work on the vector unit, sequential in ``t``, and its state
``[Dn, N]`` a token is never written to HBM (``[T, Dn, N]`` float32 is 2.7
GB at T = 8192, Dn = 5120, N = 16).

Layout: the channels are the lanes AND the sublanes of a vector register
(1024 channels are one ``[8, 128]`` register), the ``N`` states are ``N``
separate registers, and ``B[t, n]`` / ``C[t, n]`` are scalars read from
SMEM: a token's update is register-times-scalar arithmetic with no
broadcast across lanes and no reduction (the sum over ``n`` adds
registers). The grid is (batch, channel blocks, chunks of the sequence):
the chunks are the innermost axis and the state of a channel block stays in
a VMEM scratch across them.

* ``hvd_selective_scan_fwd`` writes ``y`` and the state ENTERING each chunk
  (``T / chunk x Dn x N`` float32: what the backward starts a chunk from).
* ``hvd_selective_scan_bwd`` walks the chunks in reverse, a grid step ALL
  the channels its VMEM holds (``bwd_registers``: the cell's 5120), one
  register of 1024 at a time. For a register it runs the forward again
  from the saved state, keeping the chunk's states in VMEM, then the
  reverse recurrence ``g[t] = C[t] dy[t] + a[t+1] g[t+1]`` with ``a g``
  carried across chunks in a scratch. ``dA`` and ``dDskip`` accumulate in
  the loop's carry and reach their resident output blocks once a chunk;
  ``B[t, n]`` and ``C[t, n]`` are spread to registers once a grid step.
  The products whose sums over the channels are ``dB[t, n]`` and
  ``dC[t, n]`` are added up over the step's registers of channels in VMEM
  and reduced ONCE a grid step on the otherwise idle MXU (three one-pass
  matmuls of bfloat16-exact pieces: the float32 sum), a channel block's
  part each, summed outside. What the compiled schedule showed and what
  each choice bought: docs/selective_scan.md.

``chunk`` and ``block_d`` (channels a block of the forward, and what the
channels are padded to) come from ops/kernel_autotune.py (``scan_blocks``:
forward and backward timed together) unless the caller gives them. A shape
the kernels refuse (no chunk divides ``T``, or more states than the
registers hold; off-TPU, a call inside ``shard_map``) takes
:func:`selective_scan_reference`, a plain ``lax.scan`` with the same
float32 arithmetic. Trace-time counters:
``ssm.scan_path{path=kernel|fallback}``, ``ssm.scan_chunks``,
``ssm.state_bytes``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .collective_ops import _vma
from . import flash_attention as _flash
from .flash_attention import _harmonize_vma, _out_struct

_LANES, _SUBLANES = 128, 8
_VREG = _LANES * _SUBLANES          # channels of one float32 register
_SMEM_TILE = 1024                   # words: a 1-D SMEM block's unit
MAX_STATES = 32                     # states carried as registers of a loop
#: (chunk, block_d) the kernels take where nothing was swept.
DEFAULT_BLOCKS = (64, 1024)
#: What the sweep times. The backward's grid step plans its VMEM itself
#: (``bwd_registers``: 37 MB at chunk 64, N = 16, Dn = 5120; at chunk 128
#: one register of channels a step, 48 MB); chunk 256 holds 95 MB of
#: states, sums and scalars and does not compile.
CANDIDATES = ((64, 1024), (128, 1024), (64, 2048), (128, 2048))
_VMEM_LIMIT = 64 * 2 ** 20
_BWD_VMEM = 48 * 2 ** 20            # what a backward grid step may plan for
_STATE_GROUP = 16                   # states the backward's loops carry
_LOG2E, _LN2 = math.log2(math.e), math.log(2.0)
_UNROLL = 4                         # tokens a trip (a chunk is 8 k tokens)

# Under ``jax.checkpoint`` with ``save_only_these_names(OUT_NAME)`` the scan's
# output and the chunk-boundary states are kept, and the recomputed forward
# of a rematerialised block runs no scan.
OUT_NAME = "hvd_selective_scan_out"


def _interpret() -> bool:
    """The flash kernels' answer (interpreter mode off-TPU), asked each
    time: a compile for a described chip (benchmarks/rehearse_compile.py)
    sets theirs, and the step's kernels follow together."""
    return _flash._interpret()


def pick_chunk(T: int, preferred: int, N: int) -> Optional[int]:
    """The largest chunk <= ``preferred`` among its halvings that divides
    ``T`` and whose ``chunk * N`` scalars of B (and of C) are whole SMEM
    tiles of 1024 words; None where none does."""
    c = preferred
    while c >= _SUBLANES:
        if T % c == 0 and (c * N) % _SMEM_TILE == 0:
            return c
        c //= 2
    return None


def selective_scan_reference(x, dt, A, Bm, Cm, Dskip):
    """The recurrence as a ``lax.scan`` over tokens in float32: x, dt
    [B, T, Dn]; A [Dn, N]; Bm, Cm [B, T, N]; Dskip [Dn] -> y [B, T, Dn]
    float32. Differentiated by JAX (it keeps a state a token)."""
    f32 = jnp.float32
    x, dt, A, Bm, Cm, Dskip = (a.astype(f32) for a in
                               (x, dt, A, Bm, Cm, Dskip))

    def step(h, args):
        x_t, dt_t, b_t, c_t = args                     # [B, Dn] / [B, N]
        a = jnp.exp(dt_t[..., None] * A)
        h = a * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, (h * c_t[:, None, :]).sum(-1) + Dskip * x_t

    # Zeros of the operands' own type: inside ``shard_map`` a carry varies
    # over the mesh axes its updates do.
    h0 = jnp.zeros_like(x[:, 0, :, None] * A)
    _, y = lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0)
                                    for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(y, 0, 1)


# -- the kernels --------------------------------------------------------------

def _token_forward(x, dt, a_ref, b_ref, c_ref, t, h, N):
    """One token: (the new states, sum_n h_n * C[t, n])."""
    dtx, new, y = dt * x, [], None
    for n in range(N):
        hn = jnp.exp(dt * a_ref[n]) * h[n] + dtx * b_ref[t * N + n]
        part = hn * c_ref[t * N + n]
        y = part if y is None else y + part
        new.append(hn)
    return tuple(new), y


def _fwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, y_ref, hs_ref,
                h_ref, *, L, N):
    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    hs_ref[0, 0] = h_ref[...]
    skip = d_ref[...]

    def body(t, h):
        x, dt = x_ref[0, t], dt_ref[0, t]
        h, y = _token_forward(x, dt, a_ref, b_ref, c_ref, t, h, N)
        y_ref[0, t] = y + skip * x
        return h

    h = lax.fori_loop(0, L, body, tuple(h_ref[n] for n in range(N)))
    for n in range(N):
        h_ref[n] = h[n]


def _bf16_pieces(v):
    """Three float32 arrays of at most 8 significant bits each that sum to
    ``v`` exactly: a one-pass (bfloat16) matmul of each against zeros and
    ones is the float32 sum, at half the six passes ``HIGHEST`` makes."""
    def top(a):
        bits = lax.bitcast_convert_type(a, jnp.uint32)
        return lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                        jnp.float32)

    hi = top(v)
    mid = top(v - hi)
    return hi, mid, v - hi - mid


def _token_loop(L, body, carry):
    """``fori_loop`` over a chunk's tokens, ``_UNROLL`` of them a trip: the
    scheduler packs one trip's instructions and nothing across trips."""
    def trip(i, carry):
        for k in range(_UNROLL):
            carry = body(i * _UNROLL + k, carry)
        return carry

    return lax.fori_loop(0, L // _UNROLL, trip, carry)


def _bwd_kernel(x_ref, dt_ref, a_ref, d_ref, b_ref, c_ref, dy_ref, hs_ref,
                dx_ref, ddt_ref, da_ref, dd_ref, db_ref, dc_ref,
                g_ref, a2_ref, b_vec, c_vec, h_buf, p_buf, q_buf,
                *, L, N, subs):
    """A grid step: a chunk's tokens of ``subs`` registers of channels (1024
    each). B and C are spread to registers once; then one register of
    channels at a time, and in it ``_STATE_GROUP`` states at a time (the
    loops' carries are what the register file holds); then the sums of the
    products over the step's channels."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        g_ref[...] = jnp.zeros_like(g_ref)
        # exp(dt A) = 2 ** (dt (A log2 e)): the chip's exponential is a
        # power of two behind a product, made here once a sequence.
        a2_ref[...] = a_ref[...] * _LOG2E
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    # A token's scalars B[t, n] and C[t, n] as registers, once a grid step
    # for all its channels: in the loops they are loads, not broadcasts.
    def spread(t, _):
        for n in range(N):
            for ref, vec in ((b_ref, b_vec), (c_ref, c_vec)):
                vec[t, n] = jnp.full((_SUBLANES, _LANES), ref[t * N + n])

    lax.fori_loop(0, L, spread, None)

    def scan(r, ns, first_sub, first_group):
        """The chunk's two loops for the channels ``r`` (8 rows of 128) and
        the states ``ns``, eight states' registers as one ``[8, 8, 128]``
        value (a wider one keeps more alive than the register file holds).
        The products whose sums over ALL the grid step's channels are
        dB[t, n] and dC[t, n] are added up register by register in
        ``p_buf`` / ``q_buf`` (the first one writes)."""
        # (the eight states among all N, the same among the group's)
        eights = [(pl.ds(k, min(_SUBLANES, ns.stop - k)),
                   pl.ds(k - ns.start, min(_SUBLANES, ns.stop - k)))
                  for k in range(ns.start, ns.stop, _SUBLANES)]

        def keep(buf, of_all, t, products):
            # One load and one store of eight registers: a load placed
            # after a store to the same scratch waits for it.
            buf[of_all, t] = (products if first_sub
                              else buf[of_all, t] + products)

        # The chunk's states again, from the state that entered it:
        # h_buf[:, t + 1] = h[t], h_buf[:, 0] = the state before the chunk.
        def again(t, hs):
            x, dt, dy = x_ref[0, t, r], dt_ref[0, t, r], dy_ref[0, t, r]
            new = []
            for (of_all, of_group), h in zip(eights, hs):
                h = (jnp.exp2(dt * a2_ref[of_all, r]) * h
                     + (dt * x) * b_vec[t, of_all])
                h_buf[of_group, t + 1] = h
                keep(q_buf, of_all, t, dy * h)   # sums to dC[t, n]
                new.append(h)
            return tuple(new)

        # The loop starts from the scratch's copy: under ``shard_map`` a
        # value read from an operand carries the operands' varying mesh
        # axes in its type and a value computed inside a kernel (or read
        # from a scratch) carries none, and a loop's carry must keep one
        # type.
        for of_all, of_group in eights:
            h_buf[of_group, 0] = hs_ref[0, 0, of_all, r]
        _token_loop(L, again, tuple(h_buf[of_group, 0]
                                    for _, of_group in eights))
        skip = d_ref[r]

        def back(i, carry):
            gas, das, dd = carry         # a[t+1] * g[t+1]; dA, dDskip so far
            t = L - 1 - i
            x, dt, dy = x_ref[0, t, r], dt_ref[0, t, r], dy_ref[0, t, r]
            s = ddt = jnp.zeros_like(x)
            gas_new, das_new = [], []
            for (of_all, of_group), ga, da in zip(eights, gas, das):
                a2 = a2_ref[of_all, r]
                g = dy * c_vec[t, of_all] + ga
                s = s + (g * b_vec[t, of_all]).sum(0)
                ag = g * jnp.exp2(dt * a2)
                w = ag * h_buf[of_group, t]      # h[t - 1]
                ddt = ddt + (w * a2).sum(0)
                keep(p_buf, of_all, t, g * (dt * x))     # sums to dB[t, n]
                gas_new.append(ag)
                das_new.append(da + w * dt)
            dx, ddt = dt * s, x * s + ddt * _LN2
            if first_group:              # and the skip's part, once
                dx_ref[0, t, r], ddt_ref[0, t, r] = dx + dy * skip, ddt
                dd = dd + dy * x
            else:
                dx_ref[0, t, r] += dx
                ddt_ref[0, t, r] += ddt
            return tuple(gas_new), tuple(das_new), dd

        gas = tuple(g_ref[of_all, r] for of_all, _ in eights)
        gas, das, dd = _token_loop(
            L, back, (gas, tuple(jnp.zeros_like(ga) for ga in gas),
                      jnp.zeros((_SUBLANES, _LANES), jnp.float32)))
        for (of_all, _), ga, da in zip(eights, gas, das):
            g_ref[of_all, r] = ga
            da_ref[0, of_all, r] += da
        if first_group:
            dd_ref[0, r] += dd

    def register(s, first_sub):
        r = pl.ds(pl.multiple_of(s * _SUBLANES, _SUBLANES), _SUBLANES)
        for g0 in range(0, N, _STATE_GROUP):
            scan(r, range(g0, min(g0 + _STATE_GROUP, N)), first_sub, g0 == 0)

    register(0, True)
    if subs > 1:
        lax.fori_loop(1, subs, lambda s, _: register(s, False), None)

    # The products' sums over the grid step's channels, on the MXU: over
    # the lanes, eight states at a time (state n's products meet a matrix
    # whose row n % 8 is ones, so that the eight sums land on eight
    # sublanes), then over a token's 8 sublanes, which now lie side by side
    # ([2N, L * 8] @ [L * 8, L]).
    row = lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
    token = lax.broadcasted_iota(jnp.int32, (L * _SUBLANES, L), 0) // _SUBLANES
    fold = (token == lax.broadcasted_iota(jnp.int32, (L * _SUBLANES, L), 1)
            ).astype(jnp.float32)

    def lane_sums(buf, g0):                  # 8 states: -> [8, L * 8]
        out = None
        for n in range(g0, min(g0 + _SUBLANES, N)):
            ones = (row == n - g0).astype(jnp.float32)
            for piece in _bf16_pieces(buf[n].reshape(L * _SUBLANES, _LANES)):
                part = lax.dot_general(
                    ones, piece, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                out = part if out is None else out + part
        return out

    groups = range(0, N, _SUBLANES)
    lanes = jnp.concatenate([lane_sums(buf, g0) for buf in (p_buf, q_buf)
                             for g0 in groups], axis=0)
    sums = jnp.dot(lanes, fold, precision=lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    half = len(groups) * _SUBLANES
    db_ref[0, 0, 0] = sums[:N]
    dc_ref[0, 0, 0] = sums[half:half + N]


def _specs(L, N, rows, reverse_of=None):
    """Block specs over the grid (batch, channel block, chunk); with
    ``reverse_of`` = the number of chunks they are walked last first."""
    def chunk(j):
        return j if reverse_of is None else reverse_of - 1 - j

    def tokens():            # [B, T, R, 128]
        return pl.BlockSpec((1, L, rows, _LANES),
                            lambda b, c, j: (b, chunk(j), c, 0))

    def states():            # [N, R, 128] (A, transposed)
        return pl.BlockSpec((N, rows, _LANES), lambda b, c, j: (0, c, 0))

    def channels():          # [R, 128] (Dskip)
        return pl.BlockSpec((rows, _LANES), lambda b, c, j: (c, 0))

    def scalars(n_chunks):   # [B * T * N] in SMEM, a chunk's L * N a block
        return pl.BlockSpec((L * N,),
                            lambda b, c, j: (b * n_chunks + chunk(j),),
                            memory_space=pltpu.SMEM)

    def boundary():          # [B, T / L, N, R, 128]
        return pl.BlockSpec((1, 1, N, rows, _LANES),
                            lambda b, c, j: (b, chunk(j), 0, c, 0))

    return tokens, states, channels, scalars, boundary


_SEMANTICS = ("parallel", "parallel", "arbitrary")


@functools.partial(jax.jit, inline=True,
                   static_argnames=("L", "rows", "interpret"))
def _fwd_call(x, dt, At, Dskip, Bf, Cf, *, L, rows, interpret):
    """x, dt [B, T, R, 128]; At [N, R, 128]; Dskip [R, 128]; Bf, Cf
    [B * T * N] -> (y [B, T, R, 128], states [B, T / L, N, R, 128])."""
    B, T, R, _ = x.shape
    N, n_chunks = At.shape[0], T // L
    tokens, states, channels, scalars, boundary = _specs(L, N, rows)
    ops = (x, dt, At, Dskip, Bf, Cf)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, L=L, N=N),
        grid=(B, R // rows, n_chunks),
        in_specs=[tokens(), tokens(), states(), channels(),
                  scalars(n_chunks), scalars(n_chunks)],
        out_specs=[tokens(), boundary()],
        out_shape=[_out_struct(x.shape, jnp.float32, *ops),
                   _out_struct((B, n_chunks, N, R, _LANES), jnp.float32,
                               *ops)],
        scratch_shapes=[pltpu.VMEM((N, rows, _LANES), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hvd_selective_scan_fwd",
    )(*ops)


def bwd_registers(R: int, L: int, N: int) -> int:
    """Registers of channels (8 rows of 128) a grid step of the backward
    takes: the most that divide the ``R`` rows and keep the step's VMEM
    within ``_BWD_VMEM``. In [8, 128] float32 tiles: five token blocks,
    double-buffered; A, dA and the boundary state likewise and ``a g``
    once; a group's states of a chunk; the two products' sums and the
    chunk's scalars B and C as registers."""
    total = R // _SUBLANES
    group = min(N, _STATE_GROUP)
    for subs in range(total, 1, -1):
        tiles = (10 * L + 8 * N) * subs + (L + 1) * group + 4 * N * L
        if total % subs == 0 and tiles * 4 * _VREG <= _BWD_VMEM:
            return subs
    return 1


@functools.partial(jax.jit, inline=True, static_argnames=("L", "interpret"))
def _bwd_call(x, dt, At, Dskip, Bf, Cf, dy, hs, *, L, interpret):
    """-> (dx, ddt [B, T, R, 128]; dAt [B, N, R, 128]; dDskip [B, R, 128];
    dB, dC [B, channel blocks, T / L, N, L]: a channel block's part each)."""
    B, T, R, _ = x.shape
    N, n_chunks = At.shape[0], T // L
    subs = bwd_registers(R, L, N)
    rows = subs * _SUBLANES
    n_blocks = R // rows
    tokens, states, channels, scalars, boundary = _specs(
        L, N, rows, reverse_of=n_chunks)
    ops = (x, dt, At, Dskip, Bf, Cf, dy, hs)
    part = pl.BlockSpec((1, 1, 1, N, L),
                        lambda b, c, j: (b, c, n_chunks - 1 - j, 0, 0))
    part_shape = _out_struct((B, n_blocks, n_chunks, N, L), jnp.float32,
                             *ops)
    tile = (_SUBLANES, _LANES)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, L=L, N=N, subs=subs),
        grid=(B, n_blocks, n_chunks),
        in_specs=[tokens(), tokens(), states(), channels(),
                  scalars(n_chunks), scalars(n_chunks), tokens(),
                  boundary()],
        out_specs=[
            tokens(), tokens(),
            pl.BlockSpec((1, N, rows, _LANES), lambda b, c, j: (b, 0, c, 0)),
            pl.BlockSpec((1, rows, _LANES), lambda b, c, j: (b, c, 0)),
            part, part],
        out_shape=[_out_struct(x.shape, jnp.float32, *ops),
                   _out_struct(x.shape, jnp.float32, *ops),
                   _out_struct((B, N, R, _LANES), jnp.float32, *ops),
                   _out_struct((B, R, _LANES), jnp.float32, *ops),
                   part_shape, part_shape],
        scratch_shapes=[
            pltpu.VMEM((N, rows, _LANES), jnp.float32),             # a * g
            pltpu.VMEM((N, rows, _LANES), jnp.float32),             # A log2 e
            pltpu.VMEM((L, N) + tile, jnp.float32),                 # B[t, n]
            pltpu.VMEM((L, N) + tile, jnp.float32),                 # C[t, n]
            pltpu.VMEM((min(N, _STATE_GROUP), L + 1) + tile,
                       jnp.float32),                                # h
            pltpu.VMEM((N, L) + tile, jnp.float32),                 # dB's
            pltpu.VMEM((N, L) + tile, jnp.float32),                 # dC's
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS, vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
        name="hvd_selective_scan_bwd",
    )(*ops)


# -- the differentiable call --------------------------------------------------

def _channel_tiles(a, block_d):
    """[..., Dn] -> float32 [..., Dp / 128, 128] (1024 channels a register),
    the channels padded with zeros to whole blocks of ``block_d``."""
    pad = -a.shape[-1] % block_d
    a = a.astype(jnp.float32)
    if pad:
        a = jnp.pad(a, [(0, 0)] * (a.ndim - 1) + [(0, pad)])
    return a.reshape(a.shape[:-1] + (-1, _LANES))


def _kernel_operands(x, dt, A, Bm, Cm, Dskip, block_d):
    """The kernels' views of the operands (a padded channel has x = dt = A =
    Dskip = 0: its state stays 0 and it takes no gradient)."""
    f32 = jnp.float32
    return (*(_channel_tiles(a, block_d) for a in (x, dt, A.T, Dskip)),
            Bm.astype(f32).reshape(-1), Cm.astype(f32).reshape(-1))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _scan(x, dt, A, Bm, Cm, Dskip, L, block_d, out_dtype):
    return _scan_fwd(x, dt, A, Bm, Cm, Dskip, L, block_d, out_dtype)[0]


def _scan_fwd(x, dt, A, Bm, Cm, Dskip, L, block_d, out_dtype):
    ops = _kernel_operands(x, dt, A, Bm, Cm, Dskip, block_d)
    y, hs = _fwd_call(*ops, L=L, rows=block_d // _LANES,
                      interpret=_interpret())
    B, T, Dn = x.shape
    # Named in the type the caller keeps it in: the backward reads the
    # states alone, the output is the rest of the block's to recompute from.
    y = checkpoint_name(y.reshape(B, T, -1)[..., :Dn].astype(out_dtype),
                        OUT_NAME)
    return y, (x, dt, A, Bm, Cm, Dskip, checkpoint_name(hs, OUT_NAME))


def _scan_bwd(L, block_d, out_dtype, res, dy):
    x, dt, A, Bm, Cm, Dskip, hs = res
    B, T, Dn = x.shape
    N = A.shape[1]
    dx, ddt, dAt, dD, dBp, dCp = _bwd_call(
        *_kernel_operands(x, dt, A, Bm, Cm, Dskip, block_d),
        _channel_tiles(dy, block_d), hs, L=L, interpret=_interpret())

    def tokens(g, like):
        return g.reshape(B, T, -1)[..., :Dn].astype(like.dtype)

    def states(p, like):     # [B, blocks, chunks, N, L] -> [B, T, N]
        return jnp.moveaxis(p.sum(1), 2, 3).reshape(B, T, N).astype(
            like.dtype)

    return (tokens(dx, x), tokens(ddt, dt),
            dAt.sum(0).reshape(N, -1)[:, :Dn].T.astype(A.dtype),
            states(dBp, Bm), states(dCp, Cm),
            dD.sum(0).reshape(-1)[:Dn].astype(Dskip.dtype))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _resolve_blocks(B, T, Dn, N) -> Tuple[int, int]:
    from . import kernel_autotune

    if not kernel_autotune.enabled():
        return DEFAULT_BLOCKS
    return kernel_autotune.scan_blocks(B, T, Dn, N, DEFAULT_BLOCKS,
                                       CANDIDATES, pick_chunk)


def selective_scan(x, dt, A, Bm, Cm, Dskip, *, out_dtype=jnp.float32,
                   chunk: Optional[int] = None,
                   block_d: Optional[int] = None):
    """The selective scan of the module's head: x, dt [B, T, Dn] (``dt``
    after its softplus); A [Dn, N] (negative: ``-exp(A_log)``); Bm, Cm
    [B, T, N]; Dskip [Dn] -> y [B, T, Dn] ``out_dtype``. Computed in float32
    whatever the operands' types and rounded once; differentiable in all
    six (a gradient has
    its operand's type), and ``A_log`` and the softplus take theirs through
    ``A`` and ``dt``.

    ``chunk`` tokens a grid step (a halving of it that divides ``T`` is
    taken, see :func:`pick_chunk`) and ``block_d`` channels a block (a
    multiple of 1024; the
    channels are padded to whole blocks) default to the kernel autotuner's
    choice for this (shape, chip). Where no legal chunk divides ``T``, or
    ``N`` is above ``MAX_STATES``, the call is
    :func:`selective_scan_reference`; so it is off-TPU inside
    ``shard_map``, where the Pallas interpreter cannot type a kernel's loop
    that writes an operand's value (varying over the mesh) into a scratch
    (varying over nothing)."""
    from ..monitor.registry import counter

    B, T, Dn = x.shape
    N = A.shape[1]
    if chunk is None and block_d is None:
        chunk, block_d = _resolve_blocks(B, T, Dn, N)
    else:
        chunk = DEFAULT_BLOCKS[0] if chunk is None else chunk
        block_d = DEFAULT_BLOCKS[1] if block_d is None else block_d
    if block_d % _VREG:
        raise ValueError(f"block_d must be a multiple of {_VREG} (one "
                         f"float32 register of channels), got {block_d}")
    L = pick_chunk(T, chunk, N)
    # Outside the custom VJP, as ops/flash_attention.py has it: a
    # replicated operand's cotangent is summed by the cast's transpose.
    ops = _harmonize_vma(x, dt, A, Bm, Cm, Dskip)
    with jax.named_scope("hvd.selective_scan"):
        if L is None or N > MAX_STATES or (_interpret() and _vma(ops[0])):
            counter("ssm.scan_path", path="fallback").inc()
            return selective_scan_reference(*ops).astype(out_dtype)
        counter("ssm.scan_path", path="kernel").inc()
        counter("ssm.scan_chunks").inc(B * (T // L))
        counter("ssm.state_bytes").inc(
            B * (T // L) * N * (Dn + -Dn % block_d) * 4)
        return _scan(*ops, L, block_d, jnp.dtype(out_dtype))
