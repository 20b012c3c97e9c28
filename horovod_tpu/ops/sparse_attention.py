"""Attention over a per-query selected set, chosen by a learned indexer
(DeepSeek-V3.2-Exp's sparse attention; docs/sparse_attention.md).

Two halves, both Pallas TPU kernels (interpreted off-TPU, as the flash
kernels are):

* :func:`index_select` (``hvd_index_select``) scores every causal
  (query, key) pair with the indexer,
  ``I[t, s] = (Hi*Di)^-1/2 * sum_j w[t, j] relu(qI[t, j] . kI[s])``,
  finds each query's ``topk``-th largest score EXACTLY and writes the
  selection ``S_t = {s <= t : I[t, s] >= that}`` as an int8 mask
  ``[B, T, T]``. One grid cell holds a block of queries against all their
  keys: the scores live in VMEM as order-preserving int32 keys (never in
  HBM: 1 GB a layer in float32 at T = 16k) and the threshold is built bit
  by bit, 32 counting passes over them: a count and a compare each, where
  ``lax.top_k`` at k = 2048 of 16k is a sort. Ties with the threshold are
  all kept. Operands as given (bfloat16 in the model), accumulation in
  float32.
* :func:`masked_attention` (``hvd_sparse_attn_fwd``,
  ``hvd_sparse_attn_bwd``): the flash schedule with the selection applied
  as a mask inside each score tile.
  A grid cell holds one KV head's block of keys against ALL the query
  heads that share it (``[G, bq, D]``), so K, V and the mask tile are
  fetched once for the group; cells wholly in the causal future are
  skipped and their blocks not fetched (clamped index maps). Gathering a
  query's 2048 keys instead would move 4 MB a query (64 GB a layer at 16k).
  A tile none of whose entries is selected is NOT skipped: with a
  selection as scattered as an untrained indexer's every (512, 512) tile
  holds selected pairs, so the test would cost and save nothing
  (ROADMAP "Speed"). The backward is ONE kernel that computes a tile's
  ``p`` and ``ds`` once for dq, dk and dv (5 matmuls a tile and a head),
  with a KV head's float32 dk and dv, ``8 * T * D`` bytes, resident in
  VMEM; where those pass ``_FUSED_BWD_BUDGET`` (T = 32k at D = 128) two
  kernels run in its place, ``hvd_sparse_attn_bwd_dq`` and
  ``hvd_sparse_attn_bwd_dkv`` (3 + 4 matmuls: the tile computed twice).
  The shape alone decides (:func:`_fused_bwd_fits`).

:func:`sparse_attention` composes them under the scopes
``hvd.sparse_indexer`` and ``hvd.sparse_attention``. No gradient reaches
the indexer's operands or passes through the selection (the mask is an
integer).

What the backward pass keeps of the mask is one bit a (query, key) pair
(:func:`pack_selection`: 34 MB a layer at the benchmark's T = 16k where
the int8 mask is 268 MB), and its kernels read the unpacked form. For a
rematerialised block two values carry a ``checkpoint_name``: the forward
kernel's output and log-sum-exp row (``OUT_NAME``, 0.14 GB a layer against
18 ms of forward kernel) and the packed selection (``SELECTION_NAME``,
against 7 ms of index kernel and the indexer's projections).
``models/sparse_moe_decoder.py`` keeps both whatever else its blocks keep
(its ``remat_kept`` adds the experts' plan and, where the device's memory
allows, projections and block outputs by their own names), so the
recomputed forward holds neither kernel: what feeds only a saved value is
dead there.

Trace-time counters (monitor registry): ``sparse_attn.topk``,
``sparse_attn.pairs_required`` (sum over queries of min(t + 1, topk), per
query head) and ``sparse_attn.pairs_computed`` (entries of the score tiles
a kernel runs), label ``kernel`` = ``index`` | ``fwd`` | ``bwd`` (the
fused backward) or ``bwd_dq`` | ``bwd_dkv``; ``sparse_attn.bwd_path``
(label ``path`` = ``fused`` | ``split``: one a differentiated call, by
the shape); ``sparse_attn.selection_bytes`` (the packed selection a
differentiated call names, ``B * T * T / 8``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa

_NEG_INF = _fa._NEG_INF
_INT_MIN = -2 ** 31
OUT_NAME = "hvd_sparse_out"
SELECTION_NAME = "hvd_sparse_selection"

# Blocks: the index kernel keeps [_INDEX_BLOCK_Q, T] int32 keys in VMEM
# (8 MB at T = 16k) and walks them _INDEX_CHUNK columns at a time; the
# attention kernels run (_BLOCK_Q, _BLOCK_K) score tiles for each of the G
# query heads of a cell.
_INDEX_BLOCK_Q = 128
_INDEX_CHUNK = 1024
_BLOCK_Q = 1024
_BLOCK_K = 1024
# The fused backward holds four [bq, bk] float32 intermediates a head where
# the forward holds two: at (1024, 1024) it ran 39.1 ms a call on the chip,
# at (512, 1024) 32.2 (the two backward kernels 45.0; PERF.md, PR 36).
_BWD_BLOCK_Q = 512
_BWD_BLOCK_K = 1024
_VMEM_LIMIT = 96 * 1024 * 1024
# What the fused backward may spend of it on a KV head's dk / dv.
_FUSED_BWD_BUDGET = _VMEM_LIMIT // 4


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_VMEM_LIMIT)


def _count(name: str, n: int, **labels: str) -> None:
    from ..monitor.registry import counter

    counter(f"sparse_attn.{name}", **labels).inc(int(n))


def pairs_required(T: int, topk: int) -> int:
    """sum_t min(t + 1, topk): the pairs one query head attends."""
    k = min(topk, T)
    return k * (k + 1) // 2 + (T - k) * k


# ---------------------------------------------------------------------------
# the indexer: scores, exact threshold, selection mask
# ---------------------------------------------------------------------------


def _flip(bits):
    """Float32 bit patterns <-> int32 keys of the same order (its own
    inverse): a negative float's magnitude bits are flipped."""
    return bits ^ ((bits >> 31) & 0x7FFFFFFF)


def _float_key(x):
    """int32 keys that order as the float32 ``x`` does."""
    return _flip(lax.bitcast_convert_type(x, jnp.int32))


def _key_float(key):
    """The float32 a key stands for; ``-inf`` for ``_INT_MIN`` (a row with
    fewer causal keys than ``topk``: everything is selected)."""
    return jnp.where(key == _INT_MIN, -jnp.inf,
                     lax.bitcast_convert_type(_flip(key), jnp.float32))


def _index_kernel(qi_ref, w_ref, ki_ref, mask_ref, tau_ref, key_scr, *,
                  topk, bq, ck, nck, heads, dim):
    i = pl.program_id(1)

    # The body sits under a traced truth: under ``shard_map`` the
    # interpreter evaluates what is inlined here on shard_map's own values
    # and refuses a block, typed as varying, beside an index typed the
    # same on every device; the branch of a ``cond`` it takes whole.
    @pl.when(i >= 0)
    def _body():
        first = i * bq                          # first query of the block
        n_live = (first + bq + ck - 1) // ck        # chunks with a causal key
        rows = first + lax.broadcasted_iota(jnp.int32, (bq, ck), 0)
        cols = lax.broadcasted_iota(jnp.int32, (bq, ck), 1)
        # [bq, Hi]
        w = w_ref[0].astype(jnp.float32) * (heads * dim) ** -0.5

        def chunk(c):
            return pl.ds(pl.multiple_of(c * ck, ck), ck)

        def fill(c, carry):
            kc = ki_ref[0, chunk(c), :]                            # [ck, Di]
            acc = jnp.zeros((bq, ck), jnp.float32)
            for j in range(heads):
                s = lax.dot_general(
                    qi_ref[0, :, j * dim:(j + 1) * dim], kc,
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                acc = acc + w[:, j:j + 1] * jnp.maximum(s, 0.0)
            key_scr[:, chunk(c)] = jnp.where(cols + c * ck <= rows,
                                             _float_key(acc), _INT_MIN)
            return carry

        lax.fori_loop(0, n_live, fill, 0)
        lanes = min(ck, 128)

        def count_ge(cand):
            """[bq, 1]: how many causal keys of each row are >= cand."""
            def body(c, part):
                ge = jnp.where(key_scr[:, chunk(c)] >= cand, 1, 0)
                for a in range(ck // lanes):
                    part = part + ge[:, a * lanes:(a + 1) * lanes]
                return part
            part = lax.fori_loop(0, n_live, body,
                                 jnp.zeros((bq, lanes), jnp.int32))
            return jnp.sum(part, axis=1, keepdims=True)

        # The largest key c with count(key >= c) >= topk, built from the
        # sign bit down; _INT_MIN where a row has fewer than topk causal
        # keys.
        res = jnp.where(count_ge(jnp.zeros((bq, 1), jnp.int32)) >= topk,
                        0, _INT_MIN).astype(jnp.int32)

        def bit(b, res):
            cand = res + jnp.left_shift(jnp.int32(1), 30 - b)
            return jnp.where(count_ge(cand) >= topk, cand, res)

        res = lax.fori_loop(0, 31, bit, res)

        def emit(c, carry):
            sel = (key_scr[:, chunk(c)] >= res) & (cols + c * ck <= rows)
            mask_ref[0, :, chunk(c)] = jnp.where(sel, 1, 0).astype(jnp.int8)
            return carry

        lax.fori_loop(0, nck, emit, 0)
        tau_ref[0] = jnp.broadcast_to(_key_float(res), (bq, 8))


@functools.partial(jax.jit, inline=True,
                   static_argnames=("topk", "bq", "ck", "interpret"))
def _index_call(qi, ki, w, *, topk, bq, ck, interpret):
    B, T, Hi, Di = qi.shape
    kernel = functools.partial(_index_kernel, topk=topk, bq=bq, ck=ck,
                               nck=T // ck, heads=Hi, dim=Di)
    return pl.pallas_call(
        kernel,
        grid=(B, T // bq),
        in_specs=[
            pl.BlockSpec((1, bq, Hi * Di), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, Hi), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, T, Di), lambda b, i: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, T), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, bq, 8), lambda b, i: (b, i, 0)),
        ],
        out_shape=[
            _fa._out_struct((B, T, T), jnp.int8, qi, ki, w),
            _fa._out_struct((B, T, 8), jnp.float32, qi, ki, w),
        ],
        scratch_shapes=[pltpu.VMEM((bq, T), jnp.int32)],
        compiler_params=_params("parallel", "arbitrary"),
        interpret=interpret,
        name="hvd_index_select",
    )(qi.reshape(B, T, Hi * Di), w, ki)


def index_select(index_q, index_k, index_w, *, topk: int):
    """(mask int8 [B, T, T], threshold f32 [B, T]) of the indexer's
    queries ``index_q [B, T, Hi, Di]``, keys ``index_k [B, T, Di]`` and
    head weights ``index_w [B, T, Hi]``: ``mask[b, t, s] = 1`` iff
    ``s <= t`` and ``I[t, s] >=`` the ``topk``-th largest of
    ``I[t, 0..t]`` (every causal key while ``t < topk``); the threshold is
    ``-inf`` for such rows. Not differentiable."""
    B, T = index_q.shape[:2]
    bq = _fa._pick_block(T, _INDEX_BLOCK_Q)
    ck = _fa._pick_block(T, _INDEX_CHUNK)
    if bq is None or ck is None:
        raise ValueError(f"sequence length {T} has no 128-aligned block")
    _count("topk", topk, kernel="index")
    _count("pairs_required", B * pairs_required(T, topk), kernel="index")
    _count("pairs_computed", B * sum(
        -(-(i * bq + bq) // ck) * ck * bq for i in range(T // bq)),
        kernel="index")
    qi, ki, w = _fa._harmonize_vma(*(lax.stop_gradient(x) for x in (
        index_q, index_k, index_w)))
    mask, tau = _index_call(qi, ki, w, topk=int(topk), bq=bq, ck=ck,
                            interpret=_fa._interpret())
    return mask, tau[..., 0]


# ---------------------------------------------------------------------------
# the selection, one bit a pair
# ---------------------------------------------------------------------------


def pack_selection(mask):
    """``mask [B, T, T]`` (non-zero = selected) as uint8 ``[B, T/8, T]``:
    bit ``r`` of byte ``[b, t, s]`` is ``mask[b, r * T/8 + t, s]``. Eight
    row slabs or-ed together, so that packing and unpacking are
    elementwise passes at HBM's rate and no lane is shuffled. Each slab is
    cut from the mask as given and compared on its own: the compiler then
    makes one pass of it (a compare of the whole mask first is a second
    ``[T, T]`` array in HBM)."""
    B, T, S = mask.shape
    slabs = mask.reshape(B, 8, T // 8, S)
    return functools.reduce(jnp.bitwise_or, (
        (slabs[:, r] != 0).astype(jnp.uint8) << r for r in range(8)))


def unpack_selection(packed):
    """The int8 0/1 mask ``[B, T, T]`` that :func:`pack_selection`
    packed. A concatenation of the eight slabs, which the compiler writes
    in place slab by slab; shifting a broadcast of the bytes by a row's
    slab number reads better and leaves the broadcast in HBM."""
    return jnp.concatenate(
        [((packed >> r) & 1).astype(jnp.int8) for r in range(8)], axis=1)


# ---------------------------------------------------------------------------
# attention under the mask
# ---------------------------------------------------------------------------


def _last_k(i, bq, bk):
    """The last key block a query block i sees."""
    return (i * bq + bq - 1) // bk


def _first_q(j, bq, bk):
    """The first query block that sees key block j."""
    return (j * bk) // bq


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, G, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_k(i, bq, bk)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _cell():
        sel = mask_ref[0].astype(jnp.float32) != 0.0          # [bq, bk]
        k, v = k_ref[0], v_ref[0]
        for g in range(G):
            q = q_ref[0, g] * scale
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(sel, s, _NEG_INF)
            m_prev = m_scr[g]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            # A row with nothing selected yet has m_new = -1e30 and p = 1
            # on every entry; its first selected key, which every causal
            # row has by its last block, wipes that with alpha = 0.
            p = jnp.exp(s - m_new)
            l_scr[g] = l_scr[g] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[g] = acc_scr[g] * alpha + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_scr[g] = m_new

    @pl.when(j == last)
    def _finish():
        for g in range(G):
            l = l_scr[g]
            o_ref[0, g] = (acc_scr[g] / l).astype(o_ref.dtype)
            lse_ref[0, g] = jnp.broadcast_to(m_scr[g] + jnp.log(l), (bq, 8))


def _bwd_dq_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, acc_scr, *, scale, G, bq, bk):
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_k(i, bq, bk)

    @pl.when(j == 0)
    def _init():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(j <= last)
    def _cell():
        sel = mask_ref[0].astype(jnp.float32) != 0.0
        k, v = k_ref[0], v_ref[0]
        for g in range(G):
            q = q_ref[0, g] * scale
            do = do_ref[0, g]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(sel, s, _NEG_INF) - lse_ref[0, g][:, :1])
            dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, g][:, :1])
            acc_scr[g] = acc_scr[g] + lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    @pl.when(j == last)
    def _finish():
        for g in range(G):
            dq_ref[0, g] = (acc_scr[g] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref,
                    delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *,
                    scale, G, bq, bk, nq):
    j, i = pl.program_id(1), pl.program_id(2)
    first = _first_q(j, bq, bk)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(i >= first)
    def _cell():
        sel = mask_ref[0].astype(jnp.float32) != 0.0          # [bq, bk]
        k, v = k_ref[0], v_ref[0]
        dk, dv = dk_scr[:], dv_scr[:]
        for g in range(G):
            q = q_ref[0, g] * scale
            do = do_ref[0, g]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(sel, s, _NEG_INF) - lse_ref[0, g][:, :1])
            dv = dv + lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [bk, D]
            dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, g][:, :1])
            dk = dk + lax.dot_general(
                ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_scr[:], dv_scr[:] = dk, dv

    @pl.when(i == nq - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr, *,
                scale, G, bq, bk, nq):
    """The whole backward in one query-major walk: a score tile's ``p``
    and ``ds`` are computed once and feed dq (a scratch a query block, as
    in ``_bwd_dq_kernel``) and dk / dv, whose float32 ``[T, D]``
    accumulators of the KV head stay in VMEM across both inner axes. A key
    block's contributions arrive as in ``_bwd_dkv_kernel`` (query blocks
    ascending, heads inside), so all three results are that pair's to the
    bit. The last row of cells sees every key block and writes it out."""
    i, j = pl.program_id(1), pl.program_id(2)
    last = _last_k(i, bq, bk)

    @pl.when((i == 0) & (j == 0))
    def _init_kv():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    @pl.when(j == 0)
    def _init_q():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    rows = pl.ds(pl.multiple_of(j * bk, bk), bk)

    @pl.when(j <= last)
    def _cell():
        sel = mask_ref[0].astype(jnp.float32) != 0.0          # [bq, bk]
        k, v = k_ref[0], v_ref[0]
        dk, dv = dk_scr[rows, :], dv_scr[rows, :]
        for g in range(G):
            q = q_ref[0, g] * scale
            do = do_ref[0, g]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            p = jnp.exp(jnp.where(sel, s, _NEG_INF) - lse_ref[0, g][:, :1])
            # In this order (dv's matmul before dp's): with dp first the
            # call read 32.37 ms for 32.17 on the chip (PERF.md, PR 36).
            dv = dv + lax.dot_general(
                p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)           # [bk, D]
            dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - delta_ref[0, g][:, :1])).astype(k.dtype)
            dq_scr[g] = dq_scr[g] + lax.dot_general(
                ds, k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk = dk + lax.dot_general(
                ds, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
        dk_scr[rows, :], dv_scr[rows, :] = dk, dv

    @pl.when(j == last)
    def _finish_q():
        for g in range(G):
            dq_ref[0, g] = (dq_scr[g] * scale).astype(dq_ref.dtype)

    @pl.when(i == nq - 1)
    def _finish_kv():
        dk_ref[0] = dk_scr[rows, :].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[rows, :].astype(dv_ref.dtype)


_traced_once = functools.partial(
    jax.jit, inline=True,
    static_argnames=("scale", "hkv", "bq", "bk", "interpret"))


def _specs(hkv, G, bq, bk, D, *, q_major: bool):
    """Block specs of (q-like [BHk, G, T, D], k-like [BHk, T, D], mask
    [B, T, T], row statistics [BHk, G, T, 8]) for a grid (BHk, nq, nk)
    (``q_major``) or (BHk, nk, nq). A cell in the causal future maps to
    the nearest cell that runs, so nothing is fetched for it."""
    if q_major:
        def at(b, i, j):
            return i, jnp.minimum(j, _last_k(i, bq, bk))
    else:
        def at(b, j, i):
            return jnp.maximum(i, _first_q(j, bq, bk)), j

    def qmap(b, x, y):
        return b, 0, at(b, x, y)[0], 0

    def kmap(b, x, y):
        return b, at(b, x, y)[1], 0

    def mmap(b, x, y):
        return (b // hkv, *at(b, x, y))

    return (pl.BlockSpec((1, G, bq, D), qmap),
            pl.BlockSpec((1, bk, D), kmap),
            pl.BlockSpec((1, bq, bk), mmap),
            pl.BlockSpec((1, G, bq, 8), qmap))


@_traced_once
def _fwd_call(q, k, v, mask, *, scale, hkv, bq, bk, interpret):
    BHk, G, T, D = q.shape
    qs, ks, ms, rs = _specs(hkv, G, bq, bk, D, q_major=True)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, G=G, bq=bq, bk=bk),
        grid=(BHk, T // bq, T // bk),
        in_specs=[qs, ks, ks, ms],
        out_specs=[qs, rs],
        out_shape=[_fa._out_struct(q.shape, q.dtype, q, k, v, mask),
                   _fa._out_struct((BHk, G, T, 8), jnp.float32,
                                   q, k, v, mask)],
        scratch_shapes=[pltpu.VMEM((G, bq, 1), jnp.float32),
                        pltpu.VMEM((G, bq, 1), jnp.float32),
                        pltpu.VMEM((G, bq, D), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="hvd_sparse_attn_fwd",
    )(q, k, v, mask)


@_traced_once
def _bwd_dq_call(q, k, v, mask, do, lse, delta, *, scale, hkv, bq, bk,
                 interpret):
    BHk, G, T, D = q.shape
    qs, ks, ms, rs = _specs(hkv, G, bq, bk, D, q_major=True)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, G=G, bq=bq, bk=bk),
        grid=(BHk, T // bq, T // bk),
        in_specs=[qs, ks, ks, ms, qs, rs, rs],
        out_specs=qs,
        out_shape=_fa._out_struct(q.shape, q.dtype, q, k, v, mask, do),
        scratch_shapes=[pltpu.VMEM((G, bq, D), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="hvd_sparse_attn_bwd_dq",
    )(q, k, v, mask, do, lse, delta)


@_traced_once
def _bwd_dkv_call(q, k, v, mask, do, lse, delta, *, scale, hkv, bq, bk,
                  interpret):
    BHk, G, T, D = q.shape
    qs, ks, ms, rs = _specs(hkv, G, bq, bk, D, q_major=False)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, G=G, bq=bq, bk=bk,
                          nq=T // bq),
        grid=(BHk, T // bk, T // bq),
        in_specs=[qs, ks, ks, ms, qs, rs, rs],
        out_specs=[ks, ks],
        out_shape=[_fa._out_struct(k.shape, k.dtype, q, k, v, mask, do),
                   _fa._out_struct(v.shape, v.dtype, q, k, v, mask, do)],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "arbitrary"),
        interpret=interpret,
        name="hvd_sparse_attn_bwd_dkv",
    )(q, k, v, mask, do, lse, delta)


@_traced_once
def _bwd_call(q, k, v, mask, do, lse, delta, *, scale, hkv, bq, bk,
              interpret):
    BHk, G, T, D = q.shape
    nq = T // bq
    qs, ks, ms, rs = _specs(hkv, G, bq, bk, D, q_major=True)
    # A key block leaves VMEM from the last row of cells, which sees them
    # all in turn; before it the output block stays put and is not written.
    out = pl.BlockSpec((1, bk, D),
                       lambda b, i, j: (b, jnp.where(i == nq - 1, j, 0), 0))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, scale=scale, G=G, bq=bq, bk=bk,
                          nq=nq),
        grid=(BHk, nq, T // bk),
        in_specs=[qs, ks, ks, ms, qs, rs, rs],
        out_specs=[qs, out, out],
        out_shape=[_fa._out_struct(q.shape, q.dtype, q, k, v, mask, do),
                   _fa._out_struct(k.shape, k.dtype, q, k, v, mask, do),
                   _fa._out_struct(v.shape, v.dtype, q, k, v, mask, do)],
        scratch_shapes=[pltpu.VMEM((G, bq, D), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32),
                        pltpu.VMEM((T, D), jnp.float32)],
        compiler_params=_params("parallel", "arbitrary", "arbitrary"),
        interpret=interpret,
        name="hvd_sparse_attn_bwd",
    )(q, k, v, mask, do, lse, delta)


def _count_pairs(kernel, q, topk_pairs, bq, bk):
    BHk, G, T, _ = q.shape
    cells = sum(_last_k(i, bq, bk) + 1 for i in range(T // bq))
    _count("pairs_computed", BHk * G * cells * bq * bk, kernel=kernel)
    if topk_pairs is not None:
        _count("pairs_required", BHk * G * topk_pairs, kernel=kernel)


def _kw(q, scale, hkv, fused_bwd=False):
    T = q.shape[2]
    bq, bk = (_BWD_BLOCK_Q, _BWD_BLOCK_K) if fused_bwd else (_BLOCK_Q,
                                                             _BLOCK_K)
    return dict(scale=scale, hkv=hkv, bq=_fa._pick_block(T, bq),
                bk=_fa._pick_block(T, bk), interpret=_fa._interpret())


def _fused_bwd_fits(T, D):
    """Whether one KV head's float32 dk and dv (``8 * T * D`` bytes) may
    stay in VMEM beside the fused backward's blocks: 16 MB of the budget's
    24 at the benchmark's T = 16k, D = 128; from T = 32k on the two
    kernels run, which hold a key block's accumulators alone."""
    return 8 * T * D <= _FUSED_BWD_BUDGET


def _forward(q, k, v, mask, scale, hkv, required):
    kw = _kw(q, scale, hkv)
    _count_pairs("fwd", q, required, kw["bq"], kw["bk"])
    o, lse = _fwd_call(q, k, v, mask, **kw)
    # One lane of the eight the kernel writes: a saved [.., T, 8] float32
    # pads to 128 lanes in HBM (256 MB a layer at T = 16k).
    return tuple(checkpoint_name(x, OUT_NAME) for x in (o, lse[..., 0]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _masked(q, k, v, mask, scale, hkv, required):
    return _forward(q, k, v, mask, scale, hkv, required)[0]


def _masked_fwd(q, k, v, mask, scale, hkv, required):
    o, lse = _forward(q, k, v, mask, scale, hkv, required)
    # The forward kernel reads the mask as given; the backward pass keeps
    # it packed. Under a policy that saves SELECTION_NAME whatever made
    # the mask feeds, in the recomputed forward, only this saved value.
    selection = checkpoint_name(pack_selection(mask), SELECTION_NAME)
    _count("selection_bytes", selection.nbytes)
    return o, (q, k, v, selection, o, lse)


def _masked_bwd(scale, hkv, required, res, do):
    q, k, v, selection, o, lse = res
    # The packed selection is there since the forward pass: unpacked as
    # soon as it can be, the [T, T] mask lies in HBM through the backward
    # pass of whatever follows the attention (0.27 GB more at the step's
    # peak in the benchmark's model). It waits for the cotangent.
    selection, do = lax.optimization_barrier((selection, do))
    mask = unpack_selection(selection)
    fused = _fused_bwd_fits(*q.shape[2:])
    kw = _kw(q, scale, hkv, fused)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    lse, delta = (jnp.broadcast_to(x[..., None], (*x.shape, 8))
                  for x in (lse, delta))
    _count("bwd_path", 1, path="fused" if fused else "split")
    if fused:
        _count_pairs("bwd", q, required, kw["bq"], kw["bk"])
        dq, dk, dv = _bwd_call(q, k, v, mask, do, lse, delta, **kw)
    else:
        _count_pairs("bwd_dq", q, required, kw["bq"], kw["bk"])
        _count_pairs("bwd_dkv", q, required, kw["bq"], kw["bk"])
        dq = _bwd_dq_call(q, k, v, mask, do, lse, delta, **kw)
        dk, dv = _bwd_dkv_call(q, k, v, mask, do, lse, delta, **kw)
    return dq, dk, dv, np.zeros(mask.shape, jax.dtypes.float0)


_masked.defvjp(_masked_fwd, _masked_bwd)


def masked_attention(q, k, v, mask, *, scale: Optional[float] = None,
                     topk: Optional[int] = None):
    """Softmax attention of ``q [B, T, H, D]`` over ``k``, ``v``
    ``[B, T, Hkv, D]`` (H a multiple of Hkv: query head h reads KV head
    ``h // (H / Hkv)``) restricted to ``mask [B, T, T]`` (non-zero =
    attend). Every row of the mask must select at least one key and none
    after the query (``index_select`` gives such a mask). Differentiable
    in q, k and v. ``topk`` only feeds the ``pairs_required`` counter."""
    B, T, H, D = q.shape
    Hkv = k.shape[2]
    if H % Hkv or k.shape != v.shape or mask.shape != (B, T, T):
        raise ValueError(f"shapes q {q.shape} k {k.shape} v {v.shape} "
                         f"mask {mask.shape}")
    if _fa._pick_block(T, _BLOCK_Q) is None:
        raise ValueError(f"sequence length {T} has no 128-aligned block")
    G = H // Hkv
    scale = float(scale) if scale is not None else D ** -0.5
    required = None if topk is None else pairs_required(T, topk)
    qp = jnp.transpose(q.reshape(B, T, Hkv, G, D),
                       (0, 2, 3, 1, 4)).reshape(B * Hkv, G, T, D)
    kp = jnp.transpose(k, (0, 2, 1, 3)).reshape(B * Hkv, T, D)
    vp = jnp.transpose(v, (0, 2, 1, 3)).reshape(B * Hkv, T, D)
    qp, kp, vp, mask = _fa._harmonize_vma(qp, kp, vp, mask)
    o = _masked(qp, kp, vp, mask, scale, Hkv, required)
    return jnp.transpose(o.reshape(B, Hkv, G, T, D),
                         (0, 3, 1, 2, 4)).reshape(B, T, H, D)


def sparse_attention(q, k, v, index_q, index_k, index_w, *, topk: int,
                     scale: Optional[float] = None):
    """Attention of every query over the ``topk`` keys its indexer scores
    highest (all causal keys while fewer than ``topk``): ``q [B, T, H, D]``,
    ``k``, ``v`` ``[B, T, Hkv, D]``; the indexer's ``index_q
    [B, T, Hi, Di]``, ``index_k [B, T, Di]``, ``index_w [B, T, Hi]``.
    Gradients flow to q, k and v only."""
    with jax.named_scope("hvd.sparse_indexer"):
        mask, _ = index_select(index_q, index_k, index_w, topk=topk)
    # Outside the custom_vjp call, so that the backward kernels and the
    # layout traffic carry the scope too (flash_attention does the same).
    with jax.named_scope("hvd.sparse_attention"):
        return masked_attention(q, k, v, mask, scale=scale, topk=topk)
