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

* forward: one grid cell per (head, q block, k block); the k-block axis is
  innermost, so the per-q-block running max ``m``, normalizer ``l`` and
  output accumulator live in VMEM scratch across k-steps; scores never
  leave VMEM. Emits the logsumexp residual for the backward pass. A cell
  is walked as STRIPS of queries, each against the keys it sees of the
  cell as one wide tile: one pair of matmuls, one softmax pass and one
  update of the row's state a strip, the strips issued as a pipeline
  (``_fwd_kernel``; docs/flash_window.md "The forward's walk").
* layout: the kernels read q, k, v, dO and write o, dq, dk, dv as the
  projections produce and consume them, ``[B, T, H·D]``, wherever whole
  heads fill whole 128-lane blocks: ``128 % D == 0 and (H·D) % 128 == 0``
  (head dim 64 with an even head count, head dim 128, the tensor-parallel
  shards that keep that; ``_reads_in_place``). A block is (rows of the
  sequence, 128 lanes): the width of an HBM tile, of a vector register
  and of the MXU, so every block is dense in HBM and every store a whole
  register. At D = 64 a block holds a pair of heads, which take turns on a
  grid axis of their own: the block indices do not depend on it, so a
  block is fetched once for both and the output block stays put; a head's
  matmuls contract over all 128 lanes with the other head's lanes of one
  operand zeroed (a row factor, folded into ``scale`` where there is one)
  and cost what 64 lanes cost, which half-fill the MXU anyway; the wrong
  half of an accumulator is dropped once, where it is written out. No
  transpose stands around such a call (they were 8 a layer, each a pass
  through HBM into a ``[B·H, T, 64]`` array whose 128-lane tiles are half
  empty: a quarter of the attention's time). Any other shape is packed to
  ``[B·H, T, D]`` first, one head a block; the kernel bodies are the same.
* backward: the split-kernel formulation — one kernel accumulates dQ over
  k-blocks, a second accumulates dK/dV over q-blocks — with the
  ``delta = rowsum(dO ⊙ O)`` precomputed in plain XLA (``_prep_residuals``).
  The per-query statistics (logsumexp, delta) are one dense row of lanes a
  head, ``[B·H, 1, T]``. Both kernels recompute probabilities from q, k and the
  saved logsumexp (recompute-over-store: O(T·D) residuals instead of
  O(T²)). The dK/dV kernel works on transposed score tiles ([tk, tq]), so
  that dV = PᵀdO and dK = dSᵀQ are plain matmuls.
* causal masking skips work at two levels when block positions are static
  (zero offsets). **Grid cell**: a (bq, bk) cell wholly in the future is
  skipped by ``pl.when`` (its k/v blocks are still fetched). **Sub-tile**:
  a cell that the diagonal crosses — at T <= block, where one cell holds
  the whole score square, that is every cell — is walked in (256, 256)
  sub-tiles; a sub-tile wholly in the future is never computed (no
  matmul, no softmax pass: 6 of 16 at T = 1024), only the sub-tiles the
  diagonal crosses build a mask (4 of the other 10), and the rest run
  bare. A cell wholly below the diagonal, a non-causal cell and a ring
  partial stay one tile of that lattice: nothing to skip there, and every
  cut along the keys costs the backward kernels a column of row statistics
  per sub-tile. (The forward cuts along the queries alone, which costs
  none: its strips take the bare sub-tiles of a row and the crossed ones at
  their ends as one tile.)
* what is per row or per operand is done there and not on the score
  tile: ``scale`` is folded into q (into k in the dK/dV kernel) once per
  sub-tile row, the mask is one compare of a hoisted iota difference
  against a scalar, and the fully-masked-row guards (one select on a
  [tq, 1] column) exist only with runtime offsets (ring partials): a
  causal row with zero offsets always sees its own token.
* grouped KV heads (``k``, ``v`` with fewer heads than ``q``) are found
  where they lie, never repeated in HBM: the forward and dq kernels' k/v
  index maps send query head ``h`` to KV head ``h // group`` (in place a
  head is a block then, D = 128; any other grouped shape is packed), and
  the dk/dv kernel's grid counts KV heads with the query heads of a group
  on an axis of their own that the k, v, dk, dv block indices do not
  depend on: a K/V block is fetched once for its group and dk, dv are
  summed over the group in the kernel's scratch.
* a window (``window=W``: query t sees keys t - W + 1 .. t; causal, zero
  offsets, square blocks) makes the inner grid axis walk the BAND: the
  ``ceil((W - 1) / b) + 1`` k blocks a q block's window touches, offset
  from the q block (``_specs``), and no others, so no block past the
  window's far side is fetched or computed. A band offset is a kind of
  cell known when the kernel is traced (``_band_cells``): wholly visible
  (the ``_FULL`` body), or cut into sub-tiles of which those wholly in
  the future or wholly past the window are never computed and those the
  diagonal or the window's far edge crosses are masked, the far edge by
  the same hoisted iota difference against a second scalar
  (``_Band.kind``, ``_visible``). Every branch is on grid indices and
  static sizes. The three kernels of a windowed call are named
  ``hvd_flash_*_win``. ``window=None`` with one head count compiles what
  this file compiled before it knew either (docs/flash_window.md).
* the block-diffusion mask (``block_diffusion=B``: the ``2 L`` rows are a
  noised copy of a sequence and then its clean copy; a clean query sees
  the clean keys to the END of its own block of ``B`` positions, a noised
  one the clean keys to the end of the PREVIOUS block and the noised keys
  of its own block; docs/block_diffusion.md) makes the inner grid axis
  walk the cells the mask touches and no other (``_bd_k_block``,
  ``_bd_q_block``): ``L^2 + L B`` pairs a head of the square's
  ``4 L^2``. ``B`` divides a sub-tile, so an edge runs only through the
  sub-tiles ON the diagonal of a cell on the diagonal (``_Edge``), whose
  mask is one compare against 0 of a hoisted difference of BLOCK indices
  (``row >> log2 B`` minus ``column >> log2 B``); every other cell is
  bare (``_FULL``) or never visited. A noised row meets its own block
  first, so its running maximum is finite before any tile it sees nothing
  of. The kernels are named ``hvd_flash_*_bd``. ``block_diffusion=None``
  compiles what this file compiled before it knew the mask.
* :func:`flash_ring_attention` composes the kernels with sequence
  parallelism: K/V blocks rotate around the mesh axis via
  ``lax.ppermute`` while each ring step runs the flash kernel with
  global causal positions and partial outputs merge by logsumexp; the
  backward replays the ring with dk/dv accumulators traveling alongside
  their blocks (they arrive home after n rotations). With runtime offsets
  nothing is skipped inside a kernel (whole future partials are skipped
  by the ``lax.cond`` in ``_ring_fwd_impl``).

Trace-time counters in the monitor registry (docs/observability.md):
``flash.layout`` (label ``path`` = ``in_place`` | ``packed``) counts one per
``flash_attention`` call on the side its operands' shape decides; each
kernel call adds to ``flash.tiles_total`` / ``flash.tiles_computed`` /
``flash.tiles_masked`` (label ``kernel`` = ``fwd`` | ``bwd_dq`` |
``bwd_dkv``): the sub-tiles of one head's grid, how many are computed and
how many of those are masked (16 / 10 / 4 at the benchmark cells' shape,
in either layout); a windowed call counts under one more label,
``window``, a block-diffusion call under ``block``. The forward also
counts its walk, ``flash.strips`` and ``flash.softmax_updates`` (4 / 4 at
that shape: ``_count_tiles``). ``flash.kv_group`` adds the query heads a
KV head of every grouped call.

Everything is static-shaped; block sizes adapt to divide the sequence
(see ``_pick_block`` — a whole-sequence block covers anything <= the
preferred block, and long sequences with no 128-aligned divisor fall back
to the dense path), sub-tiles to divide the block (``_sub_tile``). Off-TPU
the kernels run in Pallas interpreter mode so the CPU test suite exercises
the identical code path.
"""

from __future__ import annotations

import contextlib
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu  # noqa: F401  (TPU backend)

_NEG_INF = -1e30  # finite: keeps running-max arithmetic NaN-free

# Large grid blocks amortize Mosaic's per-grid-cell overhead; the causal
# half is saved inside the cell (sub-tiles, below), not by a finer grid,
# whose extra cells and k/v fetches cost more than their skipped work
# saves. Measured on a TPU v5e, bf16, [8, 1024, 16, 64] causal, device
# microseconds of one call of forward / dq / dk-dv (PERF.md, PR 25):
#
#   grid block  sub-tile      fwd    dq   dk/dv
#   1024        none (PR 24)  769   635    849
#   1024        1024          416   550    726   per-entry work hoisted only
#   1024        512           336   419    555
#   1024        256           329   367    505   <- _SUB_TILE
#   1024        128           351   440    507
#   512         256          1001   680    796   the finer grid
#
# A [1024, 1024] f32 score tile is 4 MB of VMEM; the backward kernels keep
# one (a non-causal cell, a cell below the diagonal, a ring partial) or a
# few 256 KB ones, the forward two strips' ([128 or 256, <= 1024]: the
# table's forward column is PR 25's walk, PR 42's is 270 us at the default).
# Tunable like the other HOROVOD_* knobs (e.g. for other chip generations'
# VMEM sizes).


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


def _resolve_blocks(B, Tq, Tk, H, D, dtype, causal, window=None, Hkv=None,
                    block_diffusion=None):
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
        (_DEF_BLOCK_Q, _DEF_BLOCK_K), _pick_block, window=window,
        kv_heads=None if Hkv in (None, H) else Hkv,
        block_diffusion=block_diffusion)


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
    return pl.BlockSpec((1, 8, 128), lambda *_: (0, 0, 0))


def _as_scalar(x):
    return jnp.broadcast_to(jnp.asarray(x, jnp.int32), (1, 8, 128))


_LANES = 128   # a vector register's, an HBM tile's and the MXU's width


def _reads_in_place(H: int, D: int) -> bool:
    """Can the kernels index ``[B, T, H * D]`` as it lies? Whole heads must
    fill whole 128-lane blocks: D = 64 with an even head count (a pair a
    block), D = 128 (a head a block), the tensor-parallel shards that keep
    that. Everything else is packed to ``[B * H, T, D]`` first."""
    return _LANES % D == 0 and (H * D) % _LANES == 0


def _head_lanes(g, G, shape):
    """Over ``shape``: is the lane one of head ``g``'s, of the ``G`` heads
    side by side in a block?"""
    d = shape[-1] // G
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
    return (lane >= g * d) & (lane < g * d + d)


def _on_head(g, G, ref, value):
    """``value`` as a factor for a sub-tile of ``ref``: on head ``g``'s
    lanes only, 0 on the block's other heads', so that a matmul which
    contracts over all the block's lanes sees one head. The Python number
    itself where a block is one head."""
    if G == 1:
        return value
    lanes = _head_lanes(g, G, (1, ref.shape[-1]))
    return jnp.where(lanes, value, 0.0).astype(ref.dtype)


def _put(ref, at, new, g, G):
    """Write a finished accumulator. The matmuls ran over all the block's
    lanes; the other heads' are dropped here, once, by keeping what the
    block already holds there. (Measured a call at the cells' shape: a
    store of half the lanes under a ``pl.when`` per head 27% slower, a
    masked 32-bit store 1% slower in the forward.)"""
    if G > 1:
        new = jnp.where(_head_lanes(g, G, new.shape), new, ref[at])
    ref[at] = new


def _sub_tile(block: int, preferred: int) -> int:
    """Sub-tile edge for a block: the largest multiple of 128 up to
    ``preferred`` that divides it, else the block itself (a whole-sequence
    block such as 100 has no aligned divisor: one sub-tile then)."""
    for t in range(preferred, 127, -128):
        if block % t == 0:
            return t
    return block


def _sub_tiles(mode: str, bq: int, bk: int, sub_tile):
    """(tq, tk): the lattice of sub-tiles inside a (bq, bk) cell of
    ``mode``: what ``flash.tiles_*`` count and what decides which pairs a
    kernel computes. A cell that can skip (``_cuts``) has the (256, 256)
    lattice, any other is one tile. The two BACKWARD kernels walk the
    lattice tile by tile: a cut along the KEYS costs them a column of row
    statistics per sub-tile ([tq, 1]: as many vregs as half a [tq, 256]
    pass), which only skipped sub-tiles pay back. The forward cuts every
    cell along the QUERIES alone (``_fwd_tiles``): rows are independent,
    each with its own statistics, and a cut there costs no update."""
    if not _cuts(mode):
        return bq, bk
    return _sub_tile(bq, sub_tile[0]), _sub_tile(bk, sub_tile[1])


# How a grid cell treats its sub-tiles. Which of them a call can meet is
# static (``_cell_modes``); which one a cell is follows from the program
# ids, so a kernel holds one copy of its body per mode, each under its
# ``pl.when``, and every sub-tile loop has static bounds: the compiler
# unrolls them and overlaps one sub-tile's matmuls with the next one's
# softmax (a loop with bounds from the program ids ran 2-3x slower).
_FULL = "full"      # wholly at or below the diagonal, or not causal:
                    # every sub-tile, no mask
_SKIP = "skip"      # first query == first key: sub-tiles past the
                    # diagonal never computed, those it crosses masked
_MASKED = "masked"  # crossed off the sub-tile lattice (bq != bk), or
                    # runtime offsets: every sub-tile, masked


# (tq, tk): the lattice inside a cell that skips. Measured on v5e at D = 64,
# bf16, block (1024, 1024), all three kernels (PERF.md, PR 25): 256 x 256 is
# the best or within 2% of it for each; 128 x 128 and 512 x 512 lose 8-9%
# overall. The backward kernels walk it tile by tile; the forward walks its
# ROWS as strips and meets the keys of a strip as one wide tile (PR 42).
_SUB_TILE = (256, 256)
# Rows of a forward strip in a cell with nothing to skip. The strips of a
# cell are a pipeline (strip a + 1's score matmul under strip a's softmax),
# which runs at half the MXUs while it fills and drains: the finer the
# strips, the shorter both. By the compiled schedule a (1024, 1024) cell at
# D = 128 takes 4,469 cycles at 128 rows, 4,800 at 256, 5,042 at 512 where
# its matmuls need 4,096 (PERF.md, PR 42).
_STRIP = 128


def _cuts(mode) -> bool:
    """Can a cell of ``mode`` skip sub-tiles?"""
    return mode == _SKIP or isinstance(mode, (_Band, _Edge))


def _cell_modes(causal, static_skip, nq, nk, bq, bk):
    if not causal:
        return (_FULL,)
    if not static_skip:
        return (_MASKED,)
    if nq == nk == 1:
        return (_SKIP,)
    return (_FULL, _SKIP) if bq == bk else (_FULL, _SKIP, _MASKED)


def _cell_is(mode, causal, static_skip, i, j, bq, bk):
    """Is block (i, j) a cell of ``mode``? A k block contributes iff its
    first key position <= the q block's last query position — decidable
    from the block indices only when offsets are zero (static_skip). With
    runtime offsets every block runs and the global-position mask does the
    work (callers skip whole fully-masked PARTIALS host-side instead: see
    _ring_fwd_impl; mixing the varying offset operands with program-id
    arithmetic in a pl.when predicate trips vma checking). The always-run
    case returns a traced truth (a literal ``True`` would inline the body,
    which equally trips the HLO interpreter's vma checks under
    shard_map). Works on Python ints too (``_tile_counts``)."""
    if not (causal and static_skip):
        return j >= 0
    if mode == _FULL:
        return j * bk + bk - 1 <= i * bq
    if mode == _SKIP:
        return j * bk == i * bq
    return ((j * bk <= i * bq + bq - 1) & (j * bk + bk - 1 > i * bq)
            & (j * bk != i * bq))


def _k_tiles(mode, a, tq, bk, tk):
    """Which k sub-tiles of the block q sub-tile ``a`` needs:
    ``(n_full, hi)``. Sub-tiles [0, n_full) lie wholly at or below the
    diagonal (no mask), [n_full, hi) are crossed by it (masked),
    [hi, bk // tk) lie wholly in the future (never computed)."""
    nks = bk // tk
    if mode == _FULL:
        return nks, nks
    if mode == _MASKED:
        return 0, nks
    return min(a * tq + 1, bk) // tk, min(a * tq + tq - 1 + tk, bk) // tk


def _q_tiles(mode, c, tk, bq, tq):
    """The same from k sub-tile ``c``'s side: ``(lo, lo_full)``. q
    sub-tiles [0, lo) lie wholly in the past of every key here (never
    computed), [lo, lo_full) are crossed by the diagonal, [lo_full,
    bq // tq) need no mask."""
    nqs = bq // tq
    if mode == _FULL:
        return 0, 0
    if mode == _MASKED:
        return 0, nqs
    return min(c * tk, bq) // tq, min(c * tk + tk + tq - 2, bq) // tq


class _Band(NamedTuple):
    """A grid cell of a WINDOWED call: visible pairs are ``0 <= t - s <
    window``. With ``bq == bk`` and zero offsets the cell at band offset
    ``jj`` of any q block has the same static distance ``d0`` from its
    first key to its first query, so, like the three modes above, what its
    sub-tiles do is known when the kernel is traced."""
    d0: int
    window: int

    def kind(self, a_minus_c: int, tq: int, tk: int):
        """What sub-tile (a, c), ``a_minus_c = a * tq - c * tk``, needs:
        ``None`` never computed (wholly in the future or wholly past the
        window), else which masks: ``""`` bare, ``"c"`` the diagonal
        crosses it, ``"w"`` the window's far edge does, ``"cw"`` both."""
        base = self.d0 + a_minus_c           # t - s at the tile's corner
        if base + tq - 1 < 0 or base - (tk - 1) >= self.window:
            return None
        return (("c" if base - (tk - 1) < 0 else "")
                + ("w" if base + tq - 1 >= self.window else ""))

    def whole(self, bq: int, bk: int) -> bool:
        """Is every pair of the cell visible?"""
        return self.kind(0, bq, bk) == ""


def _band_blocks(window: int, b: int, nk: int) -> int:
    """k blocks of ``b`` the band of one q block of ``b`` can touch: keys
    ``i * b - window + 1 .. i * b + b - 1``."""
    return min(nk, -(-(window - 1) // b) + 1)


def _band_cells(window: int, b: int, nkb: int):
    """``[(mode, lo, hi)]``: the band offsets ``lo..hi`` (0 the farthest
    block, ``nkb - 1`` the diagonal's) that run one copy of the body.
    Wholly visible cells share ``_FULL``'s."""
    cells = []
    for jj in range(nkb):
        band = _Band((nkb - 1 - jj) * b, window)
        mode = _FULL if band.whole(b, b) else band
        if cells and cells[-1][0] == mode == _FULL:
            cells[-1] = (mode, cells[-1][1], jj)
        else:
            cells.append((mode, jj, jj))
    return cells


class _Edge(NamedTuple):
    """A grid cell of a BLOCK-DIFFUSION call that a block edge crosses
    (docs/block_diffusion.md). Rows are ``[noised ; clean]``, ``L`` of
    each, position ``p`` in block ``p // B``; blocks and sub-tiles are
    square and ``B`` divides a sub-tile, so only the sub-tiles ON the
    cell's diagonal hold an edge, and what the others do is known when
    the kernel is traced, as in a band. ``edge`` says which cell:

    * ``"b"``: clean queries on the clean keys of their own positions:
      visible where ``blk(query) >= blk(key)`` (block-causal: the diagonal
      moved up to the end of the query's block);
    * ``"s"``: noised queries on the clean keys of their own positions:
      ``blk(query) > blk(key)`` (the diagonal moved down to the end of the
      PREVIOUS block); sub-tiles below the diagonal are bare in both;
    * ``"e"``: noised queries on the noised keys of their own positions:
      ``blk(query) == blk(key)``, the diagonal's sub-tiles and no other.
    """
    edge: str

    def kind(self, a_minus_c: int, tq: int, tk: int):
        """As :meth:`_Band.kind`: ``None`` never computed, ``""`` bare,
        else the edge as the mask a diagonal sub-tile builds."""
        if a_minus_c == 0:
            return self.edge
        return "" if a_minus_c > 0 and self.edge != "e" else None


def _pick(cond, a, b):
    """``a if cond else b``, on Python numbers (``_tile_counts``) and on
    program ids alike."""
    if isinstance(cond, (bool, int)):
        return a if cond else b
    return jnp.where(cond, a, b)


def _bd_k_block(i, j, n):
    """Forward and dq kernels of a block-diffusion call with ``n`` blocks a
    half: the k block that step ``j`` of q block ``i`` reads. A noised q
    block (``i < n``) reads its own noised k block FIRST (every row sees
    itself there, so the running maximum is finite from the first tile on
    and a row that sees nothing of a later tile needs no guard), then the
    clean blocks ``0 .. i``; a clean q block ``n + p`` the clean blocks
    ``0 .. p``. Past its last the index stays where it is: no fetch."""
    noised = i < n
    last = _pick(noised, i, i - n)
    c = j - _pick(noised, 1, 0)
    c = _pick(c < last, c, last)
    return _pick(noised & (j == 0), i, n + c)


def _bd_q_block(j, step, n):
    """The dk/dv kernel's side: the q block that ``step`` of k block ``j``
    reads. A noised k block is seen by the noised q block of its own
    positions alone; a clean one ``n + p`` by the noised q blocks
    ``p .. n - 1`` and then the clean ones ``n + p .. 2n - 1``."""
    p = j - n
    q = _pick(step < n - p, p + step, 2 * p + step)
    q = _pick(q < 2 * n - 1, q, 2 * n - 1)
    return _pick(j >= n, q, j)


def _bd_cells(x, step, n, kv_major=False):
    """``[(mode, is this cell one)]`` of a block-diffusion call: the copies
    of the body a kernel holds and the grid steps each runs at. ``x`` is
    the q block (forward, dq; ``_bd_k_block`` has the order of its steps)
    or, ``kv_major``, the k block (``_bd_q_block``). A step that is none of
    them runs nothing."""
    if kv_major:
        clean, m = x >= n, 2 * n - x      # m: q blocks a half from it on
        return [(_Edge("e"), (x < n) & (step == 0)),
                (_Edge("s"), clean & (step == 0)),
                (_FULL, clean & (step > 0) & (step < 2 * m) & (step != m)),
                (_Edge("b"), clean & (step == m))]
    noised = x < n
    p = _pick(noised, x, x - n)
    c = step - _pick(noised, 1, 0)        # the clean k block of this step
    return [(_Edge("e"), noised & (step == 0)),
            (_FULL, (c >= 0) & (c < p)),
            (_Edge("s"), noised & (c == p)),
            (_Edge("b"), (x >= n) & (c == p))]


def _k_plan(mode, a, tq, bk, tk):
    """``[(c, kind)]``: the k sub-tiles q sub-tile ``a`` computes, in
    order, each with the masks it needs (``_Band.kind``)."""
    if isinstance(mode, (_Band, _Edge)):
        return [(c, kind) for c in range(bk // tk)
                if (kind := mode.kind(a * tq - c * tk, tq, tk)) is not None]
    n_full, hi = _k_tiles(mode, a, tq, bk, tk)
    return [(c, "c" if c >= n_full else "") for c in range(hi)]


def _q_plan(mode, c, tk, bq, tq):
    """The same from k sub-tile ``c``'s side: ``[(a, kind)]``."""
    if isinstance(mode, (_Band, _Edge)):
        return [(a, kind) for a in range(bq // tq)
                if (kind := mode.kind(a * tq - c * tk, tq, tk)) is not None]
    lo, lo_full = _q_tiles(mode, c, tk, bq, tq)
    return [(a, "c" if a < lo_full else "") for a in range(lo, bq // tq)]


def _fwd_tiles(mode, bq: int, bk: int, sub_tile):
    """(tq, tk) of the FORWARD's walk of a cell: strips of ``tq`` queries,
    and ``tk`` the width of a sub-tile an edge crosses. A cell that can
    skip keeps its lattice (the pairs computed are the lattice's); any
    other is cut along the queries alone."""
    if _cuts(mode):
        return _sub_tiles(mode, bq, bk, sub_tile)
    return _sub_tile(bq, _STRIP), bk


def _strip_plan(mode, a, tq, bk, tk):
    """``(lo, hi, [(c0, kind)])``: the keys ``lo .. hi - 1`` of the cell
    that q strip ``a`` meets, as ONE tile, and the sub-tiles inside it that
    an edge crosses (first key ``c0``, ``tk`` wide, ``_Band.kind``); None
    for a strip that sees nothing of the cell. The sub-tiles a strip
    computes lie side by side (``_k_plan``: what is skipped lies past the
    diagonal or past the window, at the ends)."""
    plan = _k_plan(mode, a, tq, bk, tk)
    if not plan:
        return None
    (first, _), (last, _) = plan[0], plan[-1]
    assert [c for c, _ in plan] == list(range(first, last + 1)), plan
    return (first * tk, (last + 1) * tk,
            [(c * tk, kind) for c, kind in plan if kind])


def _visible(s, diag, kind, off, window, transposed=False):
    """Scores with the invisible entries of a masked sub-tile at -1e30.
    ``diag`` is the hoisted row minus column index; ``off`` the scalar the
    diagonal sits at: first key minus first query of the sub-tile, or,
    ``transposed`` (keys down the rows), first query minus first key. The
    window's far edge is the same compare against a second scalar."""
    if not kind:
        return s
    if kind in ("b", "s", "e"):
        # Block-diffusion: ``diag`` is the difference of the BLOCK indices
        # (``_block_diagonal``), of a sub-tile on its cell's diagonal.
        if kind == "e":
            seen = diag == 0
        elif transposed:
            seen = diag <= 0 if kind == "b" else diag < 0
        else:
            seen = diag >= 0 if kind == "b" else diag > 0
        return jnp.where(seen, s, _NEG_INF)
    if transposed:
        seen = diag <= off if "c" in kind else None
        if "w" in kind:
            near = diag > off - window
            seen = near if seen is None else seen & near
    else:
        seen = diag >= off if "c" in kind else None
        if "w" in kind:
            near = diag < off + window
            seen = near if seen is None else seen & near
    return jnp.where(seen, s, _NEG_INF)


def _diagonal(tq, tk):
    """Row index minus column index over a sub-tile: with it the causal
    mask of any sub-tile is one compare against a scalar."""
    return (jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1))


def _block_diagonal(tq, tk, block):
    """The row's block minus the column's over a sub-tile that starts on a
    block edge both ways: the three masks of a block-diffusion call are
    one compare of it against 0 (``_visible``)."""
    shift = block.bit_length() - 1
    return (jnp.right_shift(jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0),
                            shift)
            - jnp.right_shift(jax.lax.broadcasted_iota(jnp.int32, (tq, tk),
                                                       1), shift))


def _mask_index(mode, tq, tk, bd):
    """What a masked sub-tile of ``mode`` compares: nothing in a cell
    without a mask."""
    if mode == _FULL:
        return None
    if isinstance(mode, _Edge):
        return _block_diagonal(tq, tk, bd[0])
    return _diagonal(tq, tk)


def _first_q_minus_k(qoff_ref, koff_ref, i, j, bq, bk, mode):
    """GLOBAL position of the block's first query minus its first key's.
    Offsets arrive as operands (see ``_scalar_spec``) so ring/sharded
    callers can pass traced values (e.g. ``axis_index * T_local``). Only a
    masked cell asks; an aligned one has 0 by definition."""
    if isinstance(mode, _Band):
        return mode.d0
    if mode != _MASKED:
        return 0
    return (qoff_ref[...][0, 0, 0] + i * bq
            - koff_ref[...][0, 0, 0] - j * bk)


def _last_k_block(causal, static_skip, i, bq, bk, nk, band=None, bd=None):
    """The last k block that q block ``i`` runs (``_cell_is``); in a
    windowed call the band's last, the diagonal's; in a block-diffusion
    call the last step of ``_bd_k_block``."""
    if bd:
        return jnp.where(i < bd[1], i + 1, i - bd[1])
    if band:
        return band[1] - 1
    if causal and static_skip:
        return jnp.minimum(nk - 1, (i * bq + bq - 1) // bk)
    return nk - 1


def _each_mode(body, causal, static_skip, i, j, bq, bk, nq, nk, band=None,
               valid=None, cells=None):
    """One copy of ``body`` per kind of cell the call can meet, each under
    its ``pl.when``. In a windowed call (``band`` = (window, blocks a
    band)) ``j`` is the cell's offset inside the band and ``valid`` says
    whether the band has a block there (it sticks out of the sequence at
    its ends). A block-diffusion call hands its ``cells`` (``_bd_cells``)."""
    if cells is not None:
        for mode, here in cells:
            pl.when(here)(functools.partial(body, mode))
        return
    if band is None:
        for mode in _cell_modes(causal, static_skip, nq, nk, bq, bk):
            pl.when(_cell_is(mode, causal, static_skip, i, j, bq, bk))(
                functools.partial(body, mode))
        return
    for mode, lo, hi in _band_cells(band[0], bq, band[1]):
        here = (j == lo) if lo == hi else (j >= lo) & (j <= hi)
        pl.when(valid & here)(functools.partial(body, mode))


def _grid_cells(causal, static_skip, nq, nk, bq, bk, window=None,
                block=None):
    """``{(i, j): mode or None}``: what each (q block, k block) of one
    head's square is to a call: the mode of the cell that runs there, None
    where nothing runs (a causal call's future, a windowed call's cells
    outside the band, the cells a block-diffusion call never visits)."""
    cells = {(i, j): None for i in range(nq) for j in range(nk)}
    if block is not None:
        n = nq // 2
        for i in range(nq):
            for step in range(n + 1):
                for mode, here in _bd_cells(i, step, n):
                    if here:
                        cells[i, _bd_k_block(i, step, n)] = mode
    elif window is None:
        modes = _cell_modes(causal, static_skip, nq, nk, bq, bk)
        for i, j in cells:
            for mode in modes:
                if _cell_is(mode, causal, static_skip, i, j, bq, bk):
                    cells[i, j] = mode
                    break
    else:
        nkb = _band_blocks(window, bq, nk)
        for mode, lo, hi in _band_cells(window, bq, nkb):
            for i in range(nq):
                for jj in range(lo, hi + 1):
                    if i - (nkb - 1) + jj >= 0:
                        cells[i, i - (nkb - 1) + jj] = mode
    return cells


def _tile_counts(causal, static_skip, nq, nk, bq, bk, window=None,
                 block=None):
    """(total, computed, masked) sub-tiles of one head, over the grid; a
    cell that never runs counts at the lattice of the mode it is nearest
    to (the last one: a future cell of a causal call is ``_SKIP``'s). A
    windowed call's grid holds the band's cells alone; the cells outside
    it count to the total, at the cut-up lattice; so do the cells a
    block-diffusion call (``block`` its block length) never visits."""
    total = computed = masked = 0
    for mode in _grid_cells(causal, static_skip, nq, nk, bq, bk, window,
                            block).values():
        tq, tk = _sub_tiles(_SKIP if mode is None else mode, bq, bk,
                            _SUB_TILE)
        total += (bq // tq) * (bk // tk)
        for a in range(0 if mode is None else bq // tq):
            plan = _k_plan(mode, a, tq, bk, tk)
            computed += len(plan)
            masked += sum(1 for _, kind in plan if kind)
    return total, computed, masked


def _strip_count(causal, static_skip, nq, nk, bq, bk, window=None,
                 block=None):
    """The q strips the FORWARD walks over one head's grid: a strip of a
    cell that sees something of it. Each makes one online-softmax update
    (``_fwd_kernel``): its keys are one tile."""
    strips = 0
    for mode in _grid_cells(causal, static_skip, nq, nk, bq, bk, window,
                            block).values():
        if mode is not None:
            tq, tk = _fwd_tiles(mode, bq, bk, _SUB_TILE)
            strips += sum(_strip_plan(mode, a, tq, bk, tk) is not None
                          for a in range(bq // tq))
    return strips


def _count_tiles(kernel, *args, window=None, block=None):
    """Trace-time counters of how often the in-cell tiling engages, per
    head and kernel call (monitor registry, as plan/accounting.py keeps
    trace-time wire bytes): nothing of this runs on the device. A windowed
    call counts under a label of its own, ``window`` = its width, a
    block-diffusion call under ``block`` = its block length. The forward
    also counts its walk: ``flash.strips``, the q strips it walks, and
    ``flash.softmax_updates``, the times it brings a strip's running
    maximum, normalizer and accumulator up to date: one a strip, where a
    walk sub-tile by sub-tile made one a computed sub-tile."""
    from ..monitor.registry import counter

    labels = dict(kernel=kernel)
    if window is not None:
        labels["window"] = str(window)
    if block is not None:
        labels["block"] = str(block)
    for name, n in zip(("total", "computed", "masked"),
                       _tile_counts(*args, window=window, block=block)):
        counter(f"flash.tiles_{name}", **labels).inc(n)
    if kernel == "fwd":
        strips = _strip_count(*args, window=window, block=block)
        counter("flash.strips", **labels).inc(strips)
        counter("flash.softmax_updates", **labels).inc(strips)


def _as_row(col):
    """[t, 1] → [1, t]: a per-query statistic laid along the lanes, as the
    statistics lie in HBM and as the dk/dv kernel's transposed score tiles
    want them. Also from [t, 128], the statistic on every lane of its row
    (the forward's scratch)."""
    t = col.shape[0]
    if t % 128:
        return col[:, :1].T
    return jnp.broadcast_to(col, (t, 128)).T[0:1, :]


def _as_cols(*rows):
    """[1, t] rows → [t, 1] columns, back down the sublanes for the dq
    kernel's score tiles: stacked, so that all go through one transpose
    (of 128 sublanes: 13% faster a dq call than one of 8, and than a
    transpose a row)."""
    t = rows[0].shape[1]
    if t % 128:
        return tuple(row.T for row in rows)
    shape = (128, t)
    sublane = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    stacked = jnp.broadcast_to(rows[-1], shape)
    for n in range(len(rows) - 2, -1, -1):
        stacked = jnp.where(sublane == n, jnp.broadcast_to(rows[n], shape),
                            stacked)
    cols = stacked.T
    return tuple(cols[:, n:n + 1] for n in range(len(rows)))


def _per_row(stat, like):
    """A row statistic as a factor of ``like``: the forward's scratch keeps
    a statistic on all 128 lanes of its rows (``_fwd_call``), a reduction
    gives one column; either multiplies a 128-lane tile as it is."""
    if stat.shape[1] in (1, like.shape[1]):
        return stat
    return stat[:, :1]


def _less_row_stat(s, stat):
    """``s - stat`` for a tile ``s`` [t, W] and a statistic of its rows: a
    [t, 1] column, or on every lane of [t, 128] (the forward's state). The
    latter is taken off 128 lanes at a time, register by register: cut
    down to a column first it would go through a lane broadcast on the
    XLU, which made an uncut cell 5% and a band's far cell 67% slower by
    the compiled schedule (PERF.md, PR 42)."""
    if stat.shape[1] == 1:
        return s - stat
    w = min(s.shape[1], stat.shape[1])
    stat = stat[:, :w]
    if s.shape[1] == w:
        return s - stat
    return jnp.concatenate([s[:, c:c + w] - stat
                            for c in range(0, s.shape[1], w)], axis=1)


def _fwd_out(m, l, acc, o_dtype):
    """(o, lse [1, t]) of finished rows. Fully-masked rows have l == 0:
    emit o = 0 and lse = -inf-like so a ring merge weights them out.
    Visible rows always have l > 0 (a causal row sees at least its own
    token). A statistic is one dense row of lanes per head, [N * heads, 1,
    T] in HBM: as [.., T, 8] columns it was written lane-padded to 16 times
    its size, and the kernels' largest operand."""
    safe_l = jnp.maximum(l, 1e-30)
    lse = jnp.where(l > 0, m + jnp.log(safe_l), _NEG_INF)
    return (acc / _per_row(safe_l, acc)).astype(o_dtype), _as_row(lse)


def _fwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr,
                *, scale, causal, bq, bk, nq, nk, static_skip, sub_tile, G,
                band=None, bd=None):
    """A cell is walked as STRIPS of queries, each against the keys it
    sees of the cell as ONE tile (``_strip_plan``): one pair of matmuls,
    one pass of maximum, exponential and sum and one update of the rows'
    state a strip, whatever lies under it (a run of bare sub-tiles with a
    crossed one at either end). The strips of a cell are independent
    chains, and they are issued as a pipeline: strip a + 1's score matmul
    stands in the source before strip a's softmax, since the compiler
    assigns the two kinds of matmul to two MXUs each and schedules close
    to source order: only with both kinds in flight are the four busy
    (PERF.md, PR 42). The arithmetic of an update is what it was; where a
    strip's tile is one sub-tile, so are its bits."""
    i = pl.program_id(2)   # q block
    g = pl.program_id(3)   # head inside the lane block (``_specs``)
    j = pl.program_id(4)   # k block (innermost: scratch carries across j);
    #                        windowed: its offset inside the band;
    #                        block diffusion: a step of ``_bd_k_block``
    window, inner = band if band else (None, bd[1] + 1 if bd else nk)
    carried = inner > 1    # else a q strip finishes inside this cell

    if carried:
        @pl.when(j == 0)
        def _init():
            m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
            l_scr[:] = jnp.zeros_like(l_scr)
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def body(mode):
        tq, tk = _fwd_tiles(mode, bq, bk, sub_tile)
        d0 = _first_q_minus_k(qoff_ref, koff_ref, i, j, bq, bk, mode)
        diag = _mask_index(mode, tq, tk, bd)
        qscale = _on_head(g, G, q_ref, scale)
        walk = [(a, plan) for a in range(bq // tq)
                if (plan := _strip_plan(mode, a, tq, bk, tk))]

        def scores(a, plan):
            """Strip ``a``'s scaled queries against its keys, [tq, hi - lo]
            float32, the sub-tiles an edge crosses masked in place."""
            lo, hi, crossed = plan
            q = q_ref[0, pl.ds(a * tq, tq), :] * qscale     # [tq, D], once
            s = jax.lax.dot_general(
                q, k_ref[0, pl.ds(lo, hi - lo), :], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            if not crossed:
                return s
            parts, done = [], lo
            for c0, kind in crossed:
                if c0 > done:
                    parts.append(s[:, done - lo:c0 - lo])
                parts.append(_visible(s[:, c0 - lo:c0 - lo + tk], diag, kind,
                                      c0 - a * tq - d0, window))
                done = c0 + tk
            if done < hi:
                parts.append(s[:, done - lo:])
            return parts[0] if len(parts) == 1 else jnp.concatenate(
                parts, axis=1)

        def finish(a, plan, s):
            """The strip's softmax over its tile and ``p v``: the one update
            of its rows' state, or, where a q block has this one cell, its
            output."""
            lo, hi, crossed = plan
            rows = pl.ds(a * tq, tq)
            m_new = jnp.max(s, axis=1, keepdims=True)
            if carried:
                m_prev = m_scr[rows, :]
                m_new = jnp.maximum(m_prev, m_new)
            m_sub = m_new
            if not static_skip or any("w" in kind for _, kind in crossed):
                # Fully-masked rows (a ring partial that sees a k block
                # entirely in its causal future; a row whose window ends
                # before this tile, the first its q block meets): m_new
                # stays at _NEG_INF and s - m_new == 0 would wrongly give
                # p = 1. Subtracting 0 there instead gives
                # p = exp(-1e30) = 0.
                m_sub = jnp.where(m_new > _NEG_INF / 2, m_new, 0.0)
            p = jnp.exp(_less_row_stat(s, m_sub))
            l = jnp.sum(p, axis=1, keepdims=True)
            acc = jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, pl.ds(lo, hi - lo), :],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if carried:
                alpha = jnp.exp(m_prev - m_new)
                m_scr[rows, :] = m_new
                l_scr[rows, :] = l_scr[rows, :] * alpha + l
                acc_scr[rows, :] = (acc_scr[rows, :] * _per_row(alpha, acc)
                                    + acc)
            else:
                o, lse_ref[0, :, rows] = _fwd_out(m_new, l, acc, o_ref.dtype)
                _put(o_ref, (0, rows), o, g, G)

        s = scores(*walk[0])
        for n, (a, plan) in enumerate(walk):
            ahead = scores(*walk[n + 1]) if n + 1 < len(walk) else None
            finish(a, plan, s)
            s = ahead

    _each_mode(body, causal, static_skip, i, j, bq, bk, nq, nk, band,
               band and j >= inner - 1 - i, bd and _bd_cells(i, j, bd[1]))

    if carried:
        @pl.when(j == _last_k_block(causal, static_skip, i, bq, bk, nk,
                                    band, bd))
        def _finish():
            o, lse_ref[0] = _fwd_out(
                m_scr[:], l_scr[:], acc_scr[:], o_ref.dtype)
            _put(o_ref, (0,), o, g, G)


def _statics(scale, causal, bq, bk, static_skip, heads, window=None,
             group=1, block=None):
    """What a kernel call is specialised on, read where the call is made."""
    if (window is not None or block is not None) and not (
            causal and static_skip and bq == bk):
        raise ValueError(
            "a windowed or block-diffusion flash call is causal, has zero "
            "offsets (no ring partial) and square blocks, got "
            f"causal={causal} static_skip={static_skip} blocks ({bq}, {bk})")
    if block is not None and (window is not None
                              or _sub_tile(bq, _SUB_TILE[0]) % block):
        raise ValueError(
            f"a block-diffusion call has no window and its block length "
            f"({block}) divides a sub-tile of its blocks ({bq})")
    return dict(scale=scale, causal=causal, bq=bq, bk=bk,
                static_skip=static_skip, heads=heads, sub_tile=_SUB_TILE,
                interpret=_interpret(), window=window, group=group,
                block=block)


# The three pallas_calls are traced once per (operand shapes, statics) and
# inlined wherever they are called: a 24-layer step calls each 24 times, and
# tracing the unrolled sub-tile bodies anew every time cost its set-up 20 s.
_traced_once = functools.partial(
    jax.jit, inline=True,
    static_argnames=("scale", "causal", "bq", "bk", "static_skip", "heads",
                     "sub_tile", "interpret", "window", "group", "block"))


def _specs(heads, width, bq, bk, kv_major=False, group=1, band=None,
           nq=None, bd=None):
    """``(P, G, lanes, q_rows, k_rows, stats)`` of one kernel call over
    operands ``[N, T, width]`` with ``heads`` heads side by side. How the
    last dimension is cut: ``lanes`` a block, ``P`` blocks a row, ``G``
    heads inside a block. One head (the packed layout): the whole
    dimension, whatever its width. Several (``_reads_in_place``): 128
    lanes, so that every block is a dense HBM tile and a dense vector
    store, and the G heads of a block take turns on a grid axis of their
    own; the block indices do not depend on it, so a block is fetched once
    for all of them and the output block stays put.

    The grid is (N, P, outer blocks, G, inner blocks): rows of the batch
    (times heads when packed), lane blocks, the blocks the output belongs
    to, the heads of a lane block, the blocks accumulated over; with
    several inner blocks a lane block's are fetched once per head of it.
    ``q_rows`` / ``k_rows`` make the spec of a [N, T, width] operand
    blocked along the queries / keys, ``stats`` that of a per-query
    [N * heads, 1, Tq] statistic (``_fwd_out``).

    Grouped KV heads (``group`` query heads share one; a head is a block
    then, ``_in_place``): ``heads`` and ``width`` are q's, and k, v are
    ``[N, T, width / group]``, or ``[N / group, T, width]`` packed. The
    forward and dq kernels keep their grid and find q head ``h``'s keys in
    block ``h // group``. The dk/dv kernel's N and P count KV heads and its
    fourth axis is the query head inside the group: the k, v and dk, dv
    block indices do not depend on it, so a KV block is fetched once for
    its group and dk, dv are summed over the group in the kernel's scratch.

    Windowed (``band`` = blocks a band, with ``nq`` q blocks): the inner
    axis walks the band and not the sequence: offset ``jj`` of q block
    ``i`` is k block ``i - (band - 1) + jj``, and q block ``j + ii`` past
    k block ``j`` in the dk/dv kernel. Where the band sticks out of the
    sequence the index stays on the edge block (already there, or next to
    come: no fetch of its own) and the kernel skips the cell. No block
    outside the band is fetched.

    Block diffusion (``bd`` = blocks a half; rows ``[noised ; clean]``):
    the inner axis walks the steps of ``_bd_k_block`` (``bd + 1`` of them)
    or, in the dk/dv kernel, of ``_bd_q_block`` (``2 * bd``): the cells the
    mask touches in the order they are taken, the index at rest past the
    last."""
    if heads == 1:
        lanes, P, G = width, 1, 1
    else:
        lanes, P, G = _LANES, width // _LANES, _LANES * heads // width
    if group > 1 and G > 1:
        raise ValueError("grouped KV heads share no lane block "
                         "(``_in_place``)")
    if kv_major:   # the dk/dv kernel: (n, p, j, g, i)
        qi, ki = 4, 2
        P //= group if heads > 1 else 1
    else:          # forward and dq: (n, p, i, g, j)
        qi, ki = 2, 4

    def q_block(ids):
        if bd and kv_major:
            return _bd_q_block(ids[ki], ids[qi], bd)
        if band and kv_major:
            return jnp.minimum(ids[ki] + ids[qi], nq - 1)
        return ids[qi]

    def k_block(ids):
        if bd and not kv_major:
            return _bd_k_block(ids[qi], ids[ki], bd)
        if band and not kv_major:
            return jnp.maximum(ids[qi] - (band - 1) + ids[ki], 0)
        return ids[ki]

    if group == 1:
        q_at = lambda ids: (ids[0], q_block(ids), ids[1])
        k_at = lambda ids: (ids[0], k_block(ids), ids[1])
        head = lambda ids: (ids[0] * P + ids[1]) * G + ids[3]
    elif kv_major:   # n, p count KV heads; ids[3] the head in the group
        if heads == 1:
            q_at = lambda ids: (ids[0] * group + ids[3], q_block(ids), 0)
        else:
            q_at = lambda ids: (ids[0], q_block(ids),
                                ids[1] * group + ids[3])
        k_at = lambda ids: (ids[0], k_block(ids), ids[1])
        head = lambda ids: (ids[0] * P + ids[1]) * group + ids[3]
    else:            # n, p count query heads
        q_at = lambda ids: (ids[0], q_block(ids), ids[1])
        if heads == 1:
            k_at = lambda ids: (ids[0] // group, k_block(ids), 0)
        else:
            k_at = lambda ids: (ids[0], k_block(ids), ids[1] // group)
        head = lambda ids: ids[0] * P + ids[1]
    q_rows = lambda: pl.BlockSpec((1, bq, lanes), lambda *ids: q_at(ids))
    k_rows = lambda: pl.BlockSpec((1, bk, lanes), lambda *ids: k_at(ids))
    stats = lambda: pl.BlockSpec(
        (1, 1, bq), lambda *ids: (head(ids), 0, q_block(ids)))
    return P, G, lanes, q_rows, k_rows, stats


# A windowed call's three kernels carry names of their own: a reader that
# matches an op's whole name tells them from the full calls, one that looks
# for the full calls' names as substrings takes all six for kernels.
_WIN = "_win"
# ... and a block-diffusion call's, for the same reason.
_BD = "_bd"


def _band(window, b, nk):
    """(window, blocks a band) of a windowed call, else None."""
    return None if window is None else (window, _band_blocks(window, b, nk))


def _halves(block, nq):
    """(block length, blocks a half) of a block-diffusion call, else
    None."""
    return None if block is None else (block, nq // 2)


# The head axis revisits the output block, the innermost one accumulates.
_SEMANTICS = ("parallel", "parallel", "parallel", "arbitrary", "arbitrary")


def _flash_fwd(q, k, v, scale, causal, bq, bk, q_off=0, k_off=0,
               static_skip=True, heads=1, window=None, group=1, block=None):
    """q,k,v: [N, T, heads * D] → (o [N, Tq, heads * D], lse [N * heads,
    1, Tq] f32): ``heads`` = 1 is the packed layout ([B * H, T, D]), more
    the projections' own ([B, T, H * D], ``_reads_in_place``).

    ``q_off``/``k_off`` are global positions of the first query/key token
    (may be traced, e.g. ``lax.axis_index(...) * T_local`` under a ring);
    pass ``static_skip=False`` whenever they can be nonzero. ``window``:
    query t sees keys t - window + 1 .. t. ``group``: query heads a KV
    head (k, v hold ``heads / group`` heads, or N / group rows packed).
    ``block``: the rows are ``[noised ; clean]`` under the block-diffusion
    mask of that block length."""
    _count_tiles("fwd", causal, static_skip, q.shape[1] // bq,
                 k.shape[1] // bk, bq, bk, window=window, block=block)
    return _fwd_call(q_off, k_off, q, k, v,
                     **_statics(scale, causal, bq, bk, static_skip, heads,
                                window, group, block))


def _inner_steps(band, bd, n, kv_major=False):
    """Length of a kernel's innermost grid axis: the band's blocks, the
    steps of a block-diffusion call, else all ``n`` blocks."""
    if bd:
        return 2 * bd[1] if kv_major else bd[1] + 1
    return band[1] if band else n


@_traced_once
def _fwd_call(q_off, k_off, q, k, v, *, scale, causal, bq, bk, static_skip,
              heads, sub_tile, interpret, window, group, block):
    N, Tq, W = q.shape
    Tk = k.shape[1]
    nq, nk = Tq // bq, Tk // bk
    band, bd = _band(window, bq, nk), _halves(block, nq)
    P, G, lanes, q_rows, k_rows, stats = _specs(
        heads, W, bq, bk, group=group, band=band and band[1], nq=nq,
        bd=bd and bd[1])
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               bq=bq, bk=bk, nq=nq, nk=nk,
                               static_skip=static_skip, sub_tile=sub_tile,
                               G=G, band=band, bd=bd)
    return pl.pallas_call(
        kernel,
        grid=(N, P, nq, G, _inner_steps(band, bd, nk)),
        in_specs=[_scalar_spec(), _scalar_spec(),
                  q_rows(), k_rows(), k_rows()],
        out_specs=[q_rows(), stats()],
        out_shape=[
            _out_struct((N, Tq, W), q.dtype, q, k, v, q_off, k_off),
            _out_struct((N * heads, 1, Tq), jnp.float32, q, k, v, q_off,
                        k_off),
        ],
        # The running max m and normalizer l of a row on ALL the lanes of
        # its scratch row: a [bq, 1] column takes as many registers and a
        # lane broadcast (XLU) on every load; at D = 128 the accumulator is
        # rescaled by the loaded register as it is.
        scratch_shapes=[
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running max m
            pltpu.VMEM((bq, _LANES), jnp.float32),   # running normalizer l
            pltpu.VMEM((bq, lanes), jnp.float32),    # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="hvd_flash_fwd" + _WIN * bool(band) + _BD * bool(bd),
    )(_as_scalar(q_off), _as_scalar(k_off), q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------


def _probs(s, lse, static_skip):
    """p = exp(s - lse) over a sub-tile. Masked entries: s = -1e30 and
    finite lse → p = 0 automatically. Fully-masked rows (ring partials
    only) have lse = -1e30 from the forward, giving
    exp(-1e30 - (-1e30)) = 1 on masked entries: subtract 0 there instead."""
    if not static_skip:
        lse = jnp.where(lse > _NEG_INF / 2, lse, 0.0)
    return jnp.exp(s - lse)


def _bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                   delta_ref, dq_ref, acc_scr,
                   *, scale, causal, bq, bk, nq, nk, static_skip, sub_tile,
                   G, band=None, bd=None):
    i = pl.program_id(2)
    g = pl.program_id(3)
    j = pl.program_id(4)   # windowed: the cell's offset inside the band;
    #                        block diffusion: a step of ``_bd_k_block``
    window, inner = band if band else (None, bd[1] + 1 if bd else nk)
    carried = inner > 1

    if carried:
        @pl.when(j == 0)
        def _init():
            acc_scr[:] = jnp.zeros_like(acc_scr)

    def body(mode):
        tq, tk = _sub_tiles(mode, bq, bk, sub_tile)
        d0 = _first_q_minus_k(qoff_ref, koff_ref, i, j, bq, bk, mode)
        diag = _mask_index(mode, tq, tk, bd)
        qscale = _on_head(g, G, q_ref, scale)
        own = _on_head(g, G, do_ref, 1.0)

        for a in range(bq // tq):
            rows = pl.ds(a * tq, tq)
            q = q_ref[0, rows, :] * qscale
            do = do_ref[0, rows, :]
            if G > 1:   # dp contracts over the lanes: this head's only
                do = do * own
            lse, delta = _as_cols(lse_ref[0, :, rows], delta_ref[0, :, rows])
            acc = jnp.zeros((tq, q.shape[1]), jnp.float32)
            for c, kind in _k_plan(mode, a, tq, bk, tk):
                cols = pl.ds(c * tk, tk)
                k = k_ref[0, cols, :]
                s = jax.lax.dot_general(
                    q, k, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [tq, tk]
                s = _visible(s, diag, kind, c * tk - a * tq - d0,
                             window)
                p = _probs(s, lse, static_skip)
                dp = jax.lax.dot_general(
                    do, v_ref[0, cols, :], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [tq, tk]
                ds = p * (dp - delta)
                acc += jax.lax.dot_general(
                    ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if carried:
                acc_scr[rows, :] += acc
            else:
                _put(dq_ref, (0, rows), (acc * scale).astype(dq_ref.dtype),
                     g, G)

    _each_mode(body, causal, static_skip, i, j, bq, bk, nq, nk, band,
               band and j >= inner - 1 - i, bd and _bd_cells(i, j, bd[1]))

    if carried:
        @pl.when(j == _last_k_block(causal, static_skip, i, bq, bk, nk,
                                    band, bd))
        def _finish():
            _put(dq_ref, (0,), (acc_scr[:] * scale).astype(dq_ref.dtype),
                 g, G)


def _bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                    *, scale, causal, bq, bk, nq, nk, static_skip, sub_tile,
                    G, band=None, group=1, bd=None):
    j = pl.program_id(2)   # k block
    g = pl.program_id(3)   # head inside the lane block; with grouped KV
    #                        heads (one head a block then) the query head
    #                        inside the group: dk, dv sum over it here
    i = pl.program_id(4)   # q block (innermost: scratch carries across i);
    #                        windowed: how many blocks past the k block;
    #                        block diffusion: a step of ``_bd_q_block``
    window, inner = band if band else (None, nq)
    carried = inner > 1 or group > 1

    def at(step, head):
        """At the inner axis's ``step`` (and, grouped, on ``head``)?"""
        return (i == step) & (g == head) if group > 1 else i == step

    if carried:
        @pl.when(at(0, 0))
        def _init():
            dk_scr[:] = jnp.zeros_like(dk_scr)
            dv_scr[:] = jnp.zeros_like(dv_scr)

    def body(mode):
        # The other way round: outer over k sub-tiles, inner over the q
        # sub-tiles from the diagonal down, and every tile transposed
        # ([tk, tq]: keys down the sublanes), so that dv = p^T do and
        # dk = ds^T q are plain matmuls and no [tq, tk] tile goes through
        # the transpose unit.
        tq, tk = _sub_tiles(mode, bq, bk, sub_tile)
        d0 = _first_q_minus_k(qoff_ref, koff_ref, i, j, bq, bk, mode)
        diag = _mask_index(mode, tk, tq, bd)
        nqs = bq // tq
        kscale = _on_head(g, G, k_ref, scale)
        own = _on_head(g, G, v_ref, 1.0)
        lse = [lse_ref[0, :, pl.ds(a * tq, tq)] for a in range(nqs)]
        delta = [delta_ref[0, :, pl.ds(a * tq, tq)] for a in range(nqs)]

        for c in range(bk // tk):
            cols = pl.ds(c * tk, tk)
            k = k_ref[0, cols, :] * kscale              # [tk, D], once
            v = v_ref[0, cols, :]
            if G > 1:   # dp contracts over the lanes: this head's only
                v = v * own
            dk = dv = jnp.zeros((tk, k.shape[1]), jnp.float32)
            for a, kind in _q_plan(mode, c, tk, bq, tq):
                rows = pl.ds(a * tq, tq)
                q = q_ref[0, rows, :]
                do = do_ref[0, rows, :]
                s = jax.lax.dot_general(
                    k, q, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [tk, tq]
                # key index - query index <= first q - first k
                s = _visible(s, diag, kind, a * tq + d0 - c * tk, window,
                             transposed=True)
                p = _probs(s, lse[a], static_skip)
                dv += jax.lax.dot_general(
                    p.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [tk, D]
                dp = jax.lax.dot_general(
                    v, do, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [tk, tq]
                ds = p * (dp - delta[a])
                dk += jax.lax.dot_general(
                    ds.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)  # [tk, D]
            # k carried the scale into s; dk = scale * ds^T q is still due it.
            if carried:
                dk_scr[cols, :] += dk
                dv_scr[cols, :] += dv
            else:
                _put(dk_ref, (0, cols), (dk * scale).astype(dk_ref.dtype),
                     g, G)
                _put(dv_ref, (0, cols), dv.astype(dv_ref.dtype), g, G)

    if bd:
        _each_mode(body, causal, static_skip, i, j, bq, bk, nq, nk,
                   cells=_bd_cells(j, i, bd[1], kv_major=True))
    elif band:
        # The q block ``i`` blocks past k block ``j`` sees it as its band's
        # cell ``inner - 1 - i`` (offset 0 is a q block's farthest).
        _each_mode(body, causal, static_skip, i, inner - 1 - i, bq, bk, nq,
                   nk, band, i < nq - j)
    else:
        _each_mode(body, causal, static_skip, i, j, bq, bk, nq, nk)

    if carried:
        @pl.when(at(inner - 1, group - 1))
        def _finish():
            _put(dk_ref, (0,), (dk_scr[:] * scale).astype(dk_ref.dtype),
                 g, G)
            _put(dv_ref, (0,), dv_scr[:].astype(dv_ref.dtype), g, G)


def _prep_residuals(o, do, heads=1):
    """delta = rowsum(dO ⊙ O) per head, [N * heads, 1, Tq] as the kernels
    read a statistic, from the arrays as they lie."""
    N, T, W = o.shape
    prod = do.astype(jnp.float32) * o.astype(jnp.float32)
    if heads == 1:
        delta = jnp.sum(prod, axis=-1)                   # [BH, Tq]
    else:
        # A sum over each head's D lanes of [B, T, H * D], as a matmul with
        # the heads' 0/1 indicator [H, H * D]: it gives [B, H, T], the
        # statistics' order, and the product is its operand's fusion. As a
        # reduce over [B, T, H, D] the compiler wrote the f32 product out
        # and transposed it first (3 passes over 33.5 MB a layer of
        # gpt2-medium).
        own = (jnp.arange(W)[None, :] // (W // heads)
               == jnp.arange(heads)[:, None]).astype(jnp.float32)
        delta = jax.lax.dot_general(
            jnp.broadcast_to(own, (N, heads, W)), prod,
            (((2,), (2,)), ((0,), (0,))),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
    return delta.reshape(N * heads, 1, T)


def _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, bq, bk,
                  q_off=0, k_off=0, static_skip=True, heads=1, window=None,
                  group=1, block=None):
    _count_tiles("bwd_dq", causal, static_skip, q.shape[1] // bq,
                 k.shape[1] // bk, bq, bk, window=window, block=block)
    return _bwd_dq_call(q_off, k_off, q, k, v, do, lse, delta,
                        **_statics(scale, causal, bq, bk, static_skip,
                                   heads, window, group, block))


@_traced_once
def _bwd_dq_call(q_off, k_off, q, k, v, do, lse, delta, *, scale, causal,
                 bq, bk, static_skip, heads, sub_tile, interpret, window,
                 group, block):
    N, Tq, W = q.shape
    nq, nk = Tq // bq, k.shape[1] // bk
    band, bd = _band(window, bq, nk), _halves(block, nq)
    P, G, lanes, q_rows, k_rows, stats = _specs(
        heads, W, bq, bk, group=group, band=band and band[1], nq=nq,
        bd=bd and bd[1])
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nk=nk,
                          static_skip=static_skip, sub_tile=sub_tile, G=G,
                          band=band, bd=bd),
        grid=(N, P, nq, G, _inner_steps(band, bd, nk)),
        in_specs=[_scalar_spec(), _scalar_spec(),
                  q_rows(), k_rows(), k_rows(),       # q, k, v
                  q_rows(), stats(), stats()],        # do, lse, delta
        out_specs=q_rows(),
        out_shape=_out_struct((N, Tq, W), q.dtype, q, k, v, do, lse,
                              delta, q_off, k_off),
        scratch_shapes=[pltpu.VMEM((bq, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="hvd_flash_bwd_dq" + _WIN * bool(band) + _BD * bool(bd),
    )(_as_scalar(q_off), _as_scalar(k_off), q, k, v, do, lse, delta)


def _flash_bwd_dkv(q, k, v, do, lse, delta, scale, causal, bq, bk,
                   q_off=0, k_off=0, static_skip=True, heads=1, window=None,
                   group=1, block=None):
    _count_tiles("bwd_dkv", causal, static_skip, q.shape[1] // bq,
                 k.shape[1] // bk, bq, bk, window=window, block=block)
    return _bwd_dkv_call(q_off, k_off, q, k, v, do, lse, delta,
                         **_statics(scale, causal, bq, bk, static_skip,
                                    heads, window, group, block))


@_traced_once
def _bwd_dkv_call(q_off, k_off, q, k, v, do, lse, delta, *, scale, causal,
                  bq, bk, static_skip, heads, sub_tile, interpret, window,
                  group, block):
    N, Tk, W = k.shape
    nq, nk = q.shape[1] // bq, Tk // bk
    band, bd = _band(window, bq, nk), _halves(block, nq)
    P, G, lanes, q_rows, k_rows, stats = _specs(
        heads, q.shape[2], bq, bk, kv_major=True, group=group,
        band=band and band[1], nq=nq, bd=bd and bd[1])
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, nk=nk,
                          static_skip=static_skip, sub_tile=sub_tile, G=G,
                          band=band, group=group, bd=bd),
        grid=(N, P, nk, max(G, group),
              _inner_steps(band, bd, nq, kv_major=True)),
        in_specs=[_scalar_spec(), _scalar_spec(),
                  q_rows(), k_rows(), k_rows(),       # q, k, v
                  q_rows(), stats(), stats()],        # do, lse, delta
        out_specs=[k_rows(), k_rows()],
        out_shape=[
            _out_struct((N, Tk, W), k.dtype, q, k, v, do, lse, delta,
                        q_off, k_off),
            _out_struct((N, Tk, W), v.dtype, q, k, v, do, lse, delta,
                        q_off, k_off),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, lanes), jnp.float32),
            pltpu.VMEM((bk, lanes), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(dimension_semantics=_SEMANTICS),
        interpret=interpret,
        name="hvd_flash_bwd_dkv" + _WIN * bool(band) + _BD * bool(bd),
    )(_as_scalar(q_off), _as_scalar(k_off), q, k, v, do, lse, delta)


def _flash_bwd(q, k, v, o, lse, do, heads, scale, causal, bq, bk,
               window=None, group=1, block=None):
    delta = _prep_residuals(o, do, heads)
    dq = _flash_bwd_dq(q, k, v, do, lse, delta, scale, causal, bq, bk,
                       heads=heads, window=window, group=group, block=block)
    dk, dv = _flash_bwd_dkv(q, k, v, do, lse, delta, scale, causal, bq, bk,
                            heads=heads, window=window, group=group,
                            block=block)
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


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9, 10))
def _flash(q, k, v, heads, scale, causal, bq, bk, window, group, block):
    o, _ = _flash_fwd(q, k, v, scale, causal, bq, bk, heads=heads,
                      window=window, group=group, block=block)
    return o


# Under ``jax.checkpoint`` with ``save_only_these_names(OUT_NAME)`` the
# forward kernel's output and log-sum-exp rows are kept, and the recomputed
# forward of a rematerialised block runs no flash kernel.
OUT_NAME = "hvd_flash_out"


def _flash_vjp_fwd(q, k, v, heads, scale, causal, bq, bk, window, group,
                   block):
    o, lse = (checkpoint_name(x, OUT_NAME) for x in _flash_fwd(
        q, k, v, scale, causal, bq, bk, heads=heads, window=window,
        group=group, block=block))
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(heads, scale, causal, bq, bk, window, group, block, res,
                   g):
    q, k, v, o, lse = res
    return _flash_bwd(q, k, v, o, lse, g, heads, scale, causal, bq, bk,
                      window, group, block)


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
            return o_i.astype(jnp.float32), lse_i[:, 0, :]  # [BH,T,D],[BH,T]

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

    lse = lse[:, None, :]                                # as the kernels'
    delta = _prep_residuals(o, do)

    def contrib(dq, k_blk, v_blk, dk_blk, dv_blk, i):
        src = (my - i) % n

        def compute(k_blk, v_blk):
            q_off, k_off = my * T_local, src * T_local
            dq_i = _flash_bwd_dq(q, k_blk, v_blk, do, lse, delta, scale,
                                 causal, bq, bk, q_off=q_off, k_off=k_off,
                                 static_skip=False)
            dk_i, dv_i = _flash_bwd_dkv(q, k_blk, v_blk, do, lse, delta,
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


def block_diffusion_mask(L: int, block: int):
    """``[2L, 2L]`` bool: may row r (a query) see row c (a key)? Rows are
    ``[noised ; clean]``, position p of either half in block ``p // block``.
    A clean query sees the clean keys of its own and earlier blocks; a
    noised query the clean keys of EARLIER blocks and the noised keys of
    its own block; nothing else (BD3-LM's training mask, Arriola et al.
    2025). ``L^2 + L * block`` pairs; every row sees itself."""
    blk = jnp.arange(L) // block
    clean_clean = blk[:, None] >= blk[None, :]
    noised_clean = blk[:, None] > blk[None, :]
    noised_noised = blk[:, None] == blk[None, :]
    return jnp.block([[noised_noised, noised_clean],
                      [jnp.zeros((L, L), bool), clean_clean]])


def _dense_fallback(q, k, v, causal, window, scale, block=None):
    """The dense path for a sequence no block divides, with grouped KV
    heads and a window or the block-diffusion mask where the call has
    them."""
    from ..parallel.sequence import dense_attention

    group = q.shape[2] // k.shape[2]
    if group > 1:
        k, v = (jnp.repeat(x, group, axis=2) for x in (k, v))
    if window is None and block is None:
        return dense_attention(q, k, v, causal=causal, scale=scale)
    T, D = q.shape[1], q.shape[3]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * (
        D ** -0.5 if scale is None else scale)
    if block is not None:
        seen = block_diffusion_mask(T // 2, block)
    else:
        d = jnp.arange(T)[:, None] - jnp.arange(T)[None, :]
        seen = (d >= 0) & (d < window)
    s = jnp.where(seen, s, _NEG_INF)
    return jnp.einsum("bhqk,bkhd->bqhd",
                      jax.nn.softmax(s, axis=-1).astype(v.dtype), v)


def _in_place(H: int, Hkv: int, D: int) -> bool:
    """``_reads_in_place``, and with grouped KV heads a head a block
    (D = 128): the heads of one lane block would read different KV heads
    otherwise, and such a call is packed."""
    return _reads_in_place(H, D) and (H == Hkv or D == _LANES)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None,
                    block_diffusion: Optional[int] = None,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Exact attention with the flash schedule. Layout: q [B, T, H, D],
    k and v [B, T, Hkv, D] with ``H % Hkv == 0``: query head h reads KV
    head ``h // (H // Hkv)``, and k, v are never repeated in HBM (their
    gradients come back [B, T, Hkv, D], summed over each group inside the
    dk/dv kernel). ``window``: query t sees keys ``t - window + 1 .. t``
    (its own token is one of the ``window``); needs ``causal``. A window
    the sequence fits in is no window. ``window=None`` and ``H == Hkv``
    compile the kernels they always did (docs/flash_window.md).

    ``block_diffusion=B`` (a power of two): the ``T = 2 L`` rows are a
    noised copy of a sequence and then its clean copy, and the mask is the
    block-diffusion objective's (:func:`block_diffusion_mask`,
    docs/block_diffusion.md): a clean query sees the clean keys to the end
    of its own block of ``B`` positions, a noised query the clean keys of
    earlier blocks and the noised keys of its own. Blocks are square and
    divide ``L``; only the cells the mask touches are fetched or computed
    (``L^2 + L B`` pairs a head of the square's ``4 L^2``), in kernels
    named ``hvd_flash_*_bd`` under scope ``hvd.flash_block_diffusion``.
    ``block_diffusion=None`` compiles what the file compiled before it
    knew the mask, kernel for kernel.

    Differentiable (custom VJP with Pallas backward kernels). Block sizes
    shrink to a divisor of the sequence when needed (a single whole-sequence
    block is always legal — Mosaic accepts block dims equal to the array
    dim); only a long sequence with no 128-aligned divisor falls back to
    the dense path — numerics are identical either way. A windowed call's
    blocks are square (the smaller of the two).

    ``block_q``/``block_k`` default to the kernel autotuner's choice for
    this (shape, chip) — swept once, cached on disk
    (ops/kernel_autotune.py) — unless the ``HOROVOD_FLASH_BLOCK_Q/K``
    knobs pin them or the caller passes explicit values.
    """
    B, Tq, H, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    if causal and Tq != Tk:
        raise ValueError(
            f"causal flash attention needs Tq == Tk, got {Tq} != {Tk}")
    if H % Hkv or v.shape[2] != Hkv:
        raise ValueError(f"{H} query heads do not share {Hkv} (k) / "
                         f"{v.shape[2]} (v) KV heads evenly")
    group = H // Hkv
    if window is not None:
        if not causal or window < 1:
            raise ValueError("a window is causal and holds the query's own "
                             f"token, got causal={causal} window={window}")
        window = None if window >= Tk else int(window)
    bd = block_diffusion
    if bd is not None:
        bd = int(bd)
        if (not causal or window is not None or Tq != Tk or bd < 1
                or bd & (bd - 1) or Tq % (2 * bd)):
            raise ValueError(
                "a block-diffusion call takes [noised ; clean] rows of one "
                "length, a whole number of blocks of a power of two each, "
                f"and no window: got {Tq} queries, {Tk} keys, block {bd}, "
                f"window {window}, causal={causal}")
    if block_q is None and block_k is None:
        block_q, block_k = _resolve_blocks(B, Tq, Tk, H, D, q.dtype,
                                           causal, window, Hkv, bd)
    else:
        block_q = _DEF_BLOCK_Q if block_q is None else block_q
        block_k = _DEF_BLOCK_K if block_k is None else block_k
    if block_q < 128 or block_k < 128:
        raise ValueError(
            f"block_q/block_k must be >= 128 (MXU/lane tile), got "
            f"{block_q}/{block_k}")
    if window is not None or bd is not None:
        block_q = block_k = min(block_q, block_k)
    if bd is None:
        bq, bk = _pick_block(Tq, block_q), _pick_block(Tk, block_k)
    else:   # the blocks divide a HALF, and a sub-tile holds whole blocks
        bq = bk = _pick_block(Tq // 2, block_q)
        if bq is not None and (_sub_tile(bq, _SUB_TILE[0]) % bd or (
                bq % _LANES and not _interpret())):
            bq = None
    if bq is None or bk is None:
        return _dense_fallback(q, k, v, causal, window, scale, bd)
    scale = float(scale) if scale is not None else D ** -0.5

    from ..monitor.registry import counter

    # By shape alone: the projections' own [B, T, H * D] where whole heads
    # fill whole lane blocks (a reshape is no copy), else packed by head.
    in_place = _in_place(H, Hkv, D)
    counter("flash.layout",
            path="in_place" if in_place else "packed").inc()
    if group > 1:
        counter("flash.kv_group").inc(group)
    # Outside the custom_vjp call, so that the backward kernels and the
    # packed path's [B, T, H, D] <-> [BH, T, D] traffic carry the scope too.
    with jax.named_scope("hvd.flash_attention"), _kind_scope(window, bd):
        if in_place:
            qp, kp, vp = _harmonize_vma(*(
                x.reshape(B, x.shape[1], x.shape[2] * D) for x in (q, k, v)))
            o = _flash(qp, kp, vp, H, scale, causal, bq, bk, window, group,
                       bd)
            return o.reshape(B, Tq, H, D)
        qp, kp, vp = _harmonize_vma(_pack(q), _pack(k), _pack(v))
        return _unpack(_flash(qp, kp, vp, 1, scale, causal, bq, bk, window,
                              group, bd), B, H)


def _kind_scope(window, block=None):
    """``hvd.flash_window`` inside ``hvd.flash_attention`` around a
    windowed call (forward and backward), ``hvd.flash_block_diffusion``
    around a block-diffusion call, nothing around any other."""
    if block is not None:
        return jax.named_scope("hvd.flash_block_diffusion")
    return (contextlib.nullcontext() if window is None
            else jax.named_scope("hvd.flash_window"))
