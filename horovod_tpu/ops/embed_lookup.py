"""The token lookup with a backward of its own (docs/kernels.md "The
lookup").

``embed_lookup(table, tokens, dtype)`` is ``table.astype(dtype)[tokens]``:
the ``N`` float32 rows are gathered and THEN rounded (a cast commutes with a
gather), so no pass over the whole ``[V, C]`` table is made.

Its backward is the float32 ``[V, C]`` cotangent of the table from the ``N``
rows of ``dx``. The compiler's own transpose, a scatter-add of the rows into
a table of zeros, walks the rows one by one and takes 0.1 to 1.2 us a row
by their width (PERF.md section 5, PR 46). Here the rows are brought into
the table's order first — the ids sorted with their positions, ``dx``
gathered in that order, both by the compiler — and one kernel,
``hvd_embed_rows_add``, then writes the table ONCE, block of rows by block
of rows: a block's rows are a run of the sorted ``dx``, and a 0 / 1 matrix
``[table row, dx row]`` times that run on the MXU is the block, repeated
ids summed in float32 there, rows no id names zero. What the kernel walks is
a list of (table block, chunk of ``dx``) pairs made from the sorted ids and
scalar-prefetched: every block at least once, a chunk once for every block
whose ids it holds, ``cdiv(V, BLOCK_ROWS) + cdiv(N, CHUNK_ROWS)`` entries
at the most whatever the ids are.

The form follows what the call can see and nothing else: a width of whole
128-lane tiles takes the kernel (in the Pallas interpreter off the TPU, but
not inside ``shard_map`` there: the interpreter evaluates an index map over
scalar-prefetched values outside the map's typing, as
``ops/selective_scan.py`` found for its kernels), any other the compiler's
scatter-add in float32. Trace-time counter:
``embed.grad_rows{form=sorted_rows_kernel|scatter}``, ``N`` a traced
backward.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _flash
from .collective_ops import _vma
from .flash_attention import _harmonize_vma, _out_struct

_LANES, _SUBLANES = 128, 8
#: Rows of the table a grid step writes and rows of the sorted ``dx`` it
#: reads: timed on the chip beside other choices by
#: ``scripts/router_piece_times.py --rows embed --blocks``.
BLOCK_ROWS, CHUNK_ROWS = 128, 256
_FIRST, _ADD, _SKIP = 0, 1, 2


def _interpret() -> bool:
    """The flash kernels' answer, asked each time (a compile for a described
    chip sets theirs, and the step's kernels follow together)."""
    return _flash._interpret()


def grad_form(dx) -> str:
    """The backward the rows ``dx [N, C]`` take."""
    kernel = dx.shape[1] % _LANES == 0 and not (_interpret() and _vma(dx))
    return "sorted_rows_kernel" if kernel else "scatter"


def _rows_add_kernel(block_ref, chunk_ref, kind_ref, ids_ref, dx_ref,
                     out_ref, *, precision):
    """One (table block, chunk) pair: the chunk's rows whose ids the block
    holds, added at their rows."""
    del chunk_ref
    i = pl.program_id(0)
    rows = out_ref.shape[0]

    def placed():
        local = ids_ref[...] - block_ref[i] * rows                # [1, K]
        hit = lax.broadcasted_iota(
            jnp.int32, (rows, local.shape[1]), 0) == local
        return jnp.dot(hit.astype(dx_ref.dtype), dx_ref[...],
                       precision=precision,
                       preferred_element_type=jnp.float32)

    @pl.when(kind_ref[i] == _FIRST)
    def _():
        out_ref[...] = placed()

    @pl.when(kind_ref[i] == _ADD)
    def _():
        out_ref[...] += placed()


def _walk_plan(sorted_ids, blocks: int, block_rows: int, chunks: int,
               chunk_rows: int):
    """(table block, chunk, kind) of every grid step, ``blocks + chunks`` of
    them: block ``v`` takes the chunks from the one that holds its first id
    to the one that holds its last (one chunk, which adds nothing, when it
    has no id), the steps past the last pair skip."""
    i32 = jnp.int32
    starts = jnp.searchsorted(
        sorted_ids, jnp.arange(blocks + 1, dtype=i32) * block_rows,
        side="left", method="compare_all").astype(i32)
    first = jnp.minimum(starts[:-1] // chunk_rows, chunks - 1)
    last = jnp.where(starts[1:] > starts[:-1],
                     (starts[1:] - 1) // chunk_rows, first)
    ends = jnp.cumsum(last - first + 1)
    begins = ends - (last - first + 1)
    step = jnp.arange(blocks + chunks, dtype=i32)
    block = jnp.minimum(jnp.searchsorted(
        ends, step, side="right", method="compare_all").astype(i32),
        blocks - 1)
    chunk = jnp.minimum(first[block] + step - begins[block], chunks - 1)
    kind = jnp.where(step >= ends[-1], _SKIP,
                     jnp.where(step == begins[block], _FIRST, _ADD))
    return block, chunk, kind.astype(i32)


def embed_grad(ids, dx, vocab: int, *, block_rows=None, chunk_rows=None):
    """ids ``[N]`` int, dx ``[N, C]`` -> float32 ``[vocab, C]``: the rows of
    ``dx`` added at their ids, repeated ids summed in float32; a negative id
    counts from the end, an id out of range adds nothing (as the transpose
    of ``table[ids]`` has it)."""
    from ..monitor.registry import counter

    N, C = dx.shape
    form = grad_form(dx)
    counter("embed.grad_rows", form=form).inc(N)
    ids = ids.astype(jnp.int32)
    ids = jnp.where(ids < 0, ids + vocab, ids)
    if form == "scatter":
        return jnp.zeros((vocab, C), jnp.float32).at[ids].add(
            dx.astype(jnp.float32), mode="drop")
    rows = min(block_rows or BLOCK_ROWS, -(-vocab // _SUBLANES) * _SUBLANES)
    chunk = chunk_rows or CHUNK_ROWS
    blocks, chunks = -(-vocab // rows), -(-N // chunk)
    # Past every block: such an id sorts last and no block's rows hold it.
    nowhere = blocks * rows
    ids = jnp.where((ids < 0) | (ids >= vocab), nowhere, ids)
    sorted_ids, order = lax.sort((ids, jnp.arange(N, dtype=jnp.int32)),
                                 num_keys=1)
    pad = chunks * chunk - N
    sorted_ids = jnp.pad(sorted_ids, (0, pad), constant_values=nowhere)
    # The padding reads row 0 again: finite wherever dx is, and named by no id.
    dx_sorted = dx[jnp.pad(order, (0, pad))]
    block, chunk_of, kind = _walk_plan(sorted_ids, blocks, rows, chunks,
                                       chunk)
    precision = (lax.Precision.HIGHEST if dx.dtype == jnp.float32
                 else lax.Precision.DEFAULT)
    operands = _harmonize_vma(block, chunk_of, kind,
                              sorted_ids.reshape(chunks, 1, chunk), dx_sorted)
    return pl.pallas_call(
        functools.partial(_rows_add_kernel, precision=precision),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(blocks + chunks,),
            in_specs=[
                pl.BlockSpec((None, 1, chunk),
                             lambda i, b, c, k: (c[i], 0, 0)),
                pl.BlockSpec((chunk, C), lambda i, b, c, k: (c[i], 0)),
            ],
            out_specs=pl.BlockSpec((rows, C), lambda i, b, c, k: (b[i], 0))),
        out_shape=_out_struct((vocab, C), jnp.float32, *operands),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret(),
        name="hvd_embed_rows_add")(*operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _lookup(table, tokens, dtype, vocab):
    del vocab
    return table[tokens].astype(dtype)


def _lookup_fwd(table, tokens, dtype, vocab):
    return _lookup(table, tokens, dtype, vocab), tokens


def _lookup_bwd(dtype, vocab, tokens, dx):
    del dtype
    return embed_grad(tokens.reshape(-1), dx.reshape(-1, dx.shape[-1]),
                      vocab), None


_lookup.defvjp(_lookup_fwd, _lookup_bwd)


def embed_lookup(table, tokens, dtype):
    """table ``[V, C]`` float32, tokens ``[...]`` int -> ``[..., C]`` of
    ``dtype``: bit for bit ``table.astype(dtype)[tokens]``, with the rows
    gathered before they are rounded. Differentiable in ``table``: its
    cotangent is :func:`embed_grad`'s float32 ``[V, C]``, by the kernel
    where ``C`` is whole 128-lane tiles and by the compiler's scatter-add
    elsewhere."""
    # Outside the custom VJP, as ops/flash_attention.py has it: a replicated
    # table's cotangent is summed by the cast's transpose.
    table, tokens = _harmonize_vma(table, tokens)
    return _lookup(table, tokens, jnp.dtype(dtype), table.shape[0])
