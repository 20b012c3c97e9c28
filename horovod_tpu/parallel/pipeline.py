"""Pipeline parallelism: GPipe-style microbatch schedule over the mesh.

The reference framework is data-parallel only (SURVEY §2.7); this is the
TPU-native pipeline layer, built SPMD-style the way XLA wants it: every
rank runs the SAME program each step — its own stage on whatever
activation it holds — and activations hop to the next stage over a
non-cyclic ``lax.ppermute`` (neighbor ICI hop). With M microbatches and
n stages the schedule is the classic M + n - 1 steps; ranks in the
fill/drain bubble compute garbage that never reaches an output (masked
writes), the standard price of an SPMD pipeline.

* :func:`gpipe` — generic: ``stage_fn(stage_params, x)`` applied to a
  [M, ...] microbatch array, returns the [M, ...] outputs REPLICATED on
  every rank (the last stage's results are broadcast by a masked psum).
  Fully differentiable: the backward pass replays the schedule with
  transposed ppermutes — exactly the GPipe backward.
* :func:`pp_split_blocks` — slices a dense GPT checkpoint into stacked
  per-stage block parameters (+ the replicated embedding/head tree).
* :func:`pipelined_gpt_apply` — the GPT assembly: embedding and LM head
  are computed replicated on every rank, the transformer stack runs
  through the pipeline (inference / logits consumers).
* :func:`pipelined_gpt_loss` — the TRAINING assembly: the LM head (the
  dominant [B, T, vocab] einsum at real scale) is VOCAB-SHARDED over the
  pipeline ranks with a Megatron-style sharded cross-entropy, so the
  per-rank head cost is O(1/n) in compute and logits memory.

Exact vs the dense model (tests/test_pipeline_parallel.py).
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .sequence import _axis_size


def _pvary_tree(tree, axes):
    """pvary_missing over every leaf (single home for the tree-mapped
    form of collective_ops' idiom)."""
    from ..ops.collective_ops import pvary_missing

    if not axes:
        return tree
    return jax.tree.map(lambda a: pvary_missing(a, tuple(axes)), tree)


def _send_plan_for_axis(axis, *, quantized: bool = False,
                        block: Optional[int] = None,
                        error_feedback: bool = False):
    """The send plan of a pipeline hop over ``axis`` (docs/pipeline.md):
    the leg's level is the slowest link class the axis tuple spans —
    pod > dcn > ici — because a hop over a multi-level axis crosses its
    widest stride. Quantization is forced off on an ICI hop (the
    EQuARX placement rule the IR validates)."""
    from ..common import basics
    from ..common.basics import CROSS_AXIS, PP_AXIS, POD_AXIS
    from ..plan import planner as _planner

    axes = {axis} if isinstance(axis, str) else set(axis)
    if POD_AXIS in axes:
        level = _planner.POD
    elif CROSS_AXIS in axes:
        level = _planner.DCN
    elif PP_AXIS in axes and basics.is_initialized():
        # The dedicated pp axis leads the mesh: one hop jumps a whole
        # data mesh, i.e. the slowest link class the DATA mesh spans.
        level = _planner.pp_send_level(basics.data_mesh_shape())
    else:
        level = _planner.ICI
    q = quantized and level != _planner.ICI
    return _planner.send_plan(level, quantized=q, block=block,
                              error_feedback=error_feedback and q)


def _carry_axes(axis, x_mbs, stage_params):
    """Varying-axes type for pipeline scan carries: the pipeline axis
    itself plus whatever the inputs/stage params already vary over (e.g.
    a data-parallel batch axis). Single home for both schedules' inits."""
    from ..ops.collective_ops import _vma

    ring = {axis} if isinstance(axis, str) else set(axis)
    return tuple(sorted(
        ring | _vma(x_mbs)
        | frozenset().union(*[_vma(l) for l in
                              jax.tree.leaves(stage_params)])))


def gpipe(stage_fn, stage_params, x_mbs, *, axis):
    """Run microbatches [M, ...] through n pipeline stages over ``axis``.

    ``stage_fn(stage_params, x)`` maps one microbatch through THIS rank's
    stage (same shapes in and out). Returns [M, ...] outputs of the full
    pipeline, identical on every rank.
    """
    n = _axis_size(axis)
    if n == 1:
        return jax.vmap(lambda x: stage_fn(stage_params, x))(x_mbs)
    r = lax.axis_index(axis)
    M = x_mbs.shape[0]
    steps = M + n - 1
    shift = [(i, i + 1) for i in range(n - 1)]   # non-cyclic: 0→1→...→n-1
    # The relay hop is a wire-plan send leg (docs/pipeline.md): same
    # ppermute as always, but lowered by plan/compiler.py so the legacy
    # GPipe wire finally shows up in WireStats/comm.bytes{hop} (the scan
    # body traces once — ``repeats=steps`` charges the true per-pass
    # bytes; the autodiff-transposed backward hop is not re-accounted).
    from ..plan import compiler as _compiler

    splan = _send_plan_for_axis(axis)

    def body(carry, t):
        state, outputs = carry
        # Stage 0 injects microbatch t; later stages consume the incoming
        # activation from their left neighbor.
        mb_in = x_mbs[jnp.clip(t, 0, M - 1)]
        x = jnp.where(r == 0, mb_in, state)
        y = stage_fn(stage_params, x)
        # The last stage finishes microbatch t - (n - 1); write it (only
        # there, only when valid — other ranks contribute zeros so a
        # final psum broadcasts the real values).
        out_idx = t - (n - 1)
        valid = jnp.logical_and(r == n - 1, out_idx >= 0)
        write = jnp.where(valid, y, 0).astype(outputs.dtype)
        idx = jnp.clip(out_idx, 0, M - 1)
        outputs = outputs.at[idx].set(
            jnp.where(valid, write, outputs[idx]))
        # Hop to the next stage (rank n-1's output leaves the ring; rank
        # 0 receives zeros it never reads).
        state, _ = _compiler.lower_send(splan, y, axis=axis, perm=shift,
                                        repeats=steps)
        return (state, outputs), None

    # Scan carries become varying over the pipeline axis (per-rank stages
    # and the masked writes); the fresh zero inits must match. pcast only
    # the axes a value does not already vary over (zeros_like inherits
    # e.g. a data-parallel batch axis from x_mbs).
    from ..ops.collective_ops import pvary_missing

    axes_t = _carry_axes(axis, x_mbs, stage_params)
    state0 = pvary_missing(jnp.zeros_like(x_mbs[0]), axes_t)
    outputs0 = pvary_missing(jnp.zeros(x_mbs.shape, x_mbs.dtype), axes_t)
    (_, outputs), _ = lax.scan(body, (state0, outputs0),
                               jnp.arange(steps))
    # Only the last stage holds real outputs; the masked psum replicates
    # them everywhere (all other ranks contributed zeros).
    return lax.psum(outputs, axis)


def pp_split_blocks(params, n: int):
    """Dense GPT params → (stages, rest).

    ``stages``: for each transformer-block leaf ``h{i}/...`` a stacked
    array [n, L/n, ...] — stage r holds blocks [r·L/n, (r+1)·L/n); pass
    through shard_map with ``in_specs=P(pp_axis)`` and squeeze the
    leading dim. ``rest``: embedding/final-LN (replicated, ``P()``).
    """
    blocks = sorted((k for k in params if k.startswith("h")),
                    key=lambda k: int(k[1:]))
    L = len(blocks)
    if L % n:
        raise ValueError(f"{L} blocks not divisible by {n} stages")
    per = L // n

    def stack_stage_leaves(*leaves):
        # leaves: the same param across all L blocks, in order.
        return jnp.stack(
            [jnp.stack(leaves[s * per:(s + 1) * per]) for s in range(n)])

    stages = jax.tree.map(stack_stage_leaves,
                          *[params[b] for b in blocks])
    rest = {k: v for k, v in params.items() if not k.startswith("h")}
    return stages, rest


def _validate_pipeline_cfg(cfg, B, T, num_microbatches, axis):
    if B % num_microbatches:
        raise ValueError(
            f"batch {B} not divisible by {num_microbatches} microbatches")
    if T > cfg.max_seq_len:
        # Same guard as GPT.__call__: jit gathers clamp out-of-bounds
        # indices, which would silently reuse the last positional
        # embedding.
        raise ValueError(f"sequence length {T} exceeds "
                         f"max_seq_len={cfg.max_seq_len}")
    if cfg.moe_experts:
        raise ValueError(
            "the pipelined GPT assembly does not support MoE blocks: the "
            "router's sown aux loss cannot be returned through the "
            "pipeline stages (apply the MoE model under DP/EP instead)")
    if getattr(cfg, "tp_axis", None) and _axis_size(cfg.tp_axis) > 1:
        # With an ACTIVE tp axis (size > 1 — models/gpt.py's _tp_size
        # no-ops a size-1 axis), _Attention/_Mlp psum partial products
        # over it — but pp_split_blocks hands every pipeline rank FULL
        # (un-tp-sliced) stage weights, so those psums would sum complete
        # outputs tp-fold and silently produce garbage.
        raise ValueError(
            "the pipelined GPT assembly does not support tp_axis: stage "
            "parameters are not tensor-parallel-sliced (compose TP with "
            "DP/SP instead, or drop tp_axis for the pipeline path)")
    if cfg.attention in ("ring", "flash_ring", "ulysses"):
        seq_axes = ({cfg.seq_axis} if isinstance(cfg.seq_axis, str)
                    else set(cfg.seq_axis))
        pp_axes = {axis} if isinstance(axis, str) else set(axis)
        if seq_axes & pp_axes:
            # Mirrors the tp/seq overlap guard in models/gpt.py _Attention:
            # a K/V rotation over the pipeline axis would exchange tensors
            # between ranks holding DIFFERENT pipeline stages and silently
            # produce garbage.
            raise ValueError(
                f"attention={cfg.attention!r} is sequence-parallel over "
                f"seq_axis={cfg.seq_axis!r}, which overlaps the pipeline "
                f"axis {axis!r}; use disjoint mesh axes")


def _embed(cfg, ep, tokens):
    """Token + positional embedding from an {wte, wpe} tree (single home
    for the pipeline paths; differentiable w.r.t. ``ep``)."""
    T = tokens.shape[1]
    return (ep["wte"][tokens]
            + ep["wpe"][jnp.arange(T)][None]).astype(cfg.dtype)


def _make_stage_fn(cfg):
    """This rank's stage: its stacked [L/n, ...] blocks folded over the
    activation (single home for both schedules)."""
    from ..models.gpt import _Block

    block = _Block(cfg)

    def stage_fn(stacked, h):
        def one(h, bp):
            return block.apply({"params": bp}, h), None

        h, _ = lax.scan(one, h, stacked)
        return h

    return stage_fn


def _pipeline_hidden(cfg, stage_params, rest, tokens, *, axis,
                     num_microbatches):
    """Embedding + pipelined transformer stack → final hidden [B, T, C]
    (pre-ln_f), replicated over ``axis``."""
    B, T = tokens.shape
    _validate_pipeline_cfg(cfg, B, T, num_microbatches, axis)
    x = _embed(cfg, rest, tokens)
    x_mbs = x.reshape(num_microbatches, B // num_microbatches, T, -1)
    h = gpipe(_make_stage_fn(cfg), stage_params, x_mbs, axis=axis)
    return h.reshape(B, T, -1)


def _head_logits(cfg, rest, h):
    import flax.linen as nn

    ln = nn.LayerNorm(dtype=cfg.dtype)
    hn = ln.apply({"params": rest["ln_f"]}, h)
    return jnp.einsum("btc,vc->btv", hn, rest["wte"].astype(cfg.dtype),
                      preferred_element_type=jnp.float32)


def pipelined_gpt_apply(cfg, stage_params, rest, tokens, *, axis,
                        num_microbatches: int):
    """Forward a GPT through the pipeline. Inside shard_map: ``tokens``
    [B, T] replicated over ``axis``, ``stage_params`` this rank's stacked
    [L/n, ...] block tree, ``rest`` the replicated embedding/head tree.
    Returns logits [B, T, vocab] (replicated over ``axis``).

    Every rank computes the full [B, T, vocab] head einsum on the
    replicated hidden states; for training prefer
    :func:`pipelined_gpt_loss`, which vocab-shards the head across the
    pipeline ranks (per-rank head compute and logits memory O(1/n); the
    [B, T, C] hidden broadcast remains)."""
    h = _pipeline_hidden(cfg, stage_params, rest, tokens, axis=axis,
                         num_microbatches=num_microbatches)
    return _head_logits(cfg, rest, h)


def pipelined_gpt_loss(cfg, stage_params, rest, tokens, targets, *, axis,
                       num_microbatches: int):
    """Mean LM cross-entropy of the pipelined GPT with a VOCAB-PARALLEL
    head: the [B, T, V] einsum — the dominant term of a GPT step at real
    scale — is sharded over the pipeline ranks instead of replicated.

    :func:`pipelined_gpt_apply` makes every rank compute the full head on
    the replicated hidden states, so pipelining saved nothing on the
    dominant cost. Here each rank computes logits for its own V/n vocab
    columns of the (replicated) hidden states and the softmax
    cross-entropy is assembled with the Megatron-style sharded-vocab
    reduction — a ``pmax`` for the global row max, one ``psum`` for the
    global sum-of-exps, one ``psum`` for the label logit (exactly one
    rank holds each label's column). Per-rank head compute AND logits
    memory are O(1/n) of the replicated form, every rank does useful
    work (no idle bubble ranks), and there is no per-device control flow
    for XLA to choke on. Fully differentiable (slice/psum/gpipe all
    transpose; the row max rides ``stop_gradient``, the standard exact
    logsumexp trick). Exact vs the dense model's loss
    (tests/test_pipeline_parallel.py)."""
    import optax

    n = _axis_size(axis)
    h = _pipeline_hidden(cfg, stage_params, rest, tokens, axis=axis,
                         num_microbatches=num_microbatches)
    if n == 1:
        logits = _head_logits(cfg, rest, h)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    import flax.linen as nn

    ln = nn.LayerNorm(dtype=cfg.dtype)
    hn = ln.apply({"params": rest["ln_f"]}, h)
    wte = rest["wte"].astype(cfg.dtype)
    V, C = wte.shape
    Vp = -(-V // n)  # ceil: per-rank vocab shard
    # Pad to n*Vp rows so the per-rank dynamic_slice is never clamped
    # (clamping would silently desync vpos from the actual rows).
    wpad = jnp.pad(wte, ((0, n * Vp - V), (0, 0)))
    ax = axis if isinstance(axis, str) else tuple(axis)
    r = lax.axis_index(ax)
    w_shard = lax.dynamic_slice(wpad, (r * Vp, jnp.int32(0)), (Vp, C))
    logits_loc = jnp.einsum("btc,vc->btv", hn, w_shard,
                            preferred_element_type=jnp.float32)
    vpos = r * Vp + jax.lax.broadcasted_iota(jnp.int32, (Vp,), 0)
    valid = vpos < V
    logits_loc = jnp.where(valid[None, None, :], logits_loc, -jnp.inf)

    # Label logit: exactly one rank's shard holds each target column.
    hit = vpos[None, None, :] == targets[..., None]
    tgt_logit = lax.psum(
        jnp.sum(jnp.where(hit, logits_loc, 0.0), axis=-1), ax)
    # Global logsumexp over the sharded vocab. stop_gradient goes INSIDE
    # pmax (pmax has no JVP rule, but a symbolically-zero tangent never
    # reaches it), and pmax — not all_gather+max — re-establishes the
    # replicated (invariant) typing the P() out-spec needs. Any m gives
    # the same lse mathematically; it only sets fp scaling.
    m = lax.pmax(lax.stop_gradient(jnp.max(logits_loc, axis=-1)), ax)
    sumexp = lax.psum(
        jnp.sum(jnp.exp(logits_loc - m[..., None]), axis=-1), ax)
    lse = m + jnp.log(sumexp)
    return jnp.mean(lse - tgt_logit)


def gpipe_1f1b(stage_fn, loss_fn, stage_params, head_params, x_mbs,
               tgt_mbs, *, axis):
    """1F1B pipeline schedule: loss + gradients in one fused pass with
    O(pipeline_depth) activation memory.

    :func:`gpipe` differentiates its forward scan with autodiff, so the
    backward retains residuals for ALL M microbatches per rank — O(M)
    activation memory, GPipe's classic cost. This schedule hand-interleaves
    one-forward-one-backward: stage r runs F(m) at tick m+r and B(m) at
    tick m+2n-1-r, so at most 2n-1-2r microbatches are in flight per rank
    and the stash is a static ``[2n-1, ...]`` ring buffer — O(n), however
    large M grows. Backward uses input-stash rematerialization (the stage
    forward is recomputed at B time for its VJP — one extra forward per
    microbatch, the standard remat trade).

    ``stage_fn(stage_params, x)`` is this rank's stage.
    ``loss_fn(head_params, y, tgt)`` maps the LAST stage's output to a
    scalar per-microbatch loss (every rank evaluates it SPMD-style; only
    the last rank's result/cotangents are un-masked). Returns
    ``(loss, d_stage_params, d_head_params, d_x_mbs)`` where ``loss`` is
    the mean over microbatches (replicated over the PIPELINE axis),
    ``d_stage_params`` is this rank's stage-parameter gradient
    (device-varying, like the stage parameters themselves),
    ``d_head_params`` is replicated over the pipeline axis, and
    ``d_x_mbs`` is the gradient w.r.t. the pipeline input (for the
    caller's embedding backward).

    Composing with data parallelism: when the inputs are sharded over a
    DP axis, every returned gradient is PER-DATA-SHARD — average over
    the DP axes yourself (``hvd.allreduce_pytree(op=Average,
    axes=...)``), exactly as with ``jax.grad`` under shard_map. All
    parameter trees enter their vjps as varying copies internally so the
    implicit pvary transpose cannot pre-sum shards
    (tests/test_pipeline_parallel.py::test_dp_1f1b_2d).
    """
    n = _axis_size(axis)
    M = x_mbs.shape[0]
    if n == 1:
        # Same per-data-shard gradient contract as the scheduled path:
        # when the inputs vary over a DP axis, params enter the grad as
        # varying copies or the implicit pvary transpose psums shard
        # gradients together. Everything is harmonized to the UNION of
        # varying axes (a size-1 pipeline in_spec still marks params
        # varying over it), and the trailing ring psums — numerically
        # identity over a size-1 axis — restore the n>1 output typing
        # (gh/gx ring-invariant, gs ring-varying). All of this is a
        # no-op outside shard_map, where _vma is empty.
        from ..ops.collective_ops import _vma

        ring = ({axis} if isinstance(axis, str) else set(axis))
        union = set()
        for leaf in (jax.tree.leaves(stage_params)
                     + jax.tree.leaves(head_params)
                     + [x_mbs, tgt_mbs]):
            union |= _vma(leaf)
        union_t = tuple(sorted(union))

        sp_in, hp_in, x_in, tgt_in = (
            _pvary_tree(stage_params, union_t),
            _pvary_tree(head_params, union_t),
            _pvary_tree(x_mbs, union_t), _pvary_tree(tgt_mbs, union_t))

        def total(sp, hp, x):
            ys = jax.vmap(lambda xm: stage_fn(sp, xm))(x)
            losses = jax.vmap(lambda ym, tm: loss_fn(hp, ym, tm))(
                ys, tgt_in)
            return losses.mean()

        loss, (gs, gh, gx) = jax.value_and_grad(total, argnums=(0, 1, 2))(
            sp_in, hp_in, x_in)
        ring_in_union = tuple(a for a in sorted(ring) if a in union)
        if ring_in_union:
            # identity over the size-1 ring axis; drops it from the vma
            gh = jax.tree.map(lambda a: lax.psum(a, ring_in_union), gh)
            gx = lax.psum(gx, ring_in_union)
            loss = lax.psum(loss, ring_in_union)
        return loss, gs, gh, gx

    ax = axis if isinstance(axis, str) else tuple(axis)
    r = lax.axis_index(ax)
    S = 2 * n - 1                       # max microbatches in flight
    T_ticks = M + 2 * n - 1
    up = [(i, i + 1) for i in range(n - 1)]
    down = [(i + 1, i) for i in range(n - 1)]
    is_last = r == n - 1
    fzero = jnp.float32(0)
    from ..plan import compiler as _compiler

    splan = _send_plan_for_axis(axis)

    from ..ops.collective_ops import _vma, pvary_missing

    axes_t = _carry_axes(axis, x_mbs, stage_params)

    def vary(tree):
        return _pvary_tree(tree, axes_t)

    mb_shape = x_mbs.shape[1:]
    zeros_mb = pvary_missing(jnp.zeros(mb_shape, x_mbs.dtype), axes_t)
    carry0 = (
        zeros_mb,                                        # act in transit
        zeros_mb.astype(jnp.float32),                    # grad in transit
        vary(jnp.zeros((S,) + mb_shape, x_mbs.dtype)),   # input stash
        zeros_mb.astype(jnp.float32),                    # dy (last stage)
        vary(jax.tree.map(jnp.zeros_like, stage_params)),  # d_stage
        vary(jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), head_params)),
        vary(jnp.zeros(x_mbs.shape, jnp.float32)),       # d_x_mbs
        pvary_missing(fzero, axes_t),                    # loss accum
    )

    def tick(carry, t):
        act, gract, stash, dy_state, d_sp, d_hp, d_x, loss_acc = carry

        # ---- backward phase FIRST: B(m_b), m_b = t - (2n - 1 - r) ----
        # B consumes only previous-tick state (stash written at F time
        # ticks ago, gract/dy_state from the prior tick). Running F first
        # would overwrite dy_state with the NEXT microbatch's cotangent
        # before B(m_b) reads it — off-by-one on every last-stage grad.
        m_b = t - (2 * n - 1 - r)
        b_valid = jnp.logical_and(m_b >= 0, m_b < M)
        x_saved = stash[jnp.clip(m_b, 0, M - 1) % S]
        # Varying copy for the same reason as hp_vary below: under a DP
        # axis the stage params are invariant over it, and the implicit
        # pvary's transpose would psum shard gradients together.
        _, stage_vjp = jax.vjp(
            lambda p, x: stage_fn(p, x), vary(stage_params), x_saved)
        gy = jnp.where(is_last, dy_state, gract)
        g_sp_m, gx = stage_vjp(gy.astype(x_saved.dtype))
        d_sp = jax.tree.map(
            lambda acc, g: acc + jnp.where(b_valid, g, 0.0).astype(
                acc.dtype), d_sp, g_sp_m)
        bidx = jnp.clip(m_b, 0, M - 1)
        write_dx = jnp.logical_and(b_valid, r == 0)
        d_x = d_x.at[bidx].set(
            jnp.where(write_dx, gx.astype(jnp.float32), d_x[bidx]))
        new_gract, _ = _compiler.lower_send(
            splan, gx.astype(jnp.float32), axis=ax, perm=down,
            repeats=T_ticks)

        # ---- forward phase: F(m_f) with m_f = t - r ----
        m_f = t - r
        f_valid = jnp.logical_and(m_f >= 0, m_f < M)
        x_in = jnp.where(r == 0, x_mbs[jnp.clip(m_f, 0, M - 1)], act)
        y = stage_fn(stage_params, x_in)
        slot_f = jnp.clip(m_f, 0, M - 1) % S
        stash = stash.at[slot_f].set(
            jnp.where(f_valid, x_in, stash[slot_f]))

        # last stage: per-microbatch loss + output cotangent + head grads.
        # The head params enter the vjp as a VARYING copy: differentiating
        # through the replicated (invariant) tree would transpose the
        # implicit pvary into a psum, summing every rank's garbage-y
        # contribution into g_hp_m before our mask can drop it.
        hp_vary = vary(head_params)
        tgt = tgt_mbs[jnp.clip(m_f, 0, M - 1)]
        loss_m, head_vjp = jax.vjp(
            lambda hp, y: loss_fn(hp, y, tgt), hp_vary, y)
        # The seed cotangent must carry the same varying axes as loss_m.
        g_hp_m, dy = head_vjp(pvary_missing(jnp.float32(1),
                                            tuple(sorted(_vma(loss_m)))))
        take = jnp.logical_and(is_last, f_valid)
        loss_acc = loss_acc + jnp.where(take, loss_m, fzero)
        d_hp = jax.tree.map(
            lambda acc, g: acc + jnp.where(take, g, 0.0).astype(acc.dtype),
            d_hp, g_hp_m)
        dy_state = jnp.where(take, dy.astype(jnp.float32), dy_state)
        act, _ = _compiler.lower_send(splan, y, axis=ax, perm=up,
                                      repeats=T_ticks)

        return (act, new_gract, stash, dy_state, d_sp, d_hp, d_x,
                loss_acc), None

    (_, _, _, _, d_sp, d_hp, d_x, loss_acc), _ = lax.scan(
        tick, carry0, jnp.arange(T_ticks))

    # loss/head grads live on the last stage, input grads on stage 0;
    # masked psums replicate (every other rank contributed zeros).
    loss = lax.psum(loss_acc, ax) / M
    d_hp = jax.tree.map(
        lambda a: lax.psum(a, ax) / M, d_hp)
    d_x = lax.psum(d_x, ax) / M
    return loss, jax.tree.map(lambda a: a / M, d_sp), d_hp, d_x


def pipelined_gpt_train_1f1b(cfg, stage_params, rest, tokens, targets, *,
                             axis, num_microbatches: int):
    """One fused GPT training computation under the 1F1B schedule:
    returns ``(loss, d_stage_params, d_rest)`` directly (the schedule
    hand-interleaves forward and backward, so this is not a function you
    differentiate — it IS the gradient computation).

    Same contract as :func:`pipelined_gpt_loss` + ``jax.grad``, with
    activation memory O(pipeline_depth) instead of O(num_microbatches):
    use it when M must be large (deep pipelines want M >> n to shrink
    the bubble, which is exactly when GPipe's O(M) stash hurts). The LM
    head runs replicated per microbatch on every rank (masked off the
    last stage) — the memory-lean counterpart of
    :func:`pipelined_gpt_loss`'s vocab-sharded head; exactness vs the
    dense model is tested for both."""
    import optax

    B, T = tokens.shape
    _validate_pipeline_cfg(cfg, B, T, num_microbatches, axis)
    M = num_microbatches

    ep = {"wte": rest["wte"], "wpe": rest["wpe"]}
    # Like the head params in gpipe_1f1b: when tokens are data-sharded
    # (varying over a DP axis), the replicated embedding tree must enter
    # its vjp as a varying copy, or the implicit pvary transposes into a
    # psum over the data axis and g_ep comes back SUMMED across shards —
    # the caller's DP gradient averaging then over-counts.
    from ..ops.collective_ops import _vma

    ep = _pvary_tree(ep, tuple(sorted(_vma(tokens))))
    x, embed_vjp = jax.vjp(lambda ep: _embed(cfg, ep, tokens), ep)
    x_mbs = x.reshape(M, B // M, T, -1)
    tgt_mbs = targets.reshape(M, B // M, T)

    def loss_fn(hp, y, tgt):
        # hp carries exactly the {ln_f, wte} keys _head_logits reads.
        return optax.softmax_cross_entropy_with_integer_labels(
            _head_logits(cfg, hp, y), tgt).mean()

    hp = {"ln_f": rest["ln_f"], "wte": rest["wte"]}
    loss, g_stages, g_hp, d_x = gpipe_1f1b(
        _make_stage_fn(cfg), loss_fn, stage_params, hp, x_mbs, tgt_mbs,
        axis=axis)
    (g_ep,) = embed_vjp(d_x.reshape(B, T, -1).astype(x.dtype))
    g_rest = {
        # wte is tied: embedding-lookup grad + LM-head grad
        "wte": g_ep["wte"].astype(jnp.float32) + g_hp["wte"],
        "wpe": g_ep["wpe"].astype(jnp.float32),
        "ln_f": g_hp["ln_f"],
    }
    return loss, g_stages, g_rest


# ---------------------------------------------------------------------------
# Interleaved-1F1B (docs/pipeline.md): the production schedule. The model
# splits into K = n * v CHUNKS placed round-robin (chunk c on rank c % n,
# local index j = c // n), so each rank holds v non-contiguous "virtual
# stages". Per tick every rank executes at most ONE unit — a chunk
# forward F(m, j) or a chunk backward B(m, j) — and two cyclic ppermutes
# move the tick's products one hop: activations up (r -> r+1 mod n),
# activation-grads down. The unit order per rank is Megatron-LM's
# interleaved-1F1B stream (warmup forwards, strict 1F1B alternation,
# cooldown backwards); the tick assignment comes from a host-side
# simulation of that stream under the 1-tick hop latency, so the whole
# schedule — including every stash slot — is STATIC tables the SPMD scan
# body indexes with the traced rank. Bubble fraction falls from GPipe's
# (S-1)/(M+S-1) to ~(S-1)/(Mv+S-1): the interleave divides the fill.
#
# The ZERO-BUBBLE family (``family="zb1"``, ZB-H1 of arXiv 2401.10241,
# docs/pipeline.md): the backward splits into a dx unit **B** (the
# input-cotangent half — the only part the upstream stage waits on; it
# stays on the critical path and keeps the 1F1B placement) and a dw unit
# **W** (the weight-cotangent half — consumed by nobody downstream, so
# it is DEFERRED into the cooldown/idle ticks after its B). Each unit is
# one vjp half instead of the fused dx+dw vjp, so the per-tick compute
# shrinks while the busy fraction of the rank x tick grid rises: the
# measured ``bubble_fraction`` (idle issue slots / grid) drops strictly
# below the interleaved-1F1B bound on the same (S, M, v). The remaining
# idle ticks are enumerated per rank in ``fill_ticks`` — the T3-style
# fill capacity the ZeRO-3 bucket flights are credited against
# (``plan/accounting.bubble_fill``).
# ---------------------------------------------------------------------------

#: Schedule-table families build_interleaved_schedule can simulate.
PP_TABLE_FAMILIES = ("1f1b", "zb1")


@dataclasses.dataclass(frozen=True)
class PPSchedule:
    """Static interleaved-1F1B schedule tables (host-built, rank-major).

    Every table is ``[n, ticks]`` int32, indexed ``[rank, tick]`` inside
    the scan body. Slot ids index the three stash pools (activation /
    grad / dy); ``-1`` means "no unit" / "discard" / "read x_mbs".
    """

    stages: int
    interleave: int
    microbatches: int
    ticks: int
    act_slots: int
    grad_slots: int
    dy_slots: int
    # forward unit: valid, microbatch, local chunk, input act slot
    # (-1 = x_mbs), dy slot to write (>=0 marks the LAST chunk)
    f_valid: np.ndarray
    f_m: np.ndarray
    f_j: np.ndarray
    f_src: np.ndarray
    f_dy: np.ndarray
    # backward unit: valid, microbatch, local chunk, remat act slot
    # (-1 = x_mbs = chunk 0), grad slot to read (-1 = read dy), dy slot
    b_valid: np.ndarray
    b_m: np.ndarray
    b_j: np.ndarray
    b_src: np.ndarray
    b_g: np.ndarray
    b_dy: np.ndarray
    # arrival routing: where this tick's incoming ppermute values land
    arr_a: np.ndarray
    arr_g: np.ndarray
    # schedule family: "1f1b" (fused dx+dw backward) or "zb1" (ZB-H1
    # B/W split — the W tables below are live only for zb1)
    family: str = "1f1b"
    # weight-grad unit (zb1): valid, microbatch, local chunk, stashed
    # act slot (-1 = x_mbs), grad slot to read (-1 = read dy), dy slot
    w_valid: Optional[np.ndarray] = None
    w_m: Optional[np.ndarray] = None
    w_j: Optional[np.ndarray] = None
    w_src: Optional[np.ndarray] = None
    w_g: Optional[np.ndarray] = None
    w_dy: Optional[np.ndarray] = None
    # fill_ticks[r, t] = k if tick t is rank r's k-th idle tick (no
    # F/B/W unit), else -1 — the T3 bubble-fill capacity table
    # (docs/pipeline.md): idle counts are rank-uniform by construction.
    fill_ticks: Optional[np.ndarray] = None

    @property
    def bubble_fraction(self) -> float:
        """Idle fraction of the rank x tick grid — the measured bubble
        (each tick is one chunk-unit of compute; garbage masked units in
        the bubble cost the same wall time as real ones under SPMD).
        Under zb1 a unit is one vjp HALF (dx-only B or dw-only W), so
        the grid is finer and the idle fraction strictly smaller than
        the fused-backward 1f1b grid on the same (S, M, v)."""
        return 1.0 - self.unit_count() / float(self.stages * self.ticks)

    def unit_count(self) -> int:
        busy = int(self.f_valid.sum() + self.b_valid.sum())
        if self.w_valid is not None:
            busy += int(self.w_valid.sum())
        return busy

    @property
    def units_per_rank(self) -> int:
        """Compute units per rank: Mv forwards + Mv backwards, plus Mv
        deferred W units under zb1. Exact on every rank (the streams
        pump every microbatch through every local chunk)."""
        per = 2 * self.microbatches * self.interleave
        if self.family == "zb1":
            per += self.microbatches * self.interleave
        return per

    @property
    def idle_ticks_per_rank(self) -> int:
        """Per-rank bubble capacity in ticks — the T3 fill budget
        (rank-uniform: every rank runs exactly ``units_per_rank``)."""
        return self.ticks - self.units_per_rank


def _interleaved_streams(M: int, n: int, v: int) -> List[List[tuple]]:
    """Megatron-LM's interleaved-1F1B unit stream per rank: warmup
    forwards, 1F1B alternation, cooldown backwards. Units are
    ``("F"|"B", microbatch, local_chunk)``."""
    total = M * v

    def fwd_unit(k: int) -> tuple:
        if v == 1:
            return ("F", k, 0)
        j = (k // n) % v
        m = (k // (n * v)) * n + k % n
        return ("F", m, j)

    def bwd_unit(k: int) -> tuple:
        if v == 1:
            return ("B", k, 0)
        j = v - 1 - (k // n) % v
        m = (k // (n * v)) * n + k % n
        return ("B", m, j)

    streams = []
    for r in range(n):
        if v == 1:
            warm = min(n - r - 1, total)
        else:
            warm = min((n - r - 1) * 2 + (v - 1) * n, total)
        seq = [fwd_unit(k) for k in range(warm)]
        fi, bi = warm, 0
        while fi < total:
            seq.append(fwd_unit(fi))
            seq.append(bwd_unit(bi))
            fi += 1
            bi += 1
        while bi < total:
            seq.append(bwd_unit(bi))
            bi += 1
        streams.append(seq)
    return streams


def _alloc_slots(intervals: List[tuple]) -> Tuple[dict, int]:
    """Greedy interval-graph coloring: ``intervals`` is a list of
    ``(key, start, end)`` (inclusive); returns ``(slot_of_key,
    pool_size)``. Deterministic: sorted by (start, key)."""
    slot_of = {}
    free: List[int] = []
    in_use: List[tuple] = []  # (end, slot)
    n_slots = 0
    for key, start, end in sorted(intervals,
                                  key=lambda it: (it[1], str(it[0]))):
        still = []
        for iu_end, iu_slot in in_use:
            if iu_end < start:
                free.append(iu_slot)
            else:
                still.append((iu_end, iu_slot))
        in_use = still
        if free:
            s = min(free)
            free.remove(s)
        else:
            s = n_slots
            n_slots += 1
        slot_of[key] = s
        in_use.append((end, s))
    return slot_of, n_slots


def build_interleaved_schedule(M: int, n: int, v: int = 1,
                               family: str = "1f1b") -> PPSchedule:
    """Simulate the interleaved-1F1B streams under the 1-tick hop
    latency and freeze the result as static tables (docs/pipeline.md).

    ``family="zb1"`` runs the SAME simulation for F and B (B stays on
    the critical path: its dx is what the upstream rank waits on), then
    places each deferred W(m, c) unit greedily in the earliest idle
    tick of its rank strictly after B(m, c) — extending the tick count
    when the cooldown overflows — and re-allocates the stash pools with
    the W-extended lifetimes (W re-reads the stashed activation and
    incoming grad AFTER B consumed them).

    Requires ``M % n == 0`` when ``v > 1`` (the Megatron grouping the
    forward/backward unit order is built from)."""
    if n < 2:
        raise ValueError("build_interleaved_schedule needs >= 2 stages")
    if v < 1:
        raise ValueError(f"interleave must be >= 1, got {v}")
    if family not in PP_TABLE_FAMILIES:
        raise ValueError(
            f"unknown schedule family {family!r}: expected one of "
            f"{PP_TABLE_FAMILIES}")
    if v > 1 and M % n:
        raise ValueError(
            f"interleaved-1F1B needs microbatches ({M}) divisible by "
            f"the stage count ({n}): the Megatron unit order pumps "
            f"groups of <stages> microbatches through each virtual "
            f"stage (docs/pipeline.md)")
    K = n * v
    streams = _interleaved_streams(M, n, v)
    ptr = [0] * n
    done_f: dict = {}   # (m, c) -> tick
    done_b: dict = {}
    exec_at: List[List[tuple]] = [[] for _ in range(n)]  # (tick, unit)
    t = 0
    cap = 8 * (2 * M * v + 2 * K) + 64
    while any(p < len(s) for p, s in zip(ptr, streams)):
        if t > cap:
            raise AssertionError(
                f"pipeline schedule simulation did not converge "
                f"(M={M}, n={n}, v={v})")  # pragma: no cover
        for r in range(n):
            if ptr[r] >= len(streams[r]):
                continue
            kind, m, j = streams[r][ptr[r]]
            c = j * n + r
            if kind == "F":
                ready = c == 0 or done_f.get((m, c - 1), t) <= t - 1
            elif c == K - 1:
                ready = done_f.get((m, c), t) <= t - 1
            else:
                ready = done_b.get((m, c + 1), t) <= t - 1
            if not ready:
                continue
            (done_f if kind == "F" else done_b)[(m, c)] = t
            exec_at[r].append((t, (kind, m, j, c)))
            ptr[r] += 1
        t += 1
    T = t

    # --- zb1 W-unit placement (ZB-H1): each W(m, c) lands in the
    # earliest idle tick of its rank strictly after B(m, c), in done_b
    # order (greedy; extends T when the cooldown overflows) ------------
    done_w: dict = {}
    if family == "zb1":
        for r in range(n):
            busy_t = {tick for tick, _ in exec_at[r]}
            for tb, m, c in sorted((done_b[(m, c)], m, c)
                                   for m in range(M)
                                   for c in range(r, K, n)):
                tw = tb + 1
                while tw in busy_t:
                    tw += 1
                busy_t.add(tw)
                done_w[(m, c)] = tw
                exec_at[r].append((tw, ("W", m, c // n, c)))
        T = max(T, max(done_w.values()) + 1)

    # --- stash slot allocation (per pool, shared across ranks so the
    # tables index one pool shape). Under zb1 the stashed activation
    # and incoming grad outlive B: W re-reads both, so every lifetime
    # extends to done_w. -----------------------------------------------
    act_iv, grad_iv, dy_iv = [], [], []
    for m in range(M):
        for c in range(K):
            tf, tb = done_f[(m, c)], done_b[(m, c)]
            te = done_w.get((m, c), tb)
            if c > 0:
                ta = done_f[(m, c - 1)] + 1
                act_iv.append(((m, c), ta, te))
            if c < K - 1:
                ta = done_b[(m, c + 1)] + 1
                grad_iv.append(((m, c), ta, te))
            else:
                dy_iv.append(((m, c), tf, te))
    act_slot, n_act = _alloc_slots(act_iv)
    grad_slot, n_grad = _alloc_slots(grad_iv)
    dy_slot, n_dy = _alloc_slots(dy_iv)

    full = lambda fill: np.full((n, T), fill, np.int32)  # noqa: E731
    fv, fm, fj, fsrc, fdy = (full(0), full(0), full(0), full(-1),
                             full(-1))
    bv, bm, bj, bsrc, bg, bdy = (full(0), full(0), full(0), full(-1),
                                 full(-1), full(-1))
    wv, wm_, wj_, wsrc, wg, wdy = (full(0), full(0), full(0), full(-1),
                                   full(-1), full(-1))
    arr_a, arr_g = full(-1), full(-1)
    for r in range(n):
        for tick, (kind, m, j, c) in exec_at[r]:
            if kind == "F":
                fv[r, tick], fm[r, tick], fj[r, tick] = 1, m, j
                if c > 0:
                    fsrc[r, tick] = act_slot[(m, c)]
                if c == K - 1:
                    fdy[r, tick] = dy_slot[(m, c)]
            elif kind == "B":
                bv[r, tick], bm[r, tick], bj[r, tick] = 1, m, j
                if c > 0:
                    bsrc[r, tick] = act_slot[(m, c)]
                if c == K - 1:
                    bdy[r, tick] = dy_slot[(m, c)]
                else:
                    bg[r, tick] = grad_slot[(m, c)]
            else:  # W (zb1): same stash reads as B, one tick later
                wv[r, tick], wm_[r, tick], wj_[r, tick] = 1, m, j
                if c > 0:
                    wsrc[r, tick] = act_slot[(m, c)]
                if c == K - 1:
                    wdy[r, tick] = dy_slot[(m, c)]
                else:
                    wg[r, tick] = grad_slot[(m, c)]
            # Arrival routing at the CONSUMER: the up hop of F(m, c)
            # lands the activation of chunk c+1 on rank (r+1) % n one
            # tick later; the down hop of B(m, c) lands the grad of
            # chunk c-1 on rank (r-1) % n.
            if kind == "F" and c < K - 1 and tick + 1 < T:
                arr_a[(r + 1) % n, tick + 1] = act_slot[(m, c + 1)]
            if kind == "B" and c > 0 and tick + 1 < T:
                arr_g[(r - 1) % n, tick + 1] = grad_slot[(m, c - 1)]

    # Idle-tick enumeration: the T3 fill capacity table. Rank-uniform
    # by construction (every rank runs exactly units_per_rank units).
    fill = full(-1)
    for r in range(n):
        busy_t = {tick for tick, _ in exec_at[r]}
        k = 0
        for tick in range(T):
            if tick not in busy_t:
                fill[r, tick] = k
                k += 1

    zb = family == "zb1"
    return PPSchedule(
        stages=n, interleave=v, microbatches=M, ticks=T,
        act_slots=max(1, n_act), grad_slots=max(1, n_grad),
        dy_slots=max(1, n_dy),
        f_valid=fv, f_m=fm, f_j=fj, f_src=fsrc, f_dy=fdy,
        b_valid=bv, b_m=bm, b_j=bj, b_src=bsrc, b_g=bg, b_dy=bdy,
        arr_a=arr_a, arr_g=arr_g, family=family,
        w_valid=wv if zb else None, w_m=wm_ if zb else None,
        w_j=wj_ if zb else None, w_src=wsrc if zb else None,
        w_g=wg if zb else None, w_dy=wdy if zb else None,
        fill_ticks=fill)


def emit_schedule_spans(sched: PPSchedule) -> None:
    """Mirror the schedule onto the Timeline as per-rank ``PP:F`` /
    ``PP:B`` spans (tid ``pp-rank<r>``, tick-indexed timestamps) plus a
    ``PP:SCHEDULE`` instant carrying the measured bubble fraction —
    ``span_audit`` audits the balance, ``scripts/obs_report.py`` reads
    the bubble (docs/pipeline.md). Trace-time, like every span here."""
    from ..common import basics

    tl = basics._state.timeline if basics.is_initialized() else None
    if tl is None:
        return
    tl.instant("PP:SCHEDULE", tid="pp", args={
        "stages": sched.stages, "interleave": sched.interleave,
        "microbatches": sched.microbatches, "ticks": sched.ticks,
        "family": sched.family,
        "idle_ticks": sched.idle_ticks_per_rank,
        "bubble_fraction": round(sched.bubble_fraction, 6)})
    for r in range(sched.stages):
        tid = f"pp-rank{r}"
        for t in range(sched.ticks):
            if sched.f_valid[r, t]:
                tl.begin(tid, "PP:F")
                tl.end(tid, "PP:F")
            if sched.b_valid[r, t]:
                tl.begin(tid, "PP:B")
                tl.end(tid, "PP:B")
            if sched.w_valid is not None and sched.w_valid[r, t]:
                tl.begin(tid, "PP:W")
                tl.end(tid, "PP:W")


def pp_split_chunks(params, n: int, v: int = 1):
    """Dense GPT params → (chunks, rest) for the interleaved schedule.

    ``chunks``: each transformer-block leaf stacked ``[n, v, L/(n*v),
    ...]`` — rank r's local chunk j holds blocks of GLOBAL chunk
    ``c = j * n + r`` (round-robin placement), i.e. blocks
    ``[c*L/K, (c+1)*L/K)``. Pass through shard_map with
    ``in_specs=P(pp_axis)`` and squeeze the leading dim; ``v = 1``
    degenerates to :func:`pp_split_blocks`' contiguous split. ``rest``:
    the replicated embedding/head tree."""
    blocks = sorted((k for k in params if k.startswith("h")),
                    key=lambda k: int(k[1:]))
    L = len(blocks)
    K = n * v
    if L % K:
        raise ValueError(
            f"{L} blocks not divisible by {n} stages x {v} virtual "
            f"stages = {K} chunks")
    per = L // K

    def stack(*leaves):
        return jnp.stack([
            jnp.stack([
                jnp.stack(leaves[(j * n + r) * per:(j * n + r + 1) * per])
                for j in range(v)])
            for r in range(n)])

    chunks = jax.tree.map(stack, *[params[b] for b in blocks])
    rest = {k: p for k, p in params.items() if not k.startswith("h")}
    return chunks, rest


def interleaved_1f1b(stage_fn, loss_fn, chunk_params, head_params, x_mbs,
                     tgt_mbs, *, axis, interleave: int = 1,
                     send_plan=None, sched: Optional[PPSchedule] = None,
                     family: str = "1f1b"):
    """Interleaved-1F1B pipeline: loss + gradients in one fused pass,
    bubble ~``(S-1)/(Mv+S-1)`` vs GPipe's ``(S-1)/(M+S-1)``.

    Same contract as :func:`gpipe_1f1b` with ``chunk_params`` this
    rank's ``[v, ...]`` stacked virtual-stage tree (``stage_fn(chunk,
    x)`` applies ONE chunk); returns ``(loss, d_chunk_params,
    d_head_params, d_x_mbs)`` with the same replication/per-data-shard
    semantics. Inter-stage hops are wire-plan ``send`` legs
    (``send_plan``; default: the payload-dtype plan for ``axis``' link
    class — pass a quantized plan for the int8+EF activation wire)."""
    n = _axis_size(axis)
    v = max(1, int(interleave))
    M = x_mbs.shape[0]
    if n == 1:
        def full_fn(cp, x):
            for j in range(v):
                x = stage_fn(jax.tree.map(lambda a: a[j], cp), x)
            return x

        return gpipe_1f1b(full_fn, loss_fn, chunk_params, head_params,
                          x_mbs, tgt_mbs, axis=axis)

    from ..plan import compiler as _compiler
    from ..plan.accounting import pp_span

    if sched is None:
        sched = build_interleaved_schedule(M, n, v, family=family)
    if sched.microbatches != M or sched.stages != n \
            or sched.interleave != v:
        raise ValueError(
            f"schedule is ({sched.microbatches} microbatches, "
            f"{sched.stages} stages, x{sched.interleave}), step wants "
            f"({M}, {n}, x{v})")
    zb = sched.family == "zb1"   # host-level: the 1f1b trace is unchanged
    if send_plan is None:
        send_plan = _send_plan_for_axis(axis)
    splan = send_plan.validate()
    ef = any(l.error_feedback for l in splan.legs)
    emit_schedule_spans(sched)

    ax = axis if isinstance(axis, str) else tuple(axis)
    r = lax.axis_index(ax)
    T = sched.ticks
    up = [(i, (i + 1) % n) for i in range(n)]
    down = [(i, (i - 1) % n) for i in range(n)]
    fzero = jnp.float32(0)

    from ..ops.collective_ops import _vma, pvary_missing

    axes_t = _carry_axes(axis, x_mbs, chunk_params)

    def vary(tree):
        return _pvary_tree(tree, axes_t)

    table_keys = ["f_valid", "f_m", "f_j", "f_src", "f_dy",
                  "b_valid", "b_m", "b_j", "b_src", "b_g", "b_dy",
                  "arr_a", "arr_g"]
    if zb:
        table_keys += ["w_valid", "w_m", "w_j", "w_src", "w_g", "w_dy"]
    tables = {k: jnp.asarray(getattr(sched, k)) for k in table_keys}

    mb_shape = x_mbs.shape[1:]
    zmb = pvary_missing(jnp.zeros(mb_shape, x_mbs.dtype), axes_t)
    zmb32 = zmb.astype(jnp.float32)
    pool = lambda k, dt: vary(jnp.zeros((k,) + mb_shape, dt))  # noqa: E731
    res0 = (zmb32, zmb32) if ef else None
    carry0 = (
        zmb,                                   # activation in transit
        zmb32,                                 # grad in transit
        pool(sched.act_slots, x_mbs.dtype),    # received-act + remat stash
        pool(sched.grad_slots, jnp.float32),   # received-grad stash
        pool(sched.dy_slots, jnp.float32),     # dy stash (last chunk)
        vary(jax.tree.map(jnp.zeros_like, chunk_params)),   # d_chunks
        vary(jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), head_params)),
        vary(jnp.zeros(x_mbs.shape, jnp.float32)),          # d_x_mbs
        pvary_missing(fzero, axes_t),                       # loss accum
        res0,                                  # send EF residuals
    )

    def cell(idx):
        return lambda tbl: tbl[r, idx]

    def tick(carry, t):
        (act_in, grad_in, apool, gpool, dypool, d_cp, d_hp, d_x,
         loss_acc, res) = carry
        at = cell(t)

        # -- arrivals: last tick's ppermute values land in their slots
        aslot = at(tables["arr_a"])
        ai = jnp.clip(aslot, 0, sched.act_slots - 1)
        apool = apool.at[ai].set(
            jnp.where(aslot >= 0, act_in, apool[ai]))
        gslot = at(tables["arr_g"])
        gi = jnp.clip(gslot, 0, sched.grad_slots - 1)
        gpool = gpool.at[gi].set(
            jnp.where(gslot >= 0, grad_in, gpool[gi]))

        # -- backward unit first (consumes only pre-tick state) --------
        b_on = at(tables["b_valid"]) > 0
        bm = jnp.clip(at(tables["b_m"]), 0, M - 1)
        bj = at(tables["b_j"])
        bsrc = at(tables["b_src"])
        x_saved = jnp.where(
            bsrc >= 0,
            apool[jnp.clip(bsrc, 0, sched.act_slots - 1)],
            x_mbs[bm])
        bdy = at(tables["b_dy"])
        bgs = at(tables["b_g"])
        gy = jnp.where(
            bdy >= 0,
            dypool[jnp.clip(bdy, 0, sched.dy_slots - 1)],
            gpool[jnp.clip(bgs, 0, sched.grad_slots - 1)])
        if zb:
            # zb1 B unit: the dx HALF only — params are closed over, so
            # the transpose never forms their cotangent (that is the
            # deferred W unit's tick).
            _, x_vjp = jax.vjp(
                lambda x: stage_fn(
                    jax.tree.map(lambda a: a[bj], chunk_params), x),
                x_saved)
            (gx,) = x_vjp(gy.astype(x_saved.dtype))
        else:
            _, chunk_vjp = jax.vjp(
                lambda p, x: stage_fn(
                    jax.tree.map(lambda a: a[bj], p), x),
                vary(chunk_params), x_saved)
            g_cp, gx = chunk_vjp(gy.astype(x_saved.dtype))
            d_cp = jax.tree.map(
                lambda acc, g: acc + jnp.where(b_on, g, 0.0).astype(
                    acc.dtype), d_cp, g_cp)
        write_dx = jnp.logical_and(b_on, bsrc < 0)  # chunk 0 <=> rank 0
        d_x = d_x.at[bm].set(
            jnp.where(write_dx, gx.astype(jnp.float32), d_x[bm]))

        # -- zb1 W unit: the deferred dw HALF — re-reads the stashed
        # activation and incoming grad B left alive (the builder
        # extended both lifetimes to done_w) and forms ONLY the param
        # cotangent.
        if zb:
            w_on = at(tables["w_valid"]) > 0
            wm = jnp.clip(at(tables["w_m"]), 0, M - 1)
            wj = at(tables["w_j"])
            wsrc = at(tables["w_src"])
            x_w = jnp.where(
                wsrc >= 0,
                apool[jnp.clip(wsrc, 0, sched.act_slots - 1)],
                x_mbs[wm])
            wdy = at(tables["w_dy"])
            wgs = at(tables["w_g"])
            gy_w = jnp.where(
                wdy >= 0,
                dypool[jnp.clip(wdy, 0, sched.dy_slots - 1)],
                gpool[jnp.clip(wgs, 0, sched.grad_slots - 1)])
            _, w_vjp = jax.vjp(
                lambda p: stage_fn(
                    jax.tree.map(lambda a: a[wj], p), x_w),
                vary(chunk_params))
            (g_cp_w,) = w_vjp(gy_w.astype(x_w.dtype))
            d_cp = jax.tree.map(
                lambda acc, g: acc + jnp.where(w_on, g, 0.0).astype(
                    acc.dtype), d_cp, g_cp_w)

        # -- forward unit ----------------------------------------------
        f_on = at(tables["f_valid"]) > 0
        fm = jnp.clip(at(tables["f_m"]), 0, M - 1)
        fj = at(tables["f_j"])
        fsrc = at(tables["f_src"])
        x_in = jnp.where(
            fsrc >= 0,
            apool[jnp.clip(fsrc, 0, sched.act_slots - 1)],
            x_mbs[fm])
        y = stage_fn(jax.tree.map(lambda a: a[fj], chunk_params), x_in)
        # last chunk: per-microbatch loss + head grads + dy stash (the
        # vjp enters through VARYING copies — see gpipe_1f1b).
        hp_vary = vary(head_params)
        tgt = tgt_mbs[fm]
        loss_m, head_vjp = jax.vjp(
            lambda hp, yy: loss_fn(hp, yy, tgt), hp_vary, y)
        g_hp_m, dy = head_vjp(pvary_missing(
            jnp.float32(1), tuple(sorted(_vma(loss_m)))))
        fdy = at(tables["f_dy"])
        take = jnp.logical_and(f_on, fdy >= 0)
        loss_acc = loss_acc + jnp.where(take, loss_m, fzero)
        d_hp = jax.tree.map(
            lambda acc, g: acc + jnp.where(take, g, 0.0).astype(
                acc.dtype), d_hp, g_hp_m)
        di = jnp.clip(fdy, 0, sched.dy_slots - 1)
        dypool = dypool.at[di].set(
            jnp.where(take, dy.astype(jnp.float32), dypool[di]))

        # -- the tick's two send legs ----------------------------------
        a_res, g_res = res if ef else (None, None)
        act_out, a_res = _compiler.lower_send(
            splan, y, axis=ax, perm=up, residual=a_res, repeats=T)
        grad_out, g_res = _compiler.lower_send(
            splan, gx.astype(jnp.float32), axis=ax, perm=down,
            residual=g_res, repeats=T)
        new_res = (a_res, g_res) if ef else None
        return (act_out, grad_out, apool, gpool, dypool, d_cp, d_hp,
                d_x, loss_acc, new_res), None

    with pp_span("SCHED"):
        (_, _, _, _, _, d_cp, d_hp, d_x, loss_acc, _), _ = lax.scan(
            tick, carry0, jnp.arange(T))

    loss = lax.psum(loss_acc, ax) / M
    d_hp = jax.tree.map(lambda a: lax.psum(a, ax) / M, d_hp)
    d_x = lax.psum(d_x, ax) / M
    return loss, jax.tree.map(lambda a: a / M, d_cp), d_hp, d_x


# The schedule family (docs/pipeline.md): gpipe is the autodiff baseline,
# 1f1b the O(depth)-memory hand schedule, interleaved_1f1b the
# production schedule (1f1b == interleaved with v pinned to 1; the
# explicit name keeps the baseline selectable), zb1 the ZB-H1
# zero-bubble variant of interleaved_1f1b (B/W backward split).
PP_SCHEDULES = ("gpipe", "1f1b", "interleaved_1f1b", "zb1")


def pipelined_gpt_train(cfg, chunk_params, rest, tokens, targets, *,
                        axis, num_microbatches: int,
                        schedule: str = "interleaved_1f1b",
                        interleave: int = 1, send_plan=None):
    """One fused GPT training computation under any pipeline schedule:
    returns ``(loss, d_chunk_params, d_rest)`` — the production entry
    point (docs/pipeline.md; ``tests/test_pp.py`` holds it to the dense
    model).

    ``chunk_params`` is this rank's ``[v, L/(n*v), ...]`` stacked tree
    from :func:`pp_split_chunks` (``v = 1`` for gpipe/1f1b);
    ``schedule`` picks the family member; ``send_plan`` threads an
    explicit activation wire (e.g. the int8+EF plan) into the hops."""
    import optax

    if schedule not in PP_SCHEDULES:
        raise ValueError(
            f"unknown pipeline schedule {schedule!r}: one of "
            f"{PP_SCHEDULES} (docs/pipeline.md)")
    v = max(1, int(interleave))
    if schedule in ("gpipe", "1f1b") and v > 1:
        raise ValueError(
            f"schedule={schedule!r} does not interleave: virtual stages "
            f"(pp_interleave={v}) need schedule='interleaved_1f1b'")
    B, T = tokens.shape
    _validate_pipeline_cfg(cfg, B, T, num_microbatches, axis)
    M = num_microbatches

    ep = {"wte": rest["wte"], "wpe": rest["wpe"]}
    from ..ops.collective_ops import _vma

    ep = _pvary_tree(ep, tuple(sorted(_vma(tokens))))
    x, embed_vjp = jax.vjp(lambda e: _embed(cfg, e, tokens), ep)
    x_mbs = x.reshape(M, B // M, T, -1)
    tgt_mbs = targets.reshape(M, B // M, T)

    def loss_fn(hp, y, tgt):
        return optax.softmax_cross_entropy_with_integer_labels(
            _head_logits(cfg, hp, y), tgt).mean()

    hp = {"ln_f": rest["ln_f"], "wte": rest["wte"]}
    stage_fn = _make_stage_fn(cfg)

    if schedule == "gpipe":
        # Autodiff baseline: differentiate the relay forward + head loss
        # (O(M) activation memory — the cost 1F1B exists to cut).
        ring = ({axis} if isinstance(axis, str) else set(axis))
        union = set()
        for leaf in (jax.tree.leaves(chunk_params)
                     + jax.tree.leaves(hp) + [x_mbs, tgt_mbs]):
            union |= _vma(leaf)
        union_t = tuple(sorted(union | ring))

        def total(cp, h, xm):
            sp = jax.tree.map(lambda a: a[0], cp)  # [1, L/n, ...] -> [L/n, ...]
            ys = gpipe(stage_fn, sp, xm, axis=axis)
            losses = jax.vmap(
                lambda ym, tm: loss_fn(h, ym, tm))(
                ys, _pvary_tree(tgt_mbs, union_t))
            return losses.mean()

        loss, (g_cp, g_hp, d_x) = jax.value_and_grad(
            total, argnums=(0, 1, 2))(
            _pvary_tree(chunk_params, union_t),
            _pvary_tree(hp, union_t), _pvary_tree(x_mbs, union_t))
        n = _axis_size(axis)
        if n > 1:
            # gpipe() replicates loss/outputs itself; grads of the
            # replicated head/input come back per-rank — average.
            ax = axis if isinstance(axis, str) else tuple(axis)
            g_hp = jax.tree.map(lambda a: lax.psum(a, ax) / n, g_hp)
            d_x = lax.psum(d_x, ax) / n
            loss = lax.psum(loss, ax) / n
    elif schedule == "1f1b":
        sp = jax.tree.map(lambda a: a[0], chunk_params)
        loss, g_sp, g_hp, d_x = gpipe_1f1b(
            stage_fn, loss_fn, sp, hp, x_mbs, tgt_mbs, axis=axis)
        g_cp = jax.tree.map(lambda a: a[None], g_sp)
    else:
        loss, g_cp, g_hp, d_x = interleaved_1f1b(
            stage_fn, loss_fn, chunk_params, hp, x_mbs, tgt_mbs,
            axis=axis, interleave=v, send_plan=send_plan,
            family="zb1" if schedule == "zb1" else "1f1b")

    (g_ep,) = embed_vjp(d_x.reshape(B, T, -1).astype(x.dtype))
    g_rest = {
        # wte is tied: embedding-lookup grad + LM-head grad
        "wte": g_ep["wte"].astype(jnp.float32) + g_hp["wte"],
        "wpe": g_ep["wpe"].astype(jnp.float32),
        "ln_f": g_hp["ln_f"],
    }
    return loss, g_cp, g_rest
