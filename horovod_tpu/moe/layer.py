"""Expert-parallel MoE training: top-k gated expert FFN with wire-plan
all-to-all dispatch (docs/moe.md).

The layer is the training half of the MoE scenario family. Routing math:

1. **route** — ``logits = x @ router`` → softmax probs; ``lax.top_k``
   picks each token's K experts, the selected gates renormalize to sum
   one. Two auxiliary losses ride along: the Switch load-balance loss
   (``E · Σ_e f_e · P_e`` over top-1 assignment fractions ``f_e`` and
   mean probs ``P_e``) and the router z-loss
   (``mean(logsumexp(logits)²)``, ST-MoE: keeps logits bounded so the
   int8 dispatch wire stays well-scaled);
2. **capacity** — every expert accepts at most
   ``ceil(K · N · capacity_factor / E)`` token-choices per step.
   Position-in-expert assignment is DETERMINISTIC: choices are ranked
   choice-major (all first choices before all second choices, token
   order within a choice), so a rerun of the same batch dispatches
   identically — no RNG in the hot path;
3. **dispatch** — kept choices scatter into a static ``[E, cap, C]``
   buffer; overflow choices are DROPPED (they contribute zero to the
   combine, so a fully-dropped token passes through the caller's
   residual connection untouched — standard Switch semantics);
4. **exchange** — the buffer crosses the dedicated ``hvd_ep`` mesh axis
   as a first-class ``a2a`` wire plan
   (:func:`horovod_tpu.plan.compiler.lower_a2a`): validated IR,
   blockwise-int8 payload with error feedback on DCN-class hops
   (EQuARX), ``MOE:DISPATCH``/``MOE:COMBINE`` spans, and
   ``comm.moe.bytes{hop}`` / ``WireStats.a2a_bytes`` accounting for
   free;
5. **expert FFN** — batched einsum over this ep rank's local experts;
6. **combine** — the reverse exchange returns expert outputs to their
   source rank; each token sums its kept choices' outputs weighted by
   the renormalized gates.

The ``hvd_ep`` axis is NOT a data/world axis (the hvd_pp pattern,
docs/pipeline.md): ``hvd.init(ep_size=E)`` puts it leading the mesh, so
``axes=None`` gradient collectives resolve to the data axes only and an
expert's gradients reduce exclusively within its own data group —
ZeRO stages, overlap, and the quantized gradient wire compose unchanged.
Router/dense gradients, which ARE data-dependent per ep rank when the
batch shards over ``hvd_ep``, get their explicit ep-mean via
:func:`ep_mean_dense_grads`.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..common import basics
from ..common.basics import EP_AXIS
from ..ops.collective_ops import _vma, pvary_missing
from ..ops.flash_attention import _harmonize_vma
from ..plan import compiler as _compiler
from ..plan import planner as _planner


def _axis_size(axis) -> int:
    if axis is None:
        return 1
    n = 1
    for a in ((axis,) if isinstance(axis, str) else tuple(axis)):
        n *= basics._axis_size(a)
    return n


@dataclasses.dataclass(frozen=True)
class MoEAux:
    """Per-call routing diagnostics (all scalars/arrays are traced
    values). ``load`` is the kept token-choice count per GLOBAL expert
    ``[E]`` — the expert-load histogram's source; ``dropped_fraction``
    the fraction of token-choices that overflowed capacity."""

    load_balance_loss: jnp.ndarray
    z_loss: jnp.ndarray
    load: jnp.ndarray
    dropped_fraction: jnp.ndarray


def moe_capacity(n_tokens: int, num_experts: int,
                 capacity_factor: float, topk: int) -> int:
    """Per-expert dispatch capacity: ``ceil(K·N·cf / E)``, floor 1."""
    return max(1, int(-(-topk * n_tokens * float(capacity_factor)
                        // num_experts)))


SCORINGS = ("softmax", "sigmoid")

#: The ``checkpoint_name`` of what the dropless walk is laid out by: the
#: chosen experts and their scores here, ``moe_ffn_dropless``'s ``order``
#: and ``sizes``. A rematerialised block that keeps the name (a few bytes a
#: token-choice) runs no top-k, no gather and no sort in its recomputed
#: forward.
PLAN_NAME = "hvd_moe_plan"


@jax.custom_jvp
def _read_at(probs, experts, vals):
    """``vals``, which ARE ``probs`` at ``experts`` (a top-k's two results),
    as a function of ``probs``: the derivative ``lax.top_k``'s own rule
    gives its values, read at the indices handed in. That rule reads the
    indices of a top-k it runs for itself, so a kept plan did not spare a
    recomputed forward the top-k; this one does."""
    return vals


@_read_at.defjvp
def _read_at_jvp(primals, tangents):
    _, experts, vals = primals
    return vals, jnp.take_along_axis(tangents[0], experts, axis=-1)


def moe_router(x, router_kernel, *, topk: int = 2,
               router_logits=None, scoring: str = "softmax",
               bias=None, route_norm: bool = True,
               route_scale: float = 1.0):
    """Top-k routing of tokens ``x [N, C]`` through ``router_kernel
    [C, E]``. Returns ``(experts [N, K] int32, gates [N, K] fp32,
    load_balance_loss, z_loss, probs [N, E])``.

    ``router_logits`` overrides the computed logits (tests pin routing
    deterministically with it; shape ``[N, E]``).

    ``scoring``: ``"softmax"`` over the E logits (the default: the top K
    probabilities, renormalised to sum one) or ``"sigmoid"``, an
    independent score an expert (``probs`` is then the scores). With the
    sigmoid, ``bias`` ``[E]`` is added to the scores FOR THE SELECTION
    ONLY: the K experts are the largest of ``score + bias``, the gates are
    their scores alone (the bias chooses, it never weighs), divided by
    their sum (+ 1e-20) where ``route_norm`` and multiplied by
    ``route_scale``. The bias is state, not a parameter: no gradient
    reaches it (:func:`router_bias_update` moves it)."""
    E = router_kernel.shape[-1]
    if topk < 1 or topk > E:
        raise ValueError(f"topk must be in 1..{E} (num experts), got "
                         f"{topk}")
    if scoring not in SCORINGS:
        raise ValueError(f"scoring is one of {SCORINGS}, got {scoring!r}")
    if router_logits is None:
        router_logits = jnp.einsum(
            "nc,ce->ne", x.astype(jnp.float32),
            router_kernel.astype(jnp.float32))
    if scoring == "sigmoid":
        probs = jax.nn.sigmoid(router_logits)
        chosen_by = probs if bias is None else probs + lax.stop_gradient(
            bias.astype(jnp.float32))
        experts = checkpoint_name(lax.top_k(chosen_by, topk)[1],
                                  PLAN_NAME)                 # [N, K]
        gates = checkpoint_name(
            jnp.take_along_axis(probs, experts, axis=-1), PLAN_NAME)
        if route_norm:
            gates = gates / (jnp.sum(gates, axis=-1, keepdims=True) + 1e-20)
        gates = gates * route_scale
    else:
        if bias is not None or not route_norm or route_scale != 1.0:
            raise ValueError("a selection bias, route_norm=False and "
                             "route_scale belong to scoring='sigmoid'")
        probs = jax.nn.softmax(router_logits, axis=-1)
        gate_vals, experts = (checkpoint_name(a, PLAN_NAME)  # [N, K]
                              for a in lax.top_k(lax.stop_gradient(probs),
                                                 topk))
        gate_vals = _read_at(probs, experts, gate_vals)
        gates = gate_vals / jnp.maximum(
            jnp.sum(gate_vals, axis=-1, keepdims=True), 1e-9)
    # Load-balance aux (Switch eq. 4): f_e from the TOP-1 assignment
    # (the loss targets the primary routing decision), P_e = mean probs.
    top1 = jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32)
    frac = jnp.mean(top1, axis=0)
    lb = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    # Router z-loss (ST-MoE): keeps logits bounded.
    z = jnp.mean(jax.nn.logsumexp(router_logits, axis=-1) ** 2)
    return experts.astype(jnp.int32), gates, lb, z, probs


def moe_positions(experts, E: int, capacity: int):
    """Deterministic position-in-expert assignment for the ``[N, K]``
    expert choices: choices rank CHOICE-MAJOR (every token's first
    choice before any second choice, token order within a choice), each
    taking the next slot of its expert's queue. Returns ``(pos [N, K]
    int32, keep [N, K] bool)`` — ``keep`` is False for choices past
    ``capacity`` (dropped)."""
    N, K = experts.shape
    flat = jnp.transpose(experts).reshape(K * N)          # choice-major
    oh = jax.nn.one_hot(flat, E, dtype=jnp.int32)         # [KN, E]
    pos_flat = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1
    pos = jnp.transpose(pos_flat.reshape(K, N))           # [N, K]
    keep = pos < capacity
    return pos.astype(jnp.int32), keep


def _exchange(buf, plan, axis, residual, kind):
    """One a2a hop of the ``[E, cap, C]`` buffer over ``axis`` (size
    n): canonical row form in, dispatch semantics out. ``kind`` is
    ``DISPATCH`` (→ ``[E_local, n·cap, C]``) or ``COMBINE`` (the
    reverse)."""
    n = _axis_size(axis)
    E, cap, C = buf.shape
    if kind == "DISPATCH":
        out, new_res = _compiler.lower_a2a(plan, buf, axis=axis,
                                           residual=residual, kind=kind)
        # Row block j (= [E/n, cap, C] from rank j) concatenates along
        # the capacity dim: [n, E/n, cap, C] -> [E/n, n*cap, C].
        e_loc = E // n
        return (jnp.transpose(out.reshape(n, e_loc, cap, C),
                              (1, 0, 2, 3)).reshape(e_loc, n * cap, C),
                new_res)
    # COMBINE: [E_local, n*cap, C] -> rows [n, E_local, cap, C] -> a2a
    # -> [E, cap, C] (E = n * E_local, expert-major again).
    e_loc, ncap, C = buf.shape
    cap = ncap // n
    rows = jnp.transpose(buf.reshape(e_loc, n, cap, C),
                         (1, 0, 2, 3)).reshape(n * e_loc, cap, C)
    out, new_res = _compiler.lower_a2a(plan, rows, axis=axis,
                                       residual=residual, kind=kind)
    return out, new_res


def default_a2a_plan(axis=None, *, quantized: bool = False,
                     block: Optional[int] = None,
                     error_feedback: Optional[bool] = None):
    """The a2a plan of an hvd_ep hop (docs/moe.md): the leg's level is
    the slowest link class one ep hop crosses — the ep axis leads the
    mesh, so it jumps a whole data mesh (``ep_a2a_level``); a custom
    ``axis`` naming data axes maps onto its own widest level.
    Quantization is forced off on an ICI-class hop (the EQuARX rule
    the IR validates)."""
    from ..common.basics import CROSS_AXIS, POD_AXIS

    axes = ({axis} if isinstance(axis, str)
            else set(axis) if axis is not None else {EP_AXIS})
    if POD_AXIS in axes:
        level = _planner.POD
    elif CROSS_AXIS in axes:
        level = _planner.DCN
    elif EP_AXIS in axes and basics.is_initialized():
        level = _planner.ep_a2a_level(basics.data_mesh_shape())
    else:
        level = _planner.ICI
    q = bool(quantized) and level != _planner.ICI
    ef = q if error_feedback is None else (error_feedback and q)
    return _planner.a2a_plan(level, quantized=q, block=block,
                             error_feedback=ef)


def moe_ffn(x, params, *, topk: int = 2, capacity_factor: float = 1.25,
            ep_axis=None, a2a_plan=None, residuals=None,
            router_logits=None) -> Tuple[jnp.ndarray, MoEAux, object]:
    """Top-k gated expert FFN over flattened tokens ``x [N, C]``.

    ``params`` is a dict: ``router [C, E]`` (replicated over hvd_ep),
    ``w1 [E_local, C, F]``, ``b1 [E_local, F]``, ``w2 [E_local, F, C]``,
    ``b2 [E_local, C]`` (expert-sharded: ``E = E_local · ep`` where
    ``ep`` is the bound size of ``ep_axis``). Returns ``(y [N, C],
    :class:`MoEAux`, new_residuals)`` — ``y`` is zero for dropped
    token-choices (the caller's residual connection passes dropped
    tokens through).

    ``a2a_plan`` is the validated dispatch/combine wire plan (default:
    :func:`default_a2a_plan` for ``ep_axis``); ``residuals`` threads the
    int8 error-feedback state as a ``(dispatch_res, combine_res)`` pair
    of zero-initialized buffers (:func:`moe_ef_residuals`) — pass None
    on an exact wire."""
    N, C = x.shape
    ep = _axis_size(ep_axis) if ep_axis is not None else 1
    E_local = params["w1"].shape[0]
    E = E_local * ep
    if params["router"].shape[-1] != E:
        raise ValueError(
            f"router has {params['router'].shape[-1]} experts but "
            f"E_local {E_local} x ep {ep} = {E}")
    capacity = moe_capacity(N, E, capacity_factor, topk)

    experts, gates, lb, z, _probs = moe_router(
        x, params["router"], topk=topk, router_logits=router_logits)
    pos, keep = moe_positions(experts, E, capacity)
    pos_c = jnp.minimum(pos, capacity - 1)

    # Diagnostics: kept choices per global expert + dropped fraction.
    kept_oh = (jax.nn.one_hot(experts, E, dtype=jnp.float32)
               * keep[..., None].astype(jnp.float32))
    load = jnp.sum(kept_oh, axis=(0, 1))                  # [E]
    dropped = 1.0 - jnp.sum(keep) / float(keep.size)
    aux = MoEAux(load_balance_loss=lb, z_loss=z, load=load,
                 dropped_fraction=dropped)

    # Dispatch buffer [E, cap, C]: kept choices scatter-add into their
    # expert's queue slot (disjoint (expert, pos) per kept choice, so
    # the add is a pure placement).
    xk = jnp.broadcast_to(x[:, None, :], (N, topk, C))
    disp = jnp.zeros((E, capacity, C), x.dtype).at[
        experts, pos_c].add(jnp.where(keep[..., None], xk, 0))

    res_d = res_c = None
    if residuals is not None:
        res_d, res_c = residuals
    if ep > 1:
        plan = a2a_plan or default_a2a_plan(ep_axis)
        recv, new_res_d = _exchange(disp, plan, ep_axis, res_d,
                                    "DISPATCH")
    else:
        recv, new_res_d = disp, (None if res_d is None
                                 else jnp.zeros_like(res_d))

    h = jnp.einsum("ekc,ecf->ekf", recv, params["w1"]) \
        + params["b1"][:, None]
    h = nn.gelu(h)
    out = jnp.einsum("ekf,efc->ekc", h, params["w2"]) \
        + params["b2"][:, None]

    if ep > 1:
        back, new_res_c = _exchange(out, plan, ep_axis, res_c, "COMBINE")
    else:
        back, new_res_c = out, (None if res_c is None
                                else jnp.zeros_like(res_c))

    # Combine: each token sums its kept choices' expert outputs,
    # weighted by the renormalized gates.
    yk = back[experts, pos_c]                             # [N, K, C]
    yk = jnp.where(keep[..., None], yk, 0) \
        * gates[..., None].astype(back.dtype)
    y = jnp.sum(yk, axis=1).astype(x.dtype)
    new_residuals = (None if residuals is None
                     else (new_res_d, new_res_c))
    return y, aux, new_residuals


def moe_ef_residuals(n_tokens: int, d_model: int, num_experts: int,
                     capacity_factor: float = 1.25, topk: int = 2,
                     ep: int = 1, dtype=jnp.float32):
    """Zero-initialized error-feedback residual pair for
    :func:`moe_ffn`'s int8 wire: one buffer per exchange direction,
    each matching the exchanged buffer's shape. Thread the returned
    pair through the step's carry exactly like the optimizer's
    ``QuantizedEFState`` residual (docs/moe.md)."""
    E = num_experts
    cap = moe_capacity(n_tokens, E, capacity_factor, topk)
    shape = (E, cap, d_model)
    return (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


# ---------------------------------------------------------------------------
# The dropless path: the experts this chip holds, as grouped matmuls.
# ---------------------------------------------------------------------------

#: Rows a held expert's group starts on a multiple of: the row tile of the
#: TPU compiler's grouped matmul (its tile list at 131,072 rows and 16
#: groups is 256 + 15 long). With every group on a tile boundary no tile
#: is visited for two groups, and the tiles visited are the buffer's.
GROUP_ALIGN = 512


#: Rows of the buffer one trip of the walk takes: a multiple of
#: ``GROUP_ALIGN``, and the smallest one. One tile a trip read 18.2 ms
#: forward + backward at uniform load where two read 19.1 and four 21.4
#: (the sparse cell's sizes; PERF.md, PR 31), and it is what the walk's
#: body counts on: a chunk lies in ONE expert's group, so its group sizes
#: are one entry and its weight gradients one expert's slab.
CHUNK_ROWS = GROUP_ALIGN


def rows_grouped(choices: int, held: int) -> int:
    """The bound on :func:`moe_ffn_dropless`'s row buffer: every one of the
    ``choices`` (N * K) on a held expert, and a tile a group of padding."""
    return (-(-choices // GROUP_ALIGN) + held) * GROUP_ALIGN


def plan_bytes(choices: int, held: int) -> int:
    """Bytes of what carries ``PLAN_NAME`` in one
    :func:`moe_ffn_dropless` call of ``choices`` (N * K) token-choices:
    the chosen experts, their scores and ``order``, four bytes a choice
    each, and the held groups' ``sizes``."""
    return 4 * (3 * choices + held)


def _chunk(c, plan, K):
    """Rows ``[c, c + 1) * CHUNK_ROWS`` of the buffer, ``c`` under
    :func:`_trips`: the group they lie in, the token-choice and the token
    each holds (past the last of them where it holds none: read as zeros,
    dropped when written) and the group sizes of the chunk (all its rows
    are the one group's)."""
    order, sizes, start, first_row, padded = plan
    lo = c * CHUNK_ROWS
    group = jnp.sum(lo >= first_row + padded)
    lane = jnp.arange(CHUNK_ROWS, dtype=jnp.int32)
    rank = lo - first_row[group] + lane
    live = rank < sizes[group]
    choice = order[jnp.where(live, start[group] + rank, 0)]
    n = order.shape[0]
    gs = jnp.where(jnp.arange(sizes.shape[0]) == group, CHUNK_ROWS, 0)
    return (group, jnp.where(live, choice, n + lane),
            jnp.where(live, choice // K, n // K + lane), gs)


def _rows(a, at):
    """``a[at]``, zeros where ``at`` lies past the end (a dead row)."""
    return a.at[at].get(mode="fill", fill_value=0)


def _trips(plan):
    return -(-jnp.sum(plan[-1]) // CHUNK_ROWS)


def _zeros_like_of(x):
    """``zeros(shape, dtype)`` varying over the mesh axes ``x`` varies over:
    what a loop's carry has to be from its first trip under shard_map."""
    axes = tuple(sorted(_vma(x)))
    return lambda shape, dtype: pvary_missing(jnp.zeros(shape, dtype), axes)


#: What makes an expert's hidden rows: ``W2(act(W1 x) * W3 x)`` where the
#: expert has the gate's matrix ``W3``, ``W2 act(W1 x)`` where it has two
#: matrices (``relu2``: the squared ReLU of the two-matrix experts).
ACTIVATIONS = {"silu": nn.silu, "relu": nn.relu,
               "relu2": lambda a: jnp.square(nn.relu(a))}


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _walk(activation, x, gates, w1, w3, w2, plan):
    """``y [N, C]``: the held experts' part of the mixture for tokens ``x``,
    gates ``[N, K]`` and weights in ``x``'s dtype, walked over the filled
    rows of the buffer that ``plan`` lays out (``order`` of the choices
    sorted by held expert, the groups' ``sizes``, their ``start`` in that
    order, their ``first_row`` in the buffer, their ``padded`` sizes).
    ``activation`` names one of ``ACTIVATIONS``: static, and the same in
    the forward walk and in the hidden rows the backward makes again.
    ``w3`` None is a two-matrix expert, ``W2 act(W1 x)``: no gate's matmul
    in either direction and no gradient for it."""
    return _walk_fwd(activation, x, gates, w1, w3, w2, plan)[0]


def _walk_fwd(activation, x, gates, w1, w3, w2, plan):
    act = ACTIVATIONS[activation]
    K = gates.shape[1]
    gate_of = gates.reshape(-1)

    def body(c, y):
        _, choice, token, gs = _chunk(c, plan, K)
        xs = _rows(x, token)
        h = act(lax.ragged_dot(xs, w1, gs))
        if w3 is not None:
            h = h * lax.ragged_dot(xs, w3, gs)
        ys = lax.ragged_dot(h, w2, gs)
        return y.at[token].add(
            ys * _rows(gate_of, choice)[:, None].astype(ys.dtype),
            mode="drop")

    y = lax.fori_loop(0, _trips(plan), body,
                      _zeros_like_of(x)(x.shape, x.dtype))
    return y, (x, gates, w1, w3, w2, plan)


def _walk_bwd(activation, res, dy):
    act = ACTIVATIONS[activation]
    x, gates, w1, w3, w2, plan = res
    N, K = gates.shape
    gate_of = gates.reshape(-1)
    gated = w3 is not None
    wt1, wt3, wt2 = (jnp.swapaxes(w, 1, 2) if w is not None else None
                     for w in (w1, w3, w2))
    f32, zeros = jnp.float32, _zeros_like_of(x)

    def dw_of(a, b, group, acc):
        slab = lax.dynamic_index_in_dim(acc, group, 0, keepdims=False)
        return lax.dynamic_update_index_in_dim(acc, slab + jnp.einsum(
            "ak,an->kn", a, b, preferred_element_type=f32), group, 0)

    def body(c, carry):
        dx, dgate, dw1, dw3, dw2 = carry
        group, choice, token, gs = _chunk(c, plan, K)
        xs, dyr = _rows(x, token), _rows(dy, token)
        gate = _rows(gate_of, choice)[:, None]
        if gated:
            h, pull = jax.vjp(lambda a, b: act(a) * b,
                              lax.ragged_dot(xs, w1, gs),
                              lax.ragged_dot(xs, w3, gs))
        else:
            h, pull = jax.vjp(act, lax.ragged_dot(xs, w1, gs))
        dhu = lax.ragged_dot(dyr, wt2, gs)        # dy W2^T, not yet gated
        dh1, *dh3 = pull(dhu * gate.astype(dhu.dtype))
        dxs = lax.ragged_dot(dh1, wt1, gs)
        if gated:
            dxs = dxs + lax.ragged_dot(dh3[0], wt3, gs)
        return (dx.at[token].add(dxs, mode="drop"),
                dgate.at[choice].add(
                    jnp.sum((h * dhu).astype(f32), axis=-1), mode="drop"),
                dw_of(xs, dh1, group, dw1),
                dw_of(xs, dh3[0], group, dw3) if gated else None,
                dw_of(h, dyr * gate.astype(dyr.dtype), group, dw2))

    dx, dgate, dw1, dw3, dw2 = lax.fori_loop(
        0, _trips(plan), body,
        (zeros(x.shape, x.dtype), zeros((N * K,), f32),
         *(zeros(w.shape, f32) if w is not None else None
           for w in (w1, w3, w2))))
    # The weights came in the activations' dtype and their gradients leave
    # in it, rounded once from the float32 sums as the grouped matmul's own
    # transposes round theirs. One barrier with dx, which the layer below
    # waits for: the compiler otherwise keeps every layer's float32
    # accumulators to the optimizer (1.2 to 1.8 GB more in the two cells).
    return (*lax.optimization_barrier((
        dx, dgate.reshape(N, K).astype(gates.dtype),
        dw1.astype(w1.dtype), dw3.astype(w3.dtype) if gated else None,
        dw2.astype(w2.dtype))), None)


_walk.defvjp(_walk_fwd, _walk_bwd)


def rows_filled(load, first_expert: int, held: int):
    """Rows of :func:`moe_ffn_dropless`'s buffer that a step fills, from
    its token-choices per GLOBAL expert ``load`` (``MoEAux.load``): every
    held expert's count rounded up to ``GROUP_ALIGN``. The walk takes
    ``ceil(rows_filled / CHUNK_ROWS)`` trips of the ``moe.row_chunks`` it
    could; beside ``moe.rows_grouped`` (the bound) it says how far the
    walk went."""
    mine = jnp.asarray(load)[first_expert:first_expert + held]
    return jnp.sum(-(-mine.astype(jnp.int32) // GROUP_ALIGN) * GROUP_ALIGN)


def router_bias_update(bias, load, *, coeff: float):
    """One step of the balancing rule on a router's selection bias
    ``[E]``, from the step's token-choices per expert ``load`` ``[E]``
    (``MoEAux.load``, summed over the data axes by the caller:
    ``hvd.allreduce(load, op=hvd.Sum)``, so that every rank holds the same
    bias):

        delta = coeff * sign(mean(load) - load);  delta -= mean(delta)
        bias += delta

    An expert that got more than its share is chosen a little less often
    next step, and the biases keep summing to what they summed to. Called
    once a step after the optimizer's update; nothing differentiates
    through it."""
    with jax.named_scope("hvd.router_bias_update"):
        load = lax.stop_gradient(load.astype(jnp.float32))
        delta = coeff * jnp.sign(jnp.mean(load) - load)
        return bias + (delta - jnp.mean(delta)).astype(bias.dtype)


class MoEPlan(NamedTuple):
    """What :func:`moe_route` makes of a router's input and
    :func:`moe_apply` walks another tensor by: every token's ``gates``
    ``[N, K]`` (float32; the router's gradient comes back through them),
    the token-choices' ``order`` ``[N * K]`` sorted by held expert (those
    on an absent expert last), the held groups' ``sizes`` ``[E_held]``, and
    the routing diagnostics (``load`` the token-choices per GLOBAL expert
    ``[E]``). The chosen experts and their scores, ``order`` and ``sizes``
    carry ``PLAN_NAME``."""

    gates: jnp.ndarray
    order: jnp.ndarray
    sizes: jnp.ndarray
    load: jnp.ndarray
    load_balance_loss: jnp.ndarray
    z_loss: jnp.ndarray


def _route(x, router, *, experts_per_token, first_expert, held,
           scoring="softmax", **router_kwargs):
    """``(experts [N, K], gates, order, sizes, lb, z)`` of tokens ``x``
    through ``router [C, E]``, under the caller's scope."""
    from ..monitor.registry import counter

    N, K, E = x.shape[0], int(experts_per_token), router.shape[-1]
    if not 0 <= first_expert <= E - held:
        raise ValueError(f"experts {first_expert}..{first_expert + held} "
                         f"are not among the router's {E}")
    R = rows_grouped(N * K, held)
    counter("moe.experts_held").inc(held)
    counter("moe.rows_grouped").inc(R)
    counter("moe.row_chunks").inc(R // CHUNK_ROWS)
    counter("moe.scoring", kind=scoring).inc()
    # What the plan keeps of the N * K choices where the router spreads them
    # evenly: the held experts' share (a step's own count is sum(sizes)).
    counter("moe.choices_held").inc(N * K * held // E)
    experts, gates, lb, z, _ = moe_router(x, router, topk=K, scoring=scoring,
                                          **router_kwargs)
    local = experts.reshape(-1) - first_expert               # [N*K]
    key = jnp.where((local >= 0) & (local < held), local, held)
    _, order = lax.sort((key, jnp.arange(N * K, dtype=jnp.int32)),
                        num_keys=1)
    sizes = jnp.sum(jax.nn.one_hot(key, held, dtype=jnp.int32), axis=0)
    order, sizes = (checkpoint_name(a, PLAN_NAME) for a in (order, sizes))
    return experts, gates, order, sizes, lb, z


def _load(experts, E: int):
    return jnp.sum(jax.nn.one_hot(experts, E, dtype=jnp.float32),
                   axis=(0, 1))


def _apply(x, gates, order, sizes, params, activation):
    """The two walks over ``x`` by a plan's pieces, under the caller's
    scope."""
    from ..monitor.registry import counter

    if activation not in ACTIVATIONS:
        raise ValueError(f"activation is one of {tuple(ACTIVATIONS)}, got "
                         f"{activation!r}")
    if sizes.shape[0] != params["w1"].shape[0]:
        raise ValueError(f"the plan is laid out for {sizes.shape[0]} held "
                         f"experts, the weights are {params['w1'].shape[0]}")
    counter("moe.activation", kind=activation).inc()
    padded = -(-sizes // GROUP_ALIGN) * GROUP_ALIGN
    plan = (order, sizes, jnp.cumsum(sizes) - sizes,
            jnp.cumsum(padded) - padded, padded)
    names = [n for n in ("w1", "w3", "w2") if n in params]
    x, gates, *ws = _harmonize_vma(x, gates, *(
        params[n].astype(x.dtype) for n in names))
    ws = dict(zip(names, ws))
    y = _walk(activation, x, gates, ws["w1"], ws.get("w3"), ws["w2"], plan)
    return y.astype(x.dtype)


def _no_exchange(ep_axis) -> None:
    if ep_axis is not None and _axis_size(ep_axis) > 1:
        raise NotImplementedError(
            "moe_ffn_dropless: the expert exchange across hvd_ep is not "
            "built for the dropless path (ROADMAP R1); run it with the "
            "experts this chip holds and no ep axis")


def moe_route(router_input, router, *, experts_per_token: int,
              first_expert: int = 0, held: int, ep_axis=None,
              router_logits=None, scoring: str = "softmax", bias=None,
              route_norm: bool = True,
              route_scale: float = 1.0) -> MoEPlan:
    """The routing half of :func:`moe_ffn_dropless`, from a tensor of its
    own: tokens ``router_input [N, C]`` through ``router [C, E]`` (float32
    matmul, :func:`moe_router`'s scoring, the top ``experts_per_token``),
    the token-choices sorted by held expert (``first_expert ..
    first_expert + held``), the groups' sizes and the load, under the scope
    ``hvd.moe_route`` in both directions. A block whose router reads
    another tensor than its experts (its input, before attention) calls
    this where that tensor is and :func:`moe_apply` where the experts'
    rows are; the exchange across ``hvd_ep`` (ROADMAP R1) will hook in
    here, where the counts are known before the rows exist."""
    _no_exchange(ep_axis)
    with jax.named_scope("hvd.moe_route"):
        experts, gates, order, sizes, lb, z = _route(
            router_input, router, experts_per_token=experts_per_token,
            first_expert=first_expert, held=held,
            router_logits=router_logits, scoring=scoring, bias=bias,
            route_norm=route_norm, route_scale=route_scale)
        load = _load(experts, router.shape[-1])
    return MoEPlan(gates, order, sizes, load, lb, z)


def moe_apply(x, plan: MoEPlan, params, *, activation: str = "silu"):
    """The experts' half: ``y [N, C]``, the held experts' part of the
    mixture for tokens ``x [N, C]`` routed as ``plan`` says (made by
    :func:`moe_route` from these tokens or from others of the same rows),
    ``sum_{e held, chosen} gate_e * W2_e(act(W1_e x) * W3_e x)`` with
    ``act`` one of ``ACTIVATIONS`` (``"silu"``, ``"relu"``, ``"relu2"``);
    ``params`` holds ``w1``, ``w3`` ``[E_held, C, F]`` and ``w2``
    ``[E_held, F, C]``, or ``w1`` and ``w2`` alone: a two-matrix expert,
    ``gate_e * W2_e act(W1_e x)``. ``C`` is ``x``'s width, which need not be
    the width the router read (experts that work in a latent are handed the
    latent; the plan knows rows, not widths). The walk of
    :func:`moe_ffn_dropless`, under the scope ``hvd.moe_ffn`` in both
    directions."""
    with jax.named_scope("hvd.moe_ffn"):
        return _apply(x, plan.gates, plan.order, plan.sizes, params,
                      activation)


def moe_ffn_dropless(x, params, *, experts_per_token: int,
                     first_expert: int = 0, ep_axis=None,
                     router_logits=None, scoring: str = "softmax",
                     bias=None, route_norm: bool = True,
                     route_scale: float = 1.0, activation: str = "silu"):
    """Top-k gated experts over tokens ``x [N, C]`` with no capacity: no
    token-choice is ever dropped. :func:`moe_route` and then
    :func:`moe_apply` on the one tensor, both under the scope
    ``hvd.moe_ffn``. The layer is told which experts it
    holds: ``params["router"]`` is ``[C, E]`` over ALL the experts (the
    published width), ``w1``, ``w3`` ``[E_held, C, F]`` and ``w2``
    ``[E_held, F, C]`` are experts ``first_expert .. first_expert +
    E_held``. Every token is routed over all E (softmax, top
    ``experts_per_token``, gates renormalised to sum one); the result is
    the held experts' part, ``sum_{e held, chosen} gate_e * W2_e(act(W1_e
    x) * W3_e x)``, ``act`` the ``activation`` (one of ``ACTIVATIONS``;
    without ``w3`` in ``params`` the experts have two matrices,
    ``W2_e act(W1_e x)``); what the absent experts would add is left out
    (it is another chip's to add). Returns ``(y [N, C], MoEAux)`` with ``load``
    the token-choices per GLOBAL expert and ``dropped_fraction`` 0.
    ``scoring``, ``bias``, ``route_norm`` and ``route_scale`` are
    :func:`moe_router`'s: sigmoid scores, a selection bias carried as
    state, gates scaled; the defaults are the softmax router above. A
    shared expert is the caller's, beside this call and outside its scope.

    The N*K token-choices are sorted by held expert and laid into a row
    buffer in which every held expert's group starts on a multiple of
    ``GROUP_ALIGN``: dead rows lie only at the end of a group's last tile
    and past ``rows_filled = sum(padded)``. The buffer is bounded by ``R =
    N*K`` rows and a tile a group (``moe.rows_grouped``), the most the held
    experts can be sent, and is never made: the FILLED rows are walked in
    chunks of ``CHUNK_ROWS`` by a loop whose trips, ``ceil(rows_filled /
    CHUNK_ROWS)``, are a value of the step (at most ``moe.row_chunks``). A
    trip gathers its rows' tokens, runs the three ``lax.ragged_dot`` with
    the chunk's own group sizes, weighs by the gates and adds the rows to
    their tokens. So one program takes any routing with no branch and no
    threshold, nothing is dropped, and the layer's time grows with the rows
    the step filled in steps of one chunk: a quarter of what computing
    every tile of the bound cost at uniform load, more than it only above
    about five times that (PERF.md, PR 31; :func:`rows_filled` reads how
    far a step's walk went from ``MoEAux.load``). A loop with a traced trip
    count has no reverse rule: the walk is a ``custom_vjp`` whose backward
    is the same walk (the hidden rows made again, ``dx`` added to its
    tokens, the gates' gradient, the weight gradients summed in float32 a
    tile's expert at a time), under the scope ``hvd.moe_ffn`` in both
    directions. What lays the walk out (the chosen experts, their scores,
    ``order``, ``sizes``: :func:`plan_bytes`) carries the
    ``checkpoint_name`` ``PLAN_NAME``, for a rematerialised caller to keep
    in place of a second top-k and sort; the result ``y`` carries none (a
    caller whose backward reads it names it itself:
    ``models/sparse_moe_decoder.py``). Without ``ep_axis`` bound nothing is
    exchanged; the exchange across ``hvd_ep`` is built for neither half
    (ROADMAP R1: :func:`moe_route` is where it will hook in)."""
    _no_exchange(ep_axis)
    held = params["w1"].shape[0]
    with jax.named_scope("hvd.moe_ffn"):
        experts, gates, order, sizes, lb, z = _route(
            x, params["router"], experts_per_token=experts_per_token,
            first_expert=first_expert, held=held,
            router_logits=router_logits, scoring=scoring, bias=bias,
            route_norm=route_norm, route_scale=route_scale)
        y = _apply(x, gates, order, sizes, params, activation)
    # The load after the walk, where it always stood: the callers' traces,
    # and with them their compiled programs, are what they were.
    return y, MoEAux(
        load_balance_loss=lb, z_loss=z,
        load=_load(experts, params["router"].shape[-1]),
        dropped_fraction=jnp.zeros((), jnp.float32))


# ---------------------------------------------------------------------------
# The flax module.
# ---------------------------------------------------------------------------


class MoELayer(nn.Module):
    """Top-k gated MoE FFN (docs/moe.md) — the drop-in for a dense MLP
    block, expert-parallel over the dedicated ``hvd_ep`` axis.

    ``num_experts`` is GLOBAL; with ``ep_axis`` bound inside shard_map
    each rank creates only its ``num_experts / ep`` experts' weights
    (the router is replicated). Sows ``moe_aux_loss`` / ``moe_z_loss``
    / ``moe_expert_load`` / ``moe_dropped_frac`` into
    ``intermediates``; callers add ``aux_weight · aux + z_weight · z``
    to the task loss. ``quantized`` rides the dispatch/combine wire
    blockwise-int8 (error feedback needs the functional
    :func:`moe_ffn` — the flax layer is stateless)."""

    num_experts: int
    d_ff: int
    topk: int = 2
    capacity_factor: float = 1.25
    ep_axis: Optional[str] = None
    quantized: bool = False
    quant_block: int = 256
    dtype: jnp.dtype = jnp.float32
    kernel_init_std: float = 0.02

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        ep = _axis_size(self.ep_axis) if self.ep_axis else 1
        if self.num_experts % ep:
            raise ValueError(
                f"num_experts {self.num_experts} not divisible by "
                f"ep axis size {ep}")
        e_local = self.num_experts // ep
        init = nn.initializers.normal(self.kernel_init_std)
        params = {
            "router": self.param("router", init,
                                 (C, self.num_experts), jnp.float32),
            "w1": self.param("w1", init, (e_local, C, self.d_ff),
                             jnp.float32).astype(self.dtype),
            "b1": self.param("b1", nn.initializers.zeros,
                             (e_local, self.d_ff),
                             jnp.float32).astype(self.dtype),
            "w2": self.param("w2", init, (e_local, self.d_ff, C),
                             jnp.float32).astype(self.dtype),
            "b2": self.param("b2", nn.initializers.zeros, (e_local, C),
                             jnp.float32).astype(self.dtype),
        }
        plan = None
        if ep > 1:
            plan = default_a2a_plan(self.ep_axis,
                                    quantized=self.quantized,
                                    block=self.quant_block,
                                    error_feedback=False)
        y, aux, _ = moe_ffn(x.reshape(B * T, C), params,
                            topk=self.topk,
                            capacity_factor=self.capacity_factor,
                            ep_axis=self.ep_axis, a2a_plan=plan)
        self.sow("intermediates", "moe_aux_loss", aux.load_balance_loss)
        self.sow("intermediates", "moe_z_loss", aux.z_loss)
        self.sow("intermediates", "moe_expert_load", aux.load)
        self.sow("intermediates", "moe_dropped_frac",
                 aux.dropped_fraction)
        return y.reshape(B, T, C)


# ---------------------------------------------------------------------------
# Parameter/gradient plumbing for the hvd_ep mesh.
# ---------------------------------------------------------------------------

#: Leaf names of the expert-sharded half of an MoE params dict.
EXPERT_LEAVES = ("w1", "b1", "w2", "b2")


def ep_param_pspecs(params, ep_axis: str = EP_AXIS):
    """PartitionSpecs for a stacked MoE params tree: expert leaves
    (leading ``[ep, E_local, ...]`` dim) shard over ``ep_axis``,
    everything else (router, dense trunk) replicates."""
    from jax.sharding import PartitionSpec as P

    def spec(path, _leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        return P(ep_axis) if name in EXPERT_LEAVES else P()

    return jax.tree_util.tree_map_with_path(spec, params)


def ep_stack_params(params, ep: int):
    """Split a dense (world-1) MoE params dict into the ``[ep, ...]``
    stacked form ``ep_param_pspecs`` shards: expert leaves split their
    leading expert dim into ``ep`` groups; replicated leaves stay."""
    def split(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in EXPERT_LEAVES:
            E = leaf.shape[0]
            if E % ep:
                raise ValueError(
                    f"expert dim {E} of {name!r} not divisible by "
                    f"ep={ep}")
            return leaf.reshape((ep, E // ep) + leaf.shape[1:])
        return leaf

    return jax.tree_util.tree_map_with_path(split, params)


def ep_mean_dense_grads(grads, ep_axis: str = EP_AXIS,
                        expert_leaves=EXPERT_LEAVES):
    """Normalize a local gradient tree to the GLOBAL-MEAN gradient's ep
    share, ready for the data-axis reduction machinery (docs/moe.md).

    With the batch sharded over ``(hvd_ep, cross, local)`` and the loss
    a global token mean:

    * replicated parameters (router, dense trunk) receive a DIFFERENT
      gradient per ep rank (each saw a different token shard) — they
      take the explicit ``pmean`` over ``hvd_ep``;
    * expert leaves are NEVER averaged across groups (that would mix
      different experts' gradients — the isolation contract the
      dedicated axis exists for). But the owner's autodiff gradient
      already SUMS the contributions every ep source routed to it
      (the combine exchange's backward delivers them), so the
      global-mean normalization is the ``1/ep`` scale, applied locally
      with zero wire.

    After this, a plain ``op=Average`` reduction over the data axes
    (``DistributedOptimizer`` / ``allreduce_pytree``) yields exactly the
    global-mean gradient for every leaf."""
    ep = _axis_size(ep_axis)

    def norm(path, g):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        if name in expert_leaves:
            return g / float(ep)
        return lax.pmean(g, ep_axis)

    return jax.tree_util.tree_map_with_path(norm, grads)
