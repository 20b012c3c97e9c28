"""Expert parallelism: Switch-style Mixture-of-Experts with all-to-all.

The reference framework has no MoE (CNN-era, SURVEY §2.7), but its
``alltoall`` collective is exactly the EP dispatch primitive — this module
is the TPU-native layer built on it. Top-1 (Switch) routing with a fixed
per-expert capacity, compiled entirely into the XLA program:

1. route: ``softmax(x @ router)`` → argmax expert + gate probability;
2. dispatch: scatter tokens into a static ``[E, capacity, C]`` buffer
   (position = running count within the chosen expert; overflow tokens
   are dropped — they ride the residual connection, standard Switch
   behavior);
3. exchange: one tiled ``lax.all_to_all`` re-shards the buffer from
   expert-major [E, cap, C] to ``[E/n, n·cap, C]`` — each rank receives
   every rank's tokens for ITS experts (the reference's MPI_Alltoallv
   analogue, riding ICI);
4. expert FFN: batched einsum over the local experts' weights;
5. exchange back + combine: tokens return to their source rank and are
   scaled by the gate (straight-through for the router's gradient).

The load-balancing auxiliary loss (Switch eq. 4: E · Σ_e f_e · P_e) is
returned alongside; callers add ``aux_weight * aux`` to the task loss.

Everything is static-shaped; outside ``shard_map`` (or with a 1-sized
axis) the same code runs with all experts local and no collective.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .sequence import _axis_size


def _route(x, router_kernel, E):
    """Shared Switch top-1 routing: returns ``(expert, gate, aux,
    onehot)`` — argmax expert id [N], gate probability [N], the
    load-balancing aux loss (Switch eq. 4: E · Σ_e f_e · P_e), and the
    int32 [N, E] expert one-hot (built once; callers reuse it)."""
    probs = jax.nn.softmax(
        jnp.einsum("nc,ce->ne", x.astype(jnp.float32),
                   router_kernel.astype(jnp.float32)), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                    # [N]
    gate = jnp.take_along_axis(probs, expert[:, None], axis=-1)[:, 0]
    onehot = jax.nn.one_hot(expert, E, dtype=jnp.int32)    # [N, E]
    frac = jnp.mean(onehot.astype(jnp.float32), axis=0)
    aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))
    return expert, gate, aux, onehot


def _check_experts(router_kernel, E_local, n):
    E = E_local * n
    if router_kernel.shape[-1] != E:
        raise ValueError(
            f"router has {router_kernel.shape[-1]} experts but "
            f"E_local {E_local} x axis size {n} = {E}")
    return E


def switch_moe(x, router_kernel, w1, b1, w2, b2, *,
               axis: Optional[str] = None,
               capacity_factor: float = 1.25):
    """Top-1 MoE on flattened tokens ``x`` [N, C].

    ``router_kernel``: [C, E_global]; expert weights carry the LOCAL
    expert dim: ``w1`` [E_local, C, F], ``b1`` [E_local, F], ``w2``
    [E_local, F, C], ``b2`` [E_local, C]. ``E_global = E_local · n``
    where n is the bound size of ``axis``. Returns ``(y [N, C], aux)``.
    """
    N, C = x.shape
    n = _axis_size(axis) if axis else 1
    E = _check_experts(router_kernel, w1.shape[0], n)
    # Per-expert capacity: every rank contributes N tokens to E experts.
    capacity = max(1, int(N * capacity_factor / E + 0.9999))

    expert, gate, aux, onehot = _route(x, router_kernel, E)

    # Position of each token within its expert's queue.
    pos = jnp.sum(jnp.cumsum(onehot, axis=0) * onehot, axis=-1) - 1
    keep = pos < capacity                                  # overflow drop
    pos_c = jnp.minimum(pos, capacity - 1)

    dispatch = jnp.zeros((E, capacity, C), x.dtype).at[expert, pos_c].add(
        jnp.where(keep[:, None], x, 0))

    if n > 1:
        # [E, cap, C] → [E_local, n·cap, C]: rank r keeps/receives every
        # rank's buffer rows for ITS local experts.
        recv = lax.all_to_all(dispatch, axis, split_axis=0, concat_axis=1,
                              tiled=True)
    else:
        recv = dispatch                                    # all local

    h = jnp.einsum("ekc,ecf->ekf", recv, w1) + b1[:, None]
    h = nn.gelu(h)
    out = jnp.einsum("ekf,efc->ekc", h, w2) + b2[:, None]

    if n > 1:
        out = lax.all_to_all(out, axis, split_axis=1, concat_axis=0,
                             tiled=True)                   # back home

    y = out[expert, pos_c]                                 # [N, C]
    y = jnp.where(keep[:, None], y, 0) * gate[:, None].astype(y.dtype)
    return y.astype(x.dtype), aux


def switch_moe_ragged(x, router_kernel, w1, b1, w2, b2, *,
                      axis: Optional[str] = None,
                      capacity_factor: float = 1.25,
                      pair_capacity_factor: float = 2.0):
    """Top-1 MoE with *ragged* all-to-all dispatch (uneven per-rank
    splits, reference: MPI_Alltoallv path, operations.cc:1031-1092).

    Same signature/returns as :func:`switch_moe`, different dispatch
    protocol.  Instead of a fixed ``[E, capacity, C]`` buffer where each
    (sender, expert) pair has a hard quota, tokens are sorted by
    destination *rank* and exchanged with
    :func:`~horovod_tpu.ops.collective_ops.alltoall_ragged`; the
    receiver then pools each local expert's capacity across ALL senders.
    Drops now happen only when

    * a single (sender → rank) pair exceeds
      ``pair_capacity_factor * N / n`` rows (gross rank-level skew), or
    * one expert *globally* exceeds ``capacity_factor * N * n / E``
      rows (the same total as :func:`switch_moe`, but pooled instead of
      per-sender),

    which is strictly laxer than the fixed path's per-(sender, expert)
    quota (whose capacity-overflow cliff it avoids).  Dropped
    tokens still emit zeros and ride the residual.
    """
    N, C = x.shape
    n = _axis_size(axis) if axis else 1
    E_local = w1.shape[0]
    E = _check_experts(router_kernel, E_local, n)
    # Pooled per-local-expert capacity: global token count over global
    # expert count, same total buffer bytes as the fixed path.
    local_cap = max(1, int(N * n * capacity_factor / E + 0.9999))

    expert, gate, aux, _ = _route(x, router_kernel, E)

    dest = (expert // E_local).astype(jnp.int32)           # owning rank
    e_loc = (expert % E_local).astype(jnp.int32)
    order = jnp.argsort(dest, stable=True)                 # dest-major
    xs, es, blk = x[order], e_loc[order], dest[order]
    splits = jnp.sum(jax.nn.one_hot(dest, n, dtype=jnp.int32), axis=0)

    if n > 1:
        pair_cap = max(1, min(N, int(N * pair_capacity_factor / n
                                     + 0.9999)))
        from ..ops.collective_ops import alltoall_ragged
        recv_x, recv_splits = alltoall_ragged(
            xs, splits, capacity=pair_cap, axes=axis)
        # Same splits as the x exchange: reuse its negotiated counts.
        recv_e, _ = alltoall_ragged(es, splits, capacity=pair_cap,
                                    axes=axis, recv_splits=recv_splits)
    else:
        pair_cap = N
        recv_x, recv_splits, recv_e = xs, splits, es

    R = recv_x.shape[0]                                    # n * pair_cap
    rvalid = jnp.arange(R) < jnp.sum(recv_splits)          # compacted
    re = jnp.where(rvalid, recv_e, 0)

    # Running position within each local expert's pooled queue.
    oh = jax.nn.one_hot(re, E_local, dtype=jnp.int32) * \
        rvalid[:, None].astype(jnp.int32)
    pos = jnp.sum(jnp.cumsum(oh, axis=0) * oh, axis=-1) - 1
    keep = rvalid & (pos >= 0) & (pos < local_cap)
    pos_c = jnp.clip(pos, 0, local_cap - 1)

    buf = jnp.zeros((E_local, local_cap, C), x.dtype).at[re, pos_c].add(
        jnp.where(keep[:, None], recv_x, 0))

    h = jnp.einsum("ekc,ecf->ekf", buf, w1) + b1[:, None]
    h = nn.gelu(h)
    out = jnp.einsum("ekf,efc->ekc", h, w2) + b2[:, None]

    # Back to the received-row order (dropped rows -> zeros), then home.
    rows_out = out[re, pos_c] * keep[:, None].astype(out.dtype)
    sp_c = jnp.minimum(splits, pair_cap)
    if n > 1:
        # Return-trip recv counts are our own clamped sends — no
        # negotiation needed.
        back, _ = alltoall_ragged(rows_out, recv_splits, capacity=pair_cap,
                                  axes=axis, recv_splits=sp_c)
    else:
        back = rows_out

    # Sorted-token -> compact return position: block r of the return
    # buffer holds min(splits[r], pair_cap) rows in send order.
    boffs = jnp.cumsum(sp_c) - sp_c
    offs = jnp.cumsum(splits) - splits
    p = jnp.arange(N)
    p_in = p - offs[blk]
    sent = p_in < pair_cap
    cpos = jnp.where(sent, boffs[blk] + p_in, 0)
    y_sorted = jnp.where(sent[:, None], back[cpos], 0)
    inv = jnp.argsort(order)
    y = y_sorted[inv] * gate[:, None].astype(y_sorted.dtype)
    return y.astype(x.dtype), aux


class SwitchMoE(nn.Module):
    """Flax module: Switch-MoE FFN (drop-in for a dense MLP block).

    ``num_experts`` is GLOBAL; with ``ep_axis`` bound inside shard_map
    each rank creates only its ``num_experts / n`` experts' weights (the
    router is replicated). See ``ep_split_params`` for slicing a dense
    (world-1) checkpoint into per-rank shards.
    """

    num_experts: int
    d_ff: int
    capacity_factor: float = 1.25
    ep_axis: Optional[str] = None
    dtype: jnp.dtype = jnp.float32
    kernel_init_std: float = 0.02
    # Ragged (uneven alltoall) dispatch: pools expert capacity across
    # senders, removing the per-(sender, expert) overflow cliff.
    # pair_capacity_factor bounds the (sender -> rank) block at
    # pair_capacity_factor * N / n rows (ragged path only).
    ragged: bool = False
    pair_capacity_factor: float = 2.0

    @nn.compact
    def __call__(self, x):
        B, T, C = x.shape
        n = _axis_size(self.ep_axis) if self.ep_axis else 1
        if self.num_experts % n:
            raise ValueError(
                f"num_experts {self.num_experts} not divisible by "
                f"ep axis size {n}")
        e_local = self.num_experts // n
        init = nn.initializers.normal(self.kernel_init_std)
        router = self.param("router", init, (C, self.num_experts),
                            jnp.float32)
        w1 = self.param("w1", init, (e_local, C, self.d_ff), jnp.float32)
        b1 = self.param("b1", nn.initializers.zeros, (e_local, self.d_ff),
                        jnp.float32)
        w2 = self.param("w2", init, (e_local, self.d_ff, C), jnp.float32)
        b2 = self.param("b2", nn.initializers.zeros, (e_local, C),
                        jnp.float32)
        kw = {}
        if self.ragged:
            moe_fn = switch_moe_ragged
            kw["pair_capacity_factor"] = self.pair_capacity_factor
        else:
            moe_fn = switch_moe
        y, aux = moe_fn(
            x.reshape(B * T, C),
            router, w1.astype(self.dtype), b1.astype(self.dtype),
            w2.astype(self.dtype), b2.astype(self.dtype),
            axis=self.ep_axis, capacity_factor=self.capacity_factor, **kw)
        self.sow("intermediates", "moe_aux_loss", aux)
        return y.reshape(B, T, C)


def _ep_rule(path: str):
    """Expert weights live under a SwitchMoE module ('moe' in GPT blocks)
    — anchor on the module name so unrelated params that happen to be
    called w1/b1/w2/b2 elsewhere are never mis-sharded."""
    mod, _, leaf = path.rpartition("/")
    if leaf in ("w1", "b1", "w2", "b2") and mod.split("/")[-1] == "moe":
        return lambda a, n, i: jnp.split(a, n, axis=0)[i]
    return None


def ep_split_params(params, n: int):
    """Dense (world-1) SwitchMoE params → (sharded, replicated) trees,
    same contract as :func:`horovod_tpu.parallel.tensor.tp_split_params`:
    expert weights (leading expert dim) are stacked per-rank shards, the
    router (and everything else) stays in the replicated tree."""
    from .tensor import split_params_by_rule

    return split_params_by_rule(params, n, _ep_rule)
