"""The block-diffusion training objective (BD3-LM, Arriola et al., ICLR
2025; the objective SDAR trains with): the noise a step puts on its
sequences and the loss over what it masked. The mask the layers attend
under is :func:`hvd.flash_attention`'s ``block_diffusion=``
(docs/block_diffusion.md has the whole of it).

A sequence ``x0 [L]`` in ``n = L / B`` blocks of ``B`` positions,
``blk(i) = i // B``, mask id ``M``:

1. **Noise** (:func:`block_diffusion_noise`, scope
   ``hvd.block_diffusion_noise``): a block draws ``u_b ~ U[0, 1)`` and
   ``t_b = eps + (1 - eps) u_b``; a position draws ``v_i ~ U[0, 1)``;
   ``masked_i = v_i < t_blk(i)``; ``xt_i = M if masked_i else x0_i``. Row
   ``r`` of the global batch draws from ``fold_in(key, r)`` split in two
   (``u`` from the first half, ``v`` from the second), so that a rank's
   rows are drawn alike wherever they lie and a reference can draw them
   again (threefry gives the same bits on every backend).
2. **Rows**: ``[xt ; x0]``, ``2 L`` of them a sequence, both halves at
   positions ``0 .. L - 1``; every layer runs on all of them.
3. **Loss** (:func:`block_diffusion_loss`): the head on the noised half
   alone, no shift (the logits at position ``i`` predict ``x0_i``):
   ``(1 / L) sum_{i masked} CE(logits_i, x0_i) / t_blk(i)``, the mean over
   sequences, in float32. ``1 / t`` is the weight of the linear schedule.

Trace-time counters: ``block_diffusion.rows{half=noised|clean}``, the rows
of each half a compiled step holds.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from .softmax_xent import lm_head_loss

EPS = 1e-3   # the least noise level: 1 / t stays under 1000


class Noised(NamedTuple):
    rows: jax.Array     # [B, 2 L] int32: the noised copy, then the clean one
    masked: jax.Array   # [B, L] bool: the positions the loss runs over
    t: jax.Array        # [B, L] float32: a position's block's noise level


def block_diffusion_noise(tokens, key, *, block_length: int, mask_id: int,
                          first_row=0) -> Noised:
    """Noise ``tokens [B, L]`` (``L`` a whole number of blocks) from
    ``key``; ``first_row`` is where these rows stand in the global batch
    (a rank's ``rank * B`` inside the step's ``shard_map``; may be
    traced)."""
    from ..monitor.registry import counter

    B, L = tokens.shape
    if L % block_length:
        raise ValueError(f"{L} positions are no whole number of blocks of "
                         f"{block_length}")
    for half in ("noised", "clean"):
        counter("block_diffusion.rows", half=half).inc(B * L)

    def one(row):
        ku, kv = jax.random.split(jax.random.fold_in(key, row))
        u = jax.random.uniform(ku, (L // block_length,), jnp.float32)
        return (EPS + (1.0 - EPS) * u,
                jax.random.uniform(kv, (L,), jnp.float32))

    with jax.named_scope("hvd.block_diffusion_noise"):
        t_block, v = jax.vmap(one)(first_row + jnp.arange(B))
        t = jnp.repeat(t_block, block_length, axis=1)
        masked = v < t
        noised = jnp.where(masked, jnp.asarray(mask_id, tokens.dtype),
                           tokens)
        return Noised(jnp.concatenate([noised, tokens], axis=1), masked, t)


def block_diffusion_loss(h, head, tokens, masked, t):
    """The objective's loss from the noised half's final hidden states
    ``h [B, L, d]``, the untied head ``[vocab, d]`` and the clean
    ``tokens [B, L]``: a scalar, the mean over the ``B`` sequences.
    Through :func:`hvd.lm_head_loss` (its per-token losses times
    ``masked / t``), so whatever reads that scope reads this head."""
    per_token = lm_head_loss(h, head, tokens, mode="auto")
    with jax.named_scope("hvd.lm_head_loss"):
        weight = jnp.where(masked, 1.0 / t, 0.0)
        return jnp.mean(jnp.sum(per_token.astype(jnp.float32) * weight,
                                axis=-1)) / tokens.shape[1]
