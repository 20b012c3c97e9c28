"""Operations a decoder with sparse attention and routed experts REQUIRES
per trained token, on the share of the model one chip holds.

Only ``mfu_pct`` reads it. Counted, forward and backward (2 FLOPs a weight
forward, 4 backward), nothing a tiling recomputes, nothing computed under a
mask, nothing rematerialised:

* 6 x the matmul weights a token passes through in a layer: q, k, v and
  output projections, the router, and the experts it is routed to AMONG
  THOSE HELD HERE: ``top_k * held / experts`` experts of three
  ``d x d_expert`` matrices on average (routing is near uniform at seeded
  weights; the absent experts' work is another chip's);
* the indexer, forward only (no gradient reaches it): 2 x its three
  projections, and its causal scores, ``2 * Hi * Di`` a pair over
  ``(T + 1) / 2`` pairs a token;
* attention over the SELECTED pairs only: QK^T and PV are ``4 * H * D`` a
  pair forward, three times that with the backward, over
  ``mean_t min(t + 1, topk)`` pairs a token;
* 6 x the untied head's ``vocab x d`` (the sliced vocabulary).

Norms, rotary embedding, softmax, SiLU, top-k, the sort of token-choices
and the embedding gather are not matmuls and are left out.
"""

from __future__ import annotations

from benchmarks.lib.kernels_sparse import selected_pairs


def layer_matmul_weights(s: dict) -> float:
    """Weights a token multiplies in one layer, gradients flowing."""
    d, D = s["d_model"], s["head_dim"]
    attention = d * D * (2 * s["heads"] + 2 * s["kv_heads"])
    router = d * s["experts"]
    experts = (s["top_k"] * s["experts_held"] / s["experts"]
               * 3 * d * s["d_expert"])
    return attention + router + experts


def indexer_weights(s: dict) -> int:
    return s["d_model"] * (s["idx_heads"] * s["idx_dim"] + s["idx_dim"]
                           + s["idx_heads"])


def mean_selected(seq_len: int, topk: int) -> float:
    """mean_t min(t + 1, topk) over t = 0..seq_len-1."""
    return selected_pairs(seq_len, topk) / seq_len


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """``s`` as lib/reference_sparse_moe.py ``sizes_from_config`` gives it."""
    layer = (6 * layer_matmul_weights(s)
             + 2 * indexer_weights(s)
             + 2 * s["idx_heads"] * s["idx_dim"] * (seq_len + 1) / 2
             + 12 * s["heads"] * s["head_dim"]
             * mean_selected(seq_len, s["idx_topk"]))
    return s["layers"] * layer + 6 * s["vocab"] * s["d_model"]
