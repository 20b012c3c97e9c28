"""Operations a SmallThinker decoder (a NoPE full layer and sliding layers
at 7 query heads a KV head, ReLU-gated routed experts on every layer, no
shared expert, no dense layer) REQUIRES per trained token, on the share of
the model one chip holds.

Only ``mfu_pct`` reads it. Counted by ``lib/flops_afmoe.py``'s convention,
forward and backward (2 FLOPs a weight forward, 4 backward), nothing a
tiling recomputes, nothing computed under a mask, nothing rematerialised:

* 6 x the matmul weights a token passes through in a layer: the q, k, v and
  output projections, the router's ``d x experts``, and the experts it is
  routed to AMONG THOSE HELD HERE: ``top_k * held / experts`` experts of
  three ``d x d_expert`` matrices on average (the absent experts' work is
  another chip's);
* attention over the VISIBLE pairs only: QK^T and PV are ``4 * H * D`` a
  pair forward, three times that with the backward, over
  ``mean_t min(t + 1, window)`` pairs a token on a sliding layer and
  ``(T + 1) / 2`` on a full one (``lib/kernels_window.py``'s pairs, so that
  ``mfu_pct`` and the rooflines count the same ones);
* 6 x the untied head's ``vocab x d`` (the sliced vocabulary).

Norms, rotary embedding, softmax, ReLU, the gates' product, top-k, the sort
of token-choices and the embedding gather are not matmuls and are left out.
"""

from __future__ import annotations

from benchmarks.lib.kernels_window import visible_pairs


def attention_weights(s: dict) -> int:
    """q and output projections over ``heads``, k and v over ``kv_heads``."""
    return s["d_model"] * s["head_dim"] * 2 * (s["heads"] + s["kv_heads"])


def expert_weights(s: dict) -> int:
    """One expert's three matrices."""
    return 3 * s["d_model"] * s["d_expert"]


def layer_matmul_weights(s: dict) -> float:
    """Weights a token multiplies in a layer, the router among them."""
    routed = s["top_k"] * s["experts_held"] / s["experts"]
    return (attention_weights(s) + s["d_model"] * s["experts"]
            + routed * expert_weights(s))


def mean_visible(s: dict, layer: int, seq_len: int) -> float:
    """Pairs a token's query attends in layer ``layer``, a head."""
    window = s["window"] if s["sliding"][layer] else None
    return visible_pairs(seq_len, window) / seq_len


def parameter_count(s: dict) -> int:
    """Parameters the chip holds: what 16 bytes each are reckoned on."""
    d = s["d_model"]
    layer = (attention_weights(s) + d * s["experts"]
             + s["experts_held"] * expert_weights(s) + 2 * d)
    return s["layers"] * layer + 2 * s["vocab"] * d + d


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """``s`` as lib/reference_smallthinker.py ``sizes_from_config`` gives
    it."""
    total = 6 * s["vocab"] * s["d_model"]
    for i in range(s["layers"]):
        total += (6 * layer_matmul_weights(s)
                  + 12 * s["heads"] * s["head_dim"]
                  * mean_visible(s, i, seq_len))
    return total
