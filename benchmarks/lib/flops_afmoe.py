"""Operations an ``afmoe`` decoder (window and full grouped-KV attention, a
dense gated MLP or sigmoid-routed experts beside a shared one) REQUIRES per
trained token, on the share of the model one chip holds.

Only ``mfu_pct`` reads it. Counted, forward and backward (2 FLOPs a weight
forward, 4 backward), nothing a tiling recomputes, nothing computed under a
mask, nothing rematerialised:

* 6 x the matmul weights a token passes through in a layer: the q, k, v,
  gate and output projections; on a dense layer the three ``d x d_ff``
  matrices; on a routed layer the router, the shared expert's three
  ``d x d_expert`` and the experts it is routed to AMONG THOSE HELD HERE:
  ``top_k * held / experts`` experts of three ``d x d_expert`` matrices on
  average (the balancing bias keeps the routing near uniform; the absent
  experts' work is another chip's);
* attention over the VISIBLE pairs only: QK^T and PV are ``4 * H * D`` a
  pair forward, three times that with the backward, over
  ``mean_t min(t + 1, window)`` pairs a token on a sliding layer and
  ``(T + 1) / 2`` on a full one;
* 6 x the untied head's ``vocab x d`` (the sliced vocabulary).

Norms, rotary embedding, softmax, SiLU, sigmoid, the output gate's product,
top-k, the sort of token-choices, the bias update and the embedding gather
are not matmuls and are left out.
"""

from __future__ import annotations

from benchmarks.lib.kernels_window import visible_pairs

SLIDING = "sliding_attention"


def attention_weights(s: dict) -> float:
    """q, gate and output projections over ``heads``, k and v over
    ``kv_heads``."""
    return s["d_model"] * s["head_dim"] * (3 * s["heads"] + 2 * s["kv_heads"])


def mlp_weights(s: dict, layer: int) -> float:
    """Weights a token multiplies in layer ``layer``'s MLP."""
    d = s["d_model"]
    if layer < s["dense_layers"]:
        return 3 * d * s["d_ff"]
    routed = s["top_k"] * s["experts_held"] / s["experts"]
    return d * s["experts"] + (routed + s["shared"]) * 3 * d * s["d_expert"]


def mean_visible(s: dict, layer: int, seq_len: int) -> float:
    """Pairs a token's query attends in layer ``layer``, a head."""
    window = s["window"] if s["layer_types"][layer] == SLIDING else None
    return visible_pairs(seq_len, window) / seq_len


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """``s`` as lib/reference_afmoe.py ``sizes_from_config`` gives it."""
    total = 6 * s["vocab"] * s["d_model"]
    for i in range(s["layers"]):
        total += (6 * (attention_weights(s) + mlp_weights(s, i))
                  + 12 * s["heads"] * s["head_dim"]
                  * mean_visible(s, i, seq_len))
    return total
