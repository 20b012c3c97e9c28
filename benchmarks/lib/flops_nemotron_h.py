"""Operations a ``nemotron_h`` hybrid decoder (one sublayer a layer: a
Mamba-2 mixer, grouped-KV attention, or sigmoid-routed two-matrix experts
in a latent beside a full-width shared expert) REQUIRES per trained token,
on the share of the model one chip holds.

Only ``mfu_pct`` reads it. Counted, forward and backward (2 FLOPs a weight
forward, 4 backward), nothing a tiling recomputes, nothing computed under a
mask, nothing rematerialised:

* 6 x the matmul weights a token passes through in a layer: a Mamba
  layer's in- and out-projection; an attention layer's q, k, v and output
  projections; an expert layer's router (over ALL the experts), the two
  latent projections, the shared expert's two ``d x d_shared`` matrices and
  the experts it is routed to AMONG THOSE HELD HERE: ``top_k * held /
  experts`` experts of two ``latent x d_expert`` matrices on average (the
  balancing bias keeps the routing near uniform; the absent experts' work
  is another chip's);
* attention over the VISIBLE pairs only: QK^T and PV are ``4 * H * D`` a
  pair forward, three times that with the backward, over ``(T + 1) / 2``
  pairs a token (causal, no window);
* the chunked scan's matmuls (``lib/kernels_ssd.py``: the pairs under a
  chunk's diagonal, the chunk states and their read), three times the
  forward's;
* 6 x the untied head's ``vocab x d`` (the sliced vocabulary).

Norms, the convolution, softplus, SiLU, the squared ReLU, sigmoid, top-k,
the sort of token-choices, the bias update and the embedding gather are
not matmuls and are left out.
"""

from __future__ import annotations

from benchmarks.lib import kernels_ssd

MAMBA, ATTENTION, EXPERTS = "M", "*", "E"


def mamba_weights(s: dict) -> float:
    d_inner = s["mamba_heads"] * s["mamba_head_dim"]
    in_width = 2 * d_inner + 2 * s["groups"] * s["d_state"] + s["mamba_heads"]
    return s["d_model"] * (in_width + d_inner)


def scan_flops(s: dict, seq_len: int) -> float:
    """The scan's forward matmul operations a token."""
    flops, _ = kernels_ssd.ssd_fwd_cost(
        batch=1, seq=seq_len, heads=s["mamba_heads"],
        head_dim=s["mamba_head_dim"], groups=s["groups"],
        d_state=s["d_state"], chunk=s["chunk"])
    return flops / seq_len


def attention_weights(s: dict) -> float:
    return s["d_model"] * s["head_dim"] * (2 * s["heads"] + 2 * s["kv_heads"])


def expert_weights(s: dict) -> dict:
    """Weights a token multiplies in an expert layer, by part."""
    d = s["d_model"]
    routed = s["top_k"] * s["experts_held"] / s["experts"]
    return {"router": d * s["experts"], "latent": 2 * d * s["latent"],
            "shared": 2 * d * s["d_shared"],
            "routed": routed * 2 * s["latent"] * s["d_expert"]}


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """``s`` as lib/reference_nemotron_h.py ``sizes_from_config`` gives
    it."""
    total = 6 * s["vocab"] * s["d_model"]
    for kind in s["kinds"]:
        if kind == MAMBA:
            total += 6 * mamba_weights(s) + 3 * scan_flops(s, seq_len)
        elif kind == ATTENTION:
            total += (6 * attention_weights(s)
                      + 12 * s["heads"] * s["head_dim"] * (seq_len + 1) / 2)
        else:
            total += 6 * sum(expert_weights(s).values())
    return total
