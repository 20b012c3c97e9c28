"""Operations a decoder-hybrid-decoder model (state-space layers,
differential attention, gated memory units, cross-attention; dense gated
MLP on every layer, tied head) REQUIRES per trained token, on the share of
the model one chip holds.

Only ``mfu_pct`` reads it. Counted, forward and backward (2 FLOPs a weight
forward, 4 backward), nothing a tiling recomputes, nothing computed under a
mask, nothing rematerialised:

* 6 x the matmul weights a token passes through in a layer: the mixer's
  (``mixer_weights``) and the MLP's three ``d x d_ff`` matrices;
* differential attention over the VISIBLE pairs only, the band on a sliding
  layer and the causal triangle on a full or cross-attention one: a pair of
  heads has two softmax maps, each a 64-wide score and a 128-wide value
  product, ``2 maps x (64 + 128) x 2`` FLOPs a pair of heads and key
  forward and twice that backward: four products, dV and dP 128 wide, dQ
  and dK 64 wide. The score a flash backward makes again is NOT counted,
  as ``lib/kernels_window.py`` and ``lib/flops_afmoe.py`` do not count it
  (ISSUE 39 reckoned 2.5 times, the count that holds it);
* 6 x the tied head's ``vocab x d`` once (the sliced vocabulary): the
  embedding's lookup is a gather and is not counted.

The scan's elementwise recurrence (about ``9 x d_inner x d_state`` a token
and direction on the vector unit), the convolution's four taps, norms,
softmax, SiLU, softplus, the differential subtraction and its norm are not
matmuls and are left out: ``mfu_pct`` can only read low for them.
"""

from __future__ import annotations

from benchmarks.lib.kernels_window import visible_pairs

MAMBA, SLIDING, FULL = "mamba", "sliding_attention", "full_attention"
GMU, CROSS = "gmu", "cross_attention"


def mixer_weights(s: dict, kind: str) -> float:
    """Matmul weights a token multiplies in a mixer of ``kind``."""
    d, Dn, N, R = s["d_model"], s["d_inner"], s["d_state"], s["dt_rank"]
    HD, KD = s["heads"] * s["head_dim"], s["kv_heads"] * s["head_dim"]
    return {
        MAMBA: d * 2 * Dn + Dn * (R + 2 * N) + R * Dn + Dn * d,
        SLIDING: d * (HD + 2 * KD) + HD * d,
        FULL: d * (HD + 2 * KD) + HD * d,
        CROSS: d * HD + HD * d,
        GMU: d * Dn + Dn * d,
    }[kind]


def attention_flops(s: dict, kind: str, seq_len: int) -> float:
    """Forward and backward FLOPs of a layer's two softmax maps, a token."""
    if kind not in (SLIDING, FULL, CROSS):
        return 0.0
    window = s["window"] if kind == SLIDING else None
    pairs = visible_pairs(seq_len, window) / seq_len
    D = s["head_dim"]
    forward = (s["heads"] // 2) * 2 * (D + 2 * D) * 2 * pairs
    return 3 * forward


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """``s`` as lib/reference_sambay.py ``sizes_from_config`` gives it."""
    total = 6 * s["vocab"] * s["d_model"]
    for kind in s["layer_types"]:
        total += (6 * (mixer_weights(s, kind) + 3 * s["d_model"] * s["d_ff"])
                  + attention_flops(s, kind, seq_len))
    return total
