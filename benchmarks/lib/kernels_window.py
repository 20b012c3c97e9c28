"""Operations and bytes the flash kernels NEED for a call with a window
and grouped KV heads, from its shapes; ``lib/kernels.py: roofline`` turns
them into a least time.

By the VISIBLE pairs only: query t of a windowed call attends
``min(t + 1, window)`` keys (the band), of a full call ``t + 1`` (exactly,
not ``T / 2``); what a tile computes under a mask is not counted, so no
share can pass 100%. K and V are counted once a KV head, not once a query
head: ``kv_heads`` of them are read, and written as dk and dv. Each
function takes the builder's whole ``kernel_shapes`` entry
(``window_attention`` or ``gqa_attention``; the latter has ``window``
None) and reads the sizes it needs.
"""

from __future__ import annotations


def visible_pairs(seq: int, window=None) -> float:
    """sum_t min(t + 1, window): the pairs one query head attends; the
    causal ``seq * (seq + 1) / 2`` without a window (or one the sequence
    fits in)."""
    w = seq if window is None else min(window, seq)
    return w * (w + 1) / 2 + (seq - w) * w


def _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes):
    q = batch * seq * heads * head_dim * act_bytes
    kv = batch * seq * kv_heads * head_dim * act_bytes
    return q, kv


def attn_fwd_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                  head_dim: int, window=None, act_bytes: int = 2,
                  **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward: S = QK^T and O = PV over the visible
    pairs of every query head; reads q and the ``kv_heads`` heads of k and
    v, writes o and the fp32 log-sum-exp row."""
    pairs = batch * heads * visible_pairs(seq, window)
    q, kv = _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes)
    return 2 * 2 * pairs * head_dim, 2 * q + 2 * kv + batch * heads * seq * 4


def attn_bwd_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                  head_dim: int, window=None, act_bytes: int = 2,
                  **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one backward, dq and dk/dv together: dV = P^T dO,
    dP = dO V^T, dQ = dS K, dK = dS^T Q over the visible pairs, four
    matmuls; the S = QK^T a flash backward recomputes is not counted.
    Reads q, dO, k, v and the two fp32 rows once; writes dq and the
    ``kv_heads`` heads of dk and dv."""
    pairs = batch * heads * visible_pairs(seq, window)
    q, kv = _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes)
    return (4 * 2 * pairs * head_dim,
            3 * q + 4 * kv + 2 * batch * heads * seq * 4)
