"""Operations and bytes one differential-attention call NEEDS, from its
shapes; ``lib/kernels.py: roofline`` turns them into a least time.

A call holds ``heads`` softmax maps (a query head each; two of them make a
differential pair). A map's score is ``head_dim`` wide and its value is the
KV pair's ``[v1 | v2]``, ``2 * head_dim`` wide: the two products of a pair
of query and key are ``2 * (head_dim + 2 * head_dim)`` FLOPs forward, and
the backward's four (dV and dP at the value's width, dQ and dK at the
score's) twice that; the score a flash backward makes again is not counted.
By the VISIBLE pairs only (``lib/kernels_window.py: visible_pairs``), so no
share can pass 100%. Bytes: every operand once at ITS width: q
``heads x head_dim`` (the program lays it into a 128-wide head whose other
half is zeros; the zeros are not needed and not counted), k and v
``kv_heads x head_dim`` each, the output and its cotangent ``heads x 2 *
head_dim``, a float32 row a map (two in the backward). Each function takes
the builder's whole ``kernel_shapes`` entry (``diff_attention`` or
``diff_window_attention``).
"""

from __future__ import annotations

from benchmarks.lib.kernels_window import visible_pairs


def _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes):
    tokens = batch * seq * act_bytes
    q = tokens * heads * head_dim
    kv = tokens * kv_heads * head_dim
    out = tokens * heads * 2 * head_dim
    return q, kv, out, batch * heads * seq * 4


def attn_fwd_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                  head_dim: int, window=None, act_bytes: int = 2,
                  **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward: reads q, k, v; writes o and the
    float32 log-sum-exp row."""
    pairs = batch * heads * visible_pairs(seq, window)
    q, kv, out, row = _tensors(batch, seq, heads, kv_heads, head_dim,
                               act_bytes)
    return 2 * pairs * 3 * head_dim, q + 2 * kv + out + row


def attn_bwd_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                  head_dim: int, window=None, act_bytes: int = 2,
                  **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one backward, dq and dk/dv together: reads q, k, v,
    dO and the two float32 rows; writes dq, dk, dv."""
    pairs = batch * heads * visible_pairs(seq, window)
    q, kv, out, row = _tensors(batch, seq, heads, kv_heads, head_dim,
                               act_bytes)
    return 2 * pairs * 6 * head_dim, 2 * q + 4 * kv + out + 2 * row
