"""Operations and bytes the sparse-attention kernels NEED per call, from
their shapes; ``lib/kernels.py: roofline`` turns them into a least time.

By the SELECTED pairs only: a query attends ``min(t + 1, topk)`` keys, and
what a masked-dense kernel computes for pairs outside the selection is not
counted, so such a kernel reads a low share of its roofline and no share
can pass 100%. The index kernel needs every causal pair (it is what finds
the selection). Each function takes the builder's whole ``kernel_shapes``
entry and reads the sizes it needs.
"""

from __future__ import annotations


def selected_pairs(seq: int, topk: int) -> float:
    """sum_t min(t + 1, topk): the pairs one query head attends."""
    k = min(topk, seq)
    return k * (k + 1) / 2 + (seq - k) * k


def _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes):
    q = batch * seq * heads * head_dim * act_bytes
    kv = batch * seq * kv_heads * head_dim * act_bytes
    return q, kv


def sparse_attn_fwd_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, topk: int, act_bytes: int = 2,
                         **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward: S = QK^T and O = PV over the selected
    pairs of every query head; reads q, k, v and the selection (a byte a
    selected pair at the least), writes o and the fp32 log-sum-exp row."""
    pairs = batch * selected_pairs(seq, topk)
    q, kv = _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes)
    return (2 * 2 * pairs * heads * head_dim,
            2 * q + 2 * kv + pairs + batch * heads * seq * 4)


def sparse_attn_bwd_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, topk: int, act_bytes: int = 2,
                         **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one backward, dq and dk/dv together: dV = P^T dO,
    dP = dO V^T, dQ = dS K, dK = dS^T Q over the selected pairs; the
    S = QK^T a flash backward recomputes is not counted. Reads q, k, v, dO,
    the two fp32 rows and the selection once; writes dq, dk, dv."""
    pairs = batch * selected_pairs(seq, topk)
    q, kv = _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes)
    return (4 * 2 * pairs * heads * head_dim,
            3 * q + 4 * kv + pairs + 2 * batch * heads * seq * 4)


def index_select_cost(*, batch: int, seq: int, idx_heads: int, idx_dim: int,
                      **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one indexer call: qI . kI for every head over the
    causal pairs (2 * Hi * Di a pair); reads qI, kI, w, writes a byte a
    causal pair (the selection) and the fp32 threshold row."""
    pairs = batch * seq * (seq + 1) / 2
    reads = batch * seq * (idx_heads * idx_dim * 2 + idx_dim * 2
                           + idx_heads * 4)
    return 2 * pairs * idx_heads * idx_dim, reads + pairs + batch * seq * 4
