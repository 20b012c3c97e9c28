"""Operations and bytes the flash kernels NEED for a call under the
block-diffusion mask with grouped KV heads, from its shapes;
``lib/kernels.py: roofline`` turns them into a least time.

By the VISIBLE pairs only: over the ``2 L`` rows ``[noised ; clean]`` of a
sequence (``seq`` is L, ``block`` B) a clean query of block b attends the
``(b + 1) B`` clean keys to the end of its block, a noised one the ``b B``
clean keys before its block and the ``B`` noised keys of its own:
``L^2 + L B`` pairs a query head, of the ``4 L^2`` the square holds. What a
tile computes under a mask is not counted, so no share can pass 100%. K and
V are counted once a KV head over all ``2 L`` rows (the noised rows' K and
V are read, by their own block alone), and written once as dk and dv. Each
function takes the builder's whole ``kernel_shapes`` entry
(``block_diffusion_attention``) and reads the sizes it needs.
"""

from __future__ import annotations


def visible_pairs(seq: int, block: int) -> float:
    """The pairs one query head attends over a sequence's 2 * seq rows."""
    return float(seq) * seq + float(seq) * block


def _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes):
    rows = 2 * seq
    q = batch * rows * heads * head_dim * act_bytes
    kv = batch * rows * kv_heads * head_dim * act_bytes
    return q, kv, batch * heads * rows * 4


def attn_fwd_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                  head_dim: int, block: int, act_bytes: int = 2,
                  **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward: S = QK^T and O = PV over the visible
    pairs of every query head; reads q and the ``kv_heads`` heads of k and
    v, writes o and the fp32 log-sum-exp row."""
    pairs = batch * heads * visible_pairs(seq, block)
    q, kv, stat = _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes)
    return 2 * 2 * pairs * head_dim, 2 * q + 2 * kv + stat


def attn_bwd_cost(*, batch: int, seq: int, heads: int, kv_heads: int,
                  head_dim: int, block: int, act_bytes: int = 2,
                  **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one backward, dq and dk/dv together: dV = P^T dO,
    dP = dO V^T, dQ = dS K, dK = dS^T Q over the visible pairs, four
    matmuls; the S = QK^T a flash backward recomputes is not counted.
    Reads q, dO, k, v and the two fp32 rows once; writes dq and the
    ``kv_heads`` heads of dk and dv."""
    pairs = batch * heads * visible_pairs(seq, block)
    q, kv, stat = _tensors(batch, seq, heads, kv_heads, head_dim, act_bytes)
    return 4 * 2 * pairs * head_dim, 3 * q + 4 * kv + 2 * stat
