"""Operations and bytes a kernel call needs, from its shapes, and the least
time a chip with the given peaks could take for them (its roofline).

Kept with the benchmark so that a PR that changes a kernel cannot change
what the kernel is measured against. ``needs`` means the algorithm's own
arithmetic: a causal mask halves the score matrix, and what a tiling
recomputes or computes under the mask is not counted, so a share of the
roofline made from these cannot pass 100%.
"""

from __future__ import annotations


def flash_fwd_cost(*, batch: int, seq: int, heads: int, head_dim: int,
                   causal: bool, act_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one attention forward over [B, T, H, D]:
    S = QK^T and O = PV, 2*T*T*D multiply-adds each per head; reads q, k, v,
    writes o and the fp32 log-sum-exp row."""
    pairs = batch * heads * seq * seq * (0.5 if causal else 1.0)
    flops = 2 * 2 * pairs * head_dim
    tensor = batch * seq * heads * head_dim * act_bytes
    return flops, 4 * tensor + batch * heads * seq * 4


def flash_bwd_cost(*, batch: int, seq: int, heads: int, head_dim: int,
                   causal: bool, act_bytes: int = 2) -> tuple[float, float]:
    """(FLOPs, bytes) of one attention backward (dq and dk/dv together):
    dV = P^T dO, dP = dO V^T, dQ = dS K, dK = dS^T Q -- four matmuls; the
    S = QK^T a flash backward recomputes is not counted. Reads q, k, v, dO
    and the two fp32 rows (lse, delta) once, writes dq, dk, dv."""
    pairs = batch * heads * seq * seq * (0.5 if causal else 1.0)
    flops = 4 * 2 * pairs * head_dim
    tensor = batch * seq * heads * head_dim * act_bytes
    return flops, 7 * tensor + 2 * batch * heads * seq * 4


def roofline(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """(least seconds, which bound applies) on a chip with ``peak``."""
    t_flops = flops / peak["bf16_flops_per_s"]
    t_bytes = nbytes / peak["hbm_bytes_per_s"]
    return (t_flops, "flops") if t_flops >= t_bytes else (t_bytes, "bytes")
