"""Operations and bytes one call of the chunked state-space scan
(Mamba-2's: ``ops/ssd_scan.py``) NEEDS, from its shapes;
``lib/kernels.py: roofline`` turns them into a least time. The same work
whatever implements it: a Pallas kernel or matmuls the compiler schedules.

Each function takes the builder's whole ``kernel_shapes`` entry
(``ssd_scan``: ``batch``, ``seq``, ``heads`` of ``head_dim`` channels over
``groups``, ``d_state`` states, ``chunk`` tokens a chunk, ``act_bytes``).
Counted, a chunk of ``L`` tokens, with ``pairs = L (L + 1) / 2`` the (t, s)
pairs under the diagonal (what stands above it is masked and not counted,
so a share made from these cannot pass 100%):

* forward: ``C B^T`` over the pairs, ``2 N`` a pair a GROUP; the masked
  product times the inputs, ``2 P`` a pair a head; the chunk's own state
  ``(D xs)^T B`` and the carried state's read ``C S_in``, ``2 P N`` a token
  a head each; the recurrence over the chunk states, ``2 P N`` a chunk a
  head. Bytes: ``xs`` and ``y`` at ``act_bytes``, ``D_t`` float32, ``B``
  and ``C`` at ``act_bytes``, ``A_log`` and ``Dskip``. The chunk states
  stay on the chip (``[h, P, N]`` float32 is 0.5 MB at the cell's sizes)
  and are not counted here.
* backward: every forward matmul has two transposes, so twice the
  forward's operations (the ``[L, L]`` pieces it makes again are not
  counted); the operands and ``dy`` in, the six gradients out, and the
  chunk-entering states read once (``[T / L, h, P, N]`` float32: the
  chunked algorithm's own memory, at the chunk the entry states).
"""

from __future__ import annotations

F32 = 4


def _chunks(seq: int, chunk: int) -> int:
    return -(-seq // chunk)


def ssd_fwd_cost(*, batch: int, seq: int, heads: int, head_dim: int,
                 groups: int, d_state: int, chunk: int, act_bytes: int = 2,
                 **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one forward."""
    pairs = chunk * (chunk + 1) / 2
    a_chunk = (groups * 2 * d_state * pairs
               + heads * (2 * head_dim * pairs
                          + 2 * 2 * head_dim * d_state * chunk
                          + 2 * head_dim * d_state))
    tokens = batch * seq
    nbytes = (2 * tokens * heads * head_dim * act_bytes      # xs, y
              + tokens * heads * F32                          # D_t
              + 2 * tokens * groups * d_state * act_bytes    # B, C
              + 2 * heads * F32)                              # A_log, Dskip
    return batch * _chunks(seq, chunk) * a_chunk, nbytes


def ssd_bwd_cost(*, batch: int, seq: int, heads: int, head_dim: int,
                 groups: int, d_state: int, chunk: int, act_bytes: int = 2,
                 **_) -> tuple[float, float]:
    """(FLOPs, bytes) of one backward."""
    flops, fwd_bytes = ssd_fwd_cost(
        batch=batch, seq=seq, heads=heads, head_dim=head_dim, groups=groups,
        d_state=d_state, chunk=chunk, act_bytes=act_bytes)
    states = batch * _chunks(seq, chunk) * heads * head_dim * d_state * F32
    # xs, D_t, B, C, A_log, Dskip in and their gradients out: the forward's
    # bytes twice, its y being dy here and dxs there; the states once.
    return 2 * flops, 2 * fwd_bytes + states
