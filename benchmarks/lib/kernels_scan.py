"""Bytes and operations the selective-scan kernels NEED for a call, from its
shapes; ``lib/kernels.py: roofline`` turns them into a least time.

``lib/peaks.json`` has no row for the vector unit, so the operations are
stated for the reader and the least time is the HBM one: the shares
``selective_scan_*_roofline`` read against BYTES. Every operand once, in
float32, at its own size: nothing for the channels' padding to whole
blocks, nothing for a part that is summed outside the kernel. Each function
takes the builder's whole ``kernel_shapes`` entry (``selective_scan``).

* forward: ``xs`` and ``D_t`` [T, Dn], ``B`` and ``C`` [T, N], ``A``
  [Dn, N], ``Dskip`` [Dn] in; ``m`` [T, Dn] out. The states the backward
  starts its chunks from are the kernel's own choice and are not counted.
* backward: the same in, ``dm`` [T, Dn] and one state a chunk boundary
  ([Dn, N], at the coarsest chunking a kernel could take: one chunk, so
  none); the six gradients out, in their operands' shapes.
"""

from __future__ import annotations

F32 = 4
#: Vector-unit operations a (token, channel, state) triple needs: the
#: decay's product and exponential, the state's multiply-add, the input's
#: product and the output's multiply-add.
FWD_OPS_A_STATE = 7
#: The backward's: the reverse recurrence, the decay's and the four state
#: gradients' products. The forward it runs again is not counted.
BWD_OPS_A_STATE = 14


def _tensors(batch, seq, d_inner, d_state):
    tokens = batch * seq * d_inner * F32          # xs, D_t, m, dm
    states = batch * seq * d_state * F32          # B, C
    weights = (d_inner * d_state + d_inner) * F32  # A, Dskip
    return tokens, states, weights


def scan_fwd_cost(*, batch: int, seq: int, d_inner: int, d_state: int,
                  **_) -> tuple[float, float]:
    """(operations, bytes) of one forward."""
    tokens, states, weights = _tensors(batch, seq, d_inner, d_state)
    ops = FWD_OPS_A_STATE * batch * seq * d_inner * d_state
    return ops, 3 * tokens + 2 * states + weights


def scan_bwd_cost(*, batch: int, seq: int, d_inner: int, d_state: int,
                  **_) -> tuple[float, float]:
    """(operations, bytes) of one backward: xs, D_t and dm in, dxs and dD_t
    out; B, C in, dB, dC out; A, Dskip in, dA, dDskip out."""
    tokens, states, weights = _tensors(batch, seq, d_inner, d_state)
    ops = BWD_OPS_A_STATE * batch * seq * d_inner * d_state
    return ops, 5 * tokens + 4 * states + 2 * weights


def least_seconds(nbytes: float, peak: dict) -> float:
    """The HBM time of ``nbytes``: the only bound ``peaks.json`` can give
    a kernel that does no matmul."""
    return nbytes / peak["hbm_bytes_per_s"]
