"""Operations a decoder-only transformer REQUIRES per trained token.

One definition for every decoder configuration (the benchmark's ``mfu_pct``
and nothing else reads it). What is counted, forward and backward, nothing
recomputed, nothing the compiler happened to run:

* 6 x the matmul weights a token passes through: per block the qkv, output,
  up and down projections, plus the tied head's ``vocab x d_model`` (2 FLOPs
  a weight forward, 4 backward). Biases, LayerNorm, GELU, softmax and the
  embedding gather are not matmuls and are left out, so the count is a
  little low and a utilisation made from it cannot be flattered.
* causal attention: QK^T and PV are each ``2 * T * d_model`` a token a layer
  for full attention; forward + backward is three times that, and a causal
  mask needs half: ``6 * layers * T * d_model``.
"""

from __future__ import annotations


def block_matmul_weights(d_model: int, d_ff: int) -> int:
    """qkv (d x 3d) + attention output (d x d) + MLP up and down."""
    return 4 * d_model * d_model + 2 * d_model * d_ff


def decoder_train_flops_per_token(*, layers: int, d_model: int, d_ff: int,
                                  vocab: int, seq_len: int) -> int:
    weights = layers * block_matmul_weights(d_model, d_ff) + vocab * d_model
    return 6 * weights + 6 * layers * seq_len * d_model
