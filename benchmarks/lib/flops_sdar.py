"""Operations an ``sdar_moe`` decoder under the block-diffusion objective
REQUIRES per trained DATA token, on the share of the model one chip holds.

Only ``mfu_pct`` reads it. A sequence of ``L`` data tokens is ``2 L`` rows
through every layer (a noised and a clean copy) and ``L`` rows through the
head. Counted, forward and backward (2 FLOPs a weight forward, 4 backward),
nothing a tiling recomputes, nothing computed under a mask, nothing
rematerialised:

* 2 rows x 6 x the matmul weights a row passes through in a layer: q, k, v
  and output projections, the router, and the experts it is routed to AMONG
  THOSE HELD HERE: ``top_k * held / experts`` experts of three
  ``d x d_expert`` matrices on average (the absent experts' work is another
  chip's);
* attention over the VISIBLE pairs only: QK^T and PV are ``4 * H * D`` a
  pair forward, three times that with the backward, over the mask's
  ``L^2 + L B`` pairs a query head a sequence, ``L + B`` a data token;
* 6 x the untied head's ``vocab x d`` (the sliced vocabulary): one head
  row a data token, the noised one.

Norms, rotary embedding, softmax, SiLU, top-k, the sort of token-choices,
the noise and the embedding gather are not matmuls and are left out.
"""

from __future__ import annotations

# The block is the sparse family's without its indexer: the weights a ROW
# multiplies in a layer are that file's count. The pairs are the kernel
# cost file's, so that ``mfu_pct`` and the rooflines count the same ones.
from benchmarks.lib.flops_sparse_moe import layer_matmul_weights
from benchmarks.lib.kernels_block_diffusion import visible_pairs


def parameter_count(s: dict) -> int:
    """Parameters the chip holds: what 16 bytes each are reckoned on."""
    d, D = s["d_model"], s["head_dim"]
    layer = (d * D * (2 * s["heads"] + 2 * s["kv_heads"]) + d * s["experts"]
             + s["experts_held"] * 3 * d * s["d_expert"] + 2 * d + 2 * D)
    return s["layers"] * layer + 2 * s["vocab"] * d + d


def train_flops_per_token(s: dict, seq_len: int) -> float:
    """``s`` as lib/reference_sdar.py ``sizes_from_config`` gives it; a
    token is a DATA token (``seq_len`` of them a sequence)."""
    layer = (2 * 6 * layer_matmul_weights(s)
             + 12 * s["heads"] * s["head_dim"]
             * visible_pairs(seq_len, s["block_length"]) / seq_len)
    return s["layers"] * layer + 6 * s["vocab"] * s["d_model"]
