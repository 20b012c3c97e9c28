#!/usr/bin/env python3
"""Device time of the sparse-attention kernels at a model's shapes.

    chiprun -- python3 scripts/sparse_kernel_times.py [--seq 16384] \
        [--blocks 512x512,256x512] [--bwd-blocks 512x1024,256x1024] \
        [--index-blocks 128x512]

Runs ``hvd.sparse_attention`` forward and backward a few times under the
profiler and prints the mean duration of the events named
``hvd_index_select`` / ``hvd_sparse_attn_fwd`` and of every kernel whose
name begins ``hvd_sparse_attn_bwd`` on the first device (as the
benchmark's ``sparse_attn_bwd_roofline`` takes the backward: the fused
kernel, or ``_dq`` and ``_dkv``), with their sum a call as ``bwd_us``, for
each (bq x bk) of the attention kernels (``--blocks``: the forward and
the two backward kernels; ``--bwd-blocks``: the fused backward) and each
(block x chunk) of the index kernel, with the share of (512, 512) tiles
that hold no selected pair. Where the shape takes the fused backward a row
more a ``--blocks`` entry (``"bwd": "split"``) times the two kernels
beside it. Shapes default to
Keye-VL-2.0-30B-A3B's (32/4 heads of 128, indexer 16 x 64, topk 2048).
Needs a TPU (anything else: exit 2). Rows also go to
``chiprun_out/sparse_kernel_times.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

BWD_PREFIX = "hvd_sparse_attn_bwd"
KERNELS = ("hvd_index_select", "hvd_sparse_attn_fwd", BWD_PREFIX,
           BWD_PREFIX + "_dq", BWD_PREFIX + "_dkv")


def pairs(text):
    return [tuple(int(x) for x in p.split("x")) for p in text.split(",") if p]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seq", type=int, default=16384)
    ap.add_argument("--heads", default="32x4x128")
    ap.add_argument("--indexer", default="16x64")
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--blocks", default="")
    ap.add_argument("--bwd-blocks", default="",
                    help="the fused backward kernel's (bq x bk)")
    ap.add_argument("--index-blocks", default="")
    ap.add_argument("--iters", type=int, default=3)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    from flash_kernel_times import kernel_us
    from horovod_tpu.ops import sparse_attention as sa
    T = args.seq
    (H, Hk, D), (Hi, Di) = pairs(args.heads)[0], pairs(args.indexer)[0]
    keys = jax.random.split(jax.random.key(0), 6)

    def rnd(key, *shape):
        return jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)

    q, k, v = rnd(keys[0], 1, T, H, D), rnd(keys[1], 1, T, Hk, D), \
        rnd(keys[2], 1, T, Hk, D)
    qi, ki = rnd(keys[3], 1, T, Hi, Di), rnd(keys[4], 1, T, Di)
    w = jax.random.normal(keys[5], (1, T, Hi), jnp.float32)
    out = os.path.join("chiprun_out", "sparse_kernel_times.jsonl")
    os.makedirs("chiprun_out", exist_ok=True)

    def loss(q, k, v):
        return sa.sparse_attention(q, k, v, qi, ki, w, topk=args.topk
                                   ).astype(jnp.float32).sum()

    budget = sa._FUSED_BWD_BUDGET
    blocks = pairs(args.blocks) or [(sa._BLOCK_Q, sa._BLOCK_K)]
    bwd_blocks = pairs(args.bwd_blocks) or [
        (sa._BWD_BLOCK_Q, sa._BWD_BLOCK_K)]
    index_blocks = pairs(args.index_blocks) or [
        (sa._INDEX_BLOCK_Q, sa._INDEX_CHUNK)]
    fused = sa._fused_bwd_fits(T, D)
    # (forward and two-kernel blocks, fused backward's, index kernel's,
    # the two backward kernels in the fused one's place)
    runs = [(b, bwd_blocks[0], index_blocks[0], not fused) for b in blocks]
    if fused:
        runs += [(blocks[0], bb, index_blocks[0], False)
                 for bb in bwd_blocks[1:]]
        runs += [(b, bwd_blocks[0], index_blocks[0], True) for b in blocks]
    runs += [(blocks[0], bwd_blocks[0], ib, not fused)
             for ib in index_blocks[1:]]
    for (bq, bk), (bbq, bbk), (ibq, ick), split in runs:
        sa._BLOCK_Q, sa._BLOCK_K = bq, bk
        sa._BWD_BLOCK_Q, sa._BWD_BLOCK_K = bbq, bbk
        sa._INDEX_BLOCK_Q, sa._INDEX_CHUNK = ibq, ick
        # The path is the shape's alone; a budget of nothing sends this
        # shape down the other one, to be timed beside.
        sa._FUSED_BWD_BUDGET = 0 if split else budget
        row = {"seq": T, "blocks": [bq, bk], "index_blocks": [ibq, ick],
               "bwd": "split" if split else "fused",
               "bwd_blocks": [bq, bk] if split else [bbq, bbk]}
        try:
            step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
            jax.block_until_ready(step(q, k, v))
            with tempfile.TemporaryDirectory() as d:
                jax.profiler.start_trace(d)
                for _ in range(args.iters):
                    jax.block_until_ready(step(q, k, v))
                jax.profiler.stop_trace()
                row["us"] = us = kernel_us(d, KERNELS)
            row["bwd_us"] = sum(mean * n for name, (mean, n) in us.items()
                                if name.startswith(BWD_PREFIX)) / args.iters
        except Exception as e:  # a block the compiler refuses
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        print(json.dumps(row), flush=True)
        with open(out, "a") as f:
            f.write(json.dumps(row) + "\n")
    sa._FUSED_BWD_BUDGET = budget
    mask, _ = jax.jit(lambda: sa.index_select(qi, ki, w, topk=args.topk))()
    tiles = (mask != 0).reshape(T // 512, 512, T // 512, 512).any((1, 3))
    causal = jnp.tril(jnp.ones_like(tiles))
    row = {"seq": T, "selected_share_of_causal_pairs": float(
        (mask != 0).sum() / (T * (T + 1) / 2)),
        "empty_share_of_causal_512_tiles": float(
            1 - (tiles & causal).sum() / causal.sum())}
    print(json.dumps(row), flush=True)
    with open(out, "a") as f:
        f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
