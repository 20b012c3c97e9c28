#!/usr/bin/env python3
"""Device time of differential attention's elementwise kernels
(``ops/diff_attention.py``), and of the expressions they replaced.

    chiprun -- python3 scripts/diff_kernel_times.py [--shape 1x8192x40x64] [--blocks 256x64x2,512x64x2]

On the chip: first the two functions' values and cotangents (``do``,
``dlam``, ``dscale``, ``dq``) against their ``jax.numpy`` path at
``--check-shape`` in bfloat16 and float32, the relative gaps printed by
name (one above ``--tolerance``: exit 1). Then ``jax.grad`` of a weighted
square of ``diff_combine(lay_in_halves(q))`` a few times under the profiler:
the mean duration of each ``hvd_diff_*`` kernel with the GB/s of its
operands and result moved once, ``other`` = every other device op of a
call; and the same gradient of the expressions ``models/sambay.py`` held
before PR 43 (``xla_us``: every device op of a call). ``--blocks`` times the
kernels at other ``BLOCK_ROWSxCHUNK_ROWSxPAIR_UNROLL`` than the module's. A
shape is ``BxTxHxD``. Needs a TPU (anything else: exit 2). Rows also go to
``chiprun_out/diff_kernel_times.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "scripts"))

KERNELS = ("hvd_diff_lay_fwd", "hvd_diff_lay_bwd", "hvd_diff_combine_fwd",
           "hvd_diff_combine_bwd")
EPS = 1e-5


def kernel_bytes(B, T, H, D, itemsize) -> dict:
    """Bytes each kernel moves: its operands and its result once."""
    narrow, wide = (B * T * H * D * itemsize, B * T * H * 2 * D * itemsize)
    return {"hvd_diff_lay_fwd": narrow + wide,
            "hvd_diff_lay_bwd": wide + narrow,
            "hvd_diff_combine_fwd": wide + narrow,
            "hvd_diff_combine_bwd": wide + narrow + wide}


def operands(B, T, H, D, dtype, seed=0):
    import jax.numpy as jnp
    import numpy as np

    rs = np.random.RandomState(seed)
    return (jnp.asarray(rs.randn(B, T, H * D), dtype), jnp.float32(0.37),
            jnp.asarray(rs.rand(2 * D) + 0.5, jnp.float32),
            jnp.asarray(rs.randn(B, T, H * D), jnp.float32))


def parent_loss(H, D):
    """The expressions of ``_DiffAttention`` before PR 43."""
    import jax
    import jax.numpy as jnp

    def loss(q, lam, scale, w):
        b, t, _ = q.shape
        q6 = q.reshape(b, t, H // 2, 2, 1, D)
        o = (q6 * jnp.eye(2, dtype=q.dtype)[:, :, None]).reshape(
            b, t, H // 2, 2, 2 * D).astype(jnp.float32)
        a = o[:, :, :, 0] - lam * o[:, :, :, 1]
        y = a * jax.lax.rsqrt(jnp.mean(a * a, -1, keepdims=True) + EPS)
        y = (y * scale).astype(q.dtype).reshape(b, t, H * D)
        return (y.astype(jnp.float32) ** 2 * w).sum()

    return loss


def tree_loss(D, DA):
    def loss(q, lam, scale, w):
        import jax.numpy as jnp

        y = DA.diff_combine(DA.lay_in_halves(q, D), lam, scale, EPS)
        return (y.astype(jnp.float32) ** 2 * w).sum()

    return loss


def check(shape, dtype) -> dict:
    """The kernels' value and gradients against the ``jax.numpy`` path of
    the same functions, relative to the latter's largest entry."""
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import diff_attention as DA

    B, T, H, D = shape
    ops = operands(B, T, H, D, dtype, seed=1)
    f = jax.jit(jax.value_and_grad(tree_loss(D, DA), argnums=(0, 1, 2)))
    got = f(*ops)
    runs, DA._runs_kernels = DA._runs_kernels, lambda W: False
    try:
        want = jax.jit(jax.value_and_grad(tree_loss(D, DA),
                                          argnums=(0, 1, 2)))(*ops)
    finally:
        DA._runs_kernels = runs
    gaps = {"value": abs(float(got[0]) - float(want[0]))
            / abs(float(want[0]))}
    for name, a, b in zip(("dq", "dlam", "dscale"), got[1], want[1]):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        gaps[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    return gaps


def device_us(trace_dir, calls) -> dict:
    """{kernel: (mean us an event, events)}, ``other`` and ``all`` (us a
    call) on the first device of a trace."""
    import glob

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    plane = min((p for p in ProfileData.from_file(path).planes
                 if p.name.startswith("/device:TPU:")), key=lambda p: p.name)
    found, other = {k: [] for k in KERNELS}, 0.0
    for line in plane.lines:
        if line.name != "XLA Ops":
            continue
        for e in line.events:
            head = e.name.split(" = ")[0]
            mine = [k for k in KERNELS if k in head]
            for k in mine:
                found[k].append(e.duration_ns * 1e-3)
            if not mine:
                other += e.duration_ns * 1e-3
    us = {k: (sum(v) / len(v), len(v)) for k, v in found.items() if v}
    us["other"] = other / calls
    us["all"] = us["other"] + sum(sum(v) for v in found.values()) / calls
    return us


def time_one(loss, ops, steps) -> dict:
    import jax

    f = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    jax.block_until_ready(f(*ops))           # compile + warm
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(steps):
            out = f(*ops)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        return device_us(d, steps)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1x8192x40x64")
    ap.add_argument("--check-shape", default="2x1000x6x64")
    ap.add_argument("--blocks", default="",
                    help="BLOCK_ROWSxCHUNK_ROWSxPAIR_UNROLL,... (default: "
                         "the module's)")
    ap.add_argument("--steps", type=int, default=5)
    ap.add_argument("--tolerance", type=float, default=2e-2,
                    help="largest relative gap (a bfloat16 result may "
                         "round the other way: 2 ** -7)")
    args = ap.parse_args(argv)
    import jax
    import jax.numpy as jnp

    from horovod_tpu.ops import diff_attention as DA

    if jax.devices()[0].platform != "tpu":
        print("diff_kernel_times: needs a TPU", file=sys.stderr)
        return 2
    worst = 0.0
    for dtype in (jnp.bfloat16, jnp.float32):
        gaps = check(tuple(map(int, args.check_shape.split("x"))), dtype)
        worst = max(worst, *gaps.values())
        print(json.dumps({"check_shape": args.check_shape,
                          "dtype": jnp.dtype(dtype).name, "gaps": gaps}),
              flush=True)
    B, T, H, D = shape = tuple(map(int, args.shape.split("x")))
    ops = operands(*shape, jnp.bfloat16)
    moved = kernel_bytes(*shape, 2)
    xla_us = time_one(parent_loss(H, D), ops, args.steps)["all"]
    own = (DA.BLOCK_ROWS, DA.CHUNK_ROWS, DA.PAIR_UNROLL)
    blocks = [tuple(map(int, b.split("x"))) for b in
              args.blocks.split(",") if b] or [own]
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "diff_kernel_times.jsonl"),
              "a") as out:
        for block in blocks:
            DA.BLOCK_ROWS, DA.CHUNK_ROWS, DA.PAIR_UNROLL = block
            try:
                us = time_one(tree_loss(D, DA), ops, args.steps)
            except Exception as e:           # a blocking the chip refuses
                us = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            row = {"shape": args.shape, "blocks": "x".join(map(str, block)),
                   "us": us, "xla_us": xla_us,
                   "gb_per_s": {k: round(moved[k] / us[k][0] / 1e3, 1)
                                for k in KERNELS if k in us}}
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
    DA.BLOCK_ROWS, DA.CHUNK_ROWS, DA.PAIR_UNROLL = own
    return 0 if worst <= args.tolerance else 1


if __name__ == "__main__":
    sys.exit(main())
