#!/usr/bin/env python3
"""Device time of the selective-scan kernels, by chunk and channel block.

    chiprun -- python3 scripts/scan_kernel_times.py \
        --shape 1x8192x5120x16 --blocks 64x1024,128x1024,64x2048,128x2048

Runs ``jax.grad(hvd.selective_scan)`` (all six gradients) a few times under
the profiler for each ``CHUNKxBLOCK_D`` and prints the mean duration of the
events named ``hvd_selective_scan_fwd`` / ``hvd_selective_scan_bwd`` on the
first device, with ``other`` = every other device op of a call (the
operands' casts and pads, the sums of the parts), and beside the times
``ns_a_unit`` (a unit: a token, a state and a register of 1024 channels) and
``vector_tops`` (10 ** 12 vector operations a second on float32 elements, at
``OPS_A_UNIT`` operations a unit: what the source does, the exponential
as one). Before the timing the kernels' values and gradients are held
against ``selective_scan_reference`` (a ``lax.scan``) at ``--check-shape``,
on the chip, and the relative gaps are printed by name: one above 1e-4 is
exit 1. A shape is ``BxTxDnxN``. Needs a TPU (anything else: exit 2).
Results also go to ``chiprun_out/scan_kernel_times.jsonl``.
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

KERNELS = ("hvd_selective_scan_fwd", "hvd_selective_scan_bwd")
#: Vector operations a (token, state, 1024 channels) in ops/selective_scan.py:
#: the forward's state update is 5 and its output 2; the backward makes the
#: states again (5) with the dC product and its sum (2), then 14 in reverse.
OPS_A_UNIT = {"hvd_selective_scan_fwd": 7, "hvd_selective_scan_bwd": 21}
CHANNELS_A_UNIT = 1024


def operands(B, T, Dn, N, seed=0):
    """Operands in the ranges a Mamba layer's own have at its start."""
    import jax.numpy as jnp
    import numpy as np

    rs = np.random.RandomState(seed)
    x = jnp.asarray(rs.randn(B, T, Dn), jnp.float32)
    dt = jnp.asarray(np.exp(rs.uniform(np.log(1e-3), np.log(1e-1),
                                       (B, T, Dn))), jnp.float32)
    A = -jnp.asarray(np.tile(np.arange(1.0, N + 1), (Dn, 1)), jnp.float32)
    Bm, Cm = (jnp.asarray(rs.randn(B, T, N), jnp.float32) for _ in "bc")
    return x, dt, A, Bm, Cm, jnp.ones((Dn,), jnp.float32)


def grads(fn, weights, **kw):
    import jax

    return jax.jit(jax.value_and_grad(
        lambda *ops: (fn(*ops, **kw) * weights).sum(),
        argnums=tuple(range(6))))


def check(shape) -> dict:
    """The gap of the value and of each gradient between the kernels and
    the token-by-token scan, relative to the reference's largest."""
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import selective_scan as S

    ops = operands(*shape, seed=1)
    w = jnp.asarray(np.random.RandomState(2).randn(*ops[0].shape),
                    jnp.float32)
    got = grads(S.selective_scan, w)(*ops)
    want = grads(S.selective_scan_reference, w)(*ops)
    gaps = {"y": abs(float(got[0]) - float(want[0])) / abs(float(want[0]))}
    for name, a, b in zip(("dx", "ddt", "dA", "dB", "dC", "dDskip"),
                          got[1], want[1]):
        gaps[name] = float(jnp.abs(a - b).max() / jnp.abs(b).max())
    return gaps


def time_one(shape, chunk, block_d, steps):
    import jax
    import jax.numpy as jnp

    from flash_kernel_times import kernel_us
    from horovod_tpu.ops import selective_scan as S

    ops = operands(*shape)
    f = grads(S.selective_scan, jnp.float32(1), chunk=chunk,
              block_d=block_d)
    jax.block_until_ready(f(*ops))           # compile + warm
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        for _ in range(steps):
            out = f(*ops)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        return kernel_us(d, kernels=KERNELS, calls=steps)


def rates(shape, us) -> dict:
    """ns a unit and 10 ** 12 vector operations a second for each kernel of
    ``us`` (name -> [mean us a call, events]); the padded channels of a
    last register count, the chip computes them."""
    B, T, Dn, N = shape
    units = B * T * N * -(-Dn // CHANNELS_A_UNIT)
    out = {"ns_a_unit": {}, "vector_tops": {}}
    for name, ops in OPS_A_UNIT.items():
        if name in us and us[name][0] > 0:
            ns = us[name][0] * 1e3 / units
            out["ns_a_unit"][name] = round(ns, 3)
            out["vector_tops"][name] = round(
                ops * CHANNELS_A_UNIT / ns / 1e3, 3)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1x8192x5120x16")
    ap.add_argument("--check-shape", default="2x512x1280x16")
    ap.add_argument("--blocks", default="64x1024,128x1024,64x2048,128x2048")
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("scan_kernel_times: needs a TPU", file=sys.stderr)
        return 2
    gaps = check(tuple(map(int, args.check_shape.split("x"))))
    gap = max(gaps.values())
    print(json.dumps({"check_shape": args.check_shape, "worst_gap": gap,
                      "gaps": gaps}), flush=True)
    shape = tuple(map(int, args.shape.split("x")))
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "scan_kernel_times.jsonl"),
              "a") as out:
        for block in args.blocks.split(","):
            chunk, block_d = map(int, block.split("x"))
            try:
                us = time_one(shape, chunk, block_d, args.steps)
            except Exception as e:           # a blocking the chip refuses
                us = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
            row = {"shape": args.shape, "chunk": chunk, "block_d": block_d,
                   "us": us, **rates(shape, us)}
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
    return 0 if gap <= 1e-4 else 1


if __name__ == "__main__":
    sys.exit(main())
