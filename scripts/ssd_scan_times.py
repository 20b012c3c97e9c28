#!/usr/bin/env python3
"""Time Mamba-2's chunked scan (ops/ssd_scan.py) alone on the chip, and
hold it against the token-by-token recurrence there.

    chiprun -- python3 scripts/ssd_scan_times.py [--shape 1x8192x16x64] [--groups 1] [--states 128] [--chunks 64,128,256]

First the values and the six gradients of ``hvd.ssd_scan`` against
``ssd_scan_reference`` at 1,024 tokens (bfloat16 operands as the model
hands them, float32 in the reference: the worst relative error by
operand). Then, a chunk size a row: milliseconds of one forward and of one
forward + backward at the shape given (the cell's by default), by the
host's clock around ``block_until_ready`` over ``--reps`` calls of one
jitted function each (a device number: nothing else runs), beside the
least times ``benchmarks/lib/kernels_ssd.py`` gives. Rows also go to
``chiprun_out/ssd_scan_times.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def operands(key, B, T, h, P, G, N, dtype):
    import jax
    import jax.numpy as jnp

    ks = jax.random.split(key, 6)
    return (jax.random.normal(ks[0], (B, T, h, P), dtype),
            jax.nn.softplus(jax.random.normal(ks[1], (B, T, h)) - 3.0),
            jnp.log(jax.random.uniform(ks[2], (h,), minval=1., maxval=16.)),
            jax.random.normal(ks[3], (B, T, G, N), dtype),
            jax.random.normal(ks[4], (B, T, G, N), dtype),
            jnp.ones((h,), jnp.float32))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1x8192x16x64")
    ap.add_argument("--groups", type=int, default=1)
    ap.add_argument("--states", type=int, default=128)
    ap.add_argument("--chunks", default="64,128,256")
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp

    from benchmarks.lib import kernels, kernels_ssd, peaks
    from horovod_tpu.ops.ssd_scan import ssd_scan, ssd_scan_reference

    dev = jax.devices()[0]
    print(f"device {dev.platform} {dev.device_kind}")
    B, T, h, P = (int(v) for v in args.shape.split("x"))
    G, N = args.groups, args.states

    # -- values and gradients against the token loop ------------------------
    ops = operands(jax.random.key(1), B, 1024, h, P, G, N, jnp.bfloat16)
    w = jax.random.normal(jax.random.key(2), (B, 1024, h, P))

    def loss(fn):
        return lambda *a: (fn(*a).astype(jnp.float32) * w).sum()

    got = jax.jit(jax.value_and_grad(
        loss(lambda *a: ssd_scan(*a, chunk=128)), argnums=range(6)))(*ops)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(jax.value_and_grad(
            loss(ssd_scan_reference), argnums=range(6)))(*ops)
    names = ("xs", "dt", "A_log", "B", "C", "Dskip")
    errs = {n: float(jnp.abs(a.astype(jnp.float32) - b).max()
                     / (jnp.abs(b).max() + 1e-30))
            for n, a, b in zip(names, got[1], want[1])}
    print(f"[values] loss {float(got[0]):.4f} vs {float(want[0]):.4f}; "
          f"worst relative gradient error by operand {errs}")

    # -- times ---------------------------------------------------------------
    peak = peaks.for_device_kind(dev.device_kind) \
        if dev.platform == "tpu" else None
    ops = operands(jax.random.key(3), B, T, h, P, G, N, jnp.bfloat16)
    w = jax.random.normal(jax.random.key(4), (B, T, h, P), jnp.bfloat16)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)

    def timed(fn):
        jax.block_until_ready(fn(*ops))
        laps = []
        for _ in range(args.reps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*ops))
            laps.append(time.perf_counter() - t0)
        return statistics.median(laps) * 1e3

    with open(os.path.join(out, "ssd_scan_times.jsonl"), "a") as f:
        for chunk in (int(c) for c in args.chunks.split(",")):
            fwd = jax.jit(lambda *a: ssd_scan(*a, chunk=chunk))
            both = jax.jit(jax.grad(
                lambda *a: (ssd_scan(*a, chunk=chunk) * w).sum()
                .astype(jnp.float32), argnums=range(6)))
            row = {"shape": args.shape, "groups": G, "states": N,
                   "chunk": chunk, "device": dev.device_kind,
                   "fwd_ms": timed(fwd), "fwd_bwd_ms": timed(both)}
            if peak is not None:
                shape = dict(batch=B, seq=T, heads=h, head_dim=P, groups=G,
                             d_state=N, chunk=chunk)
                row["fwd_least_ms"] = 1e3 * kernels.roofline(
                    *kernels_ssd.ssd_fwd_cost(**shape), peak)[0]
                row["bwd_least_ms"] = 1e3 * kernels.roofline(
                    *kernels_ssd.ssd_bwd_cost(**shape), peak)[0]
            print(json.dumps(row), flush=True)
            f.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
