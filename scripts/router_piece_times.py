#!/usr/bin/env python3
"""What the pieces of ``moe_router`` and of ``moe_ffn_dropless``'s plan cost
alone on the chip (ROADMAP S13(h); PERF.md, PR 38).

    chiprun -- python3 scripts/router_piece_times.py

One JSON line a token count (16,384 and 8,192; 128 experts, 8 a token):
microseconds on the host's clock around ONE jitted op, the first quartile of
30 calls; ``noop`` (an elementwise pass over the scores) is what a call
costs anyway, so read every other row less that one. ``onehot_read`` and
``onehot_scatter`` give the same numbers as ``take_along_axis`` and its
transpose (one nonzero term a sum).
"""

import json
import time

import jax
import jax.numpy as jnp
from jax import lax

E, K = 128, 8


def timed(f, *args, n=30):
    f = jax.jit(f)
    jax.block_until_ready(f(*args))
    ts = []
    for _ in range(n):
        t0 = time.perf_counter()
        jax.block_until_ready(f(*args))
        ts.append(time.perf_counter() - t0)
    return round(1e6 * sorted(ts)[n // 4], 1)


def main() -> None:
    for N in (16384, 8192):
        probs = jax.nn.softmax(
            jax.random.normal(jax.random.key(0), (N, E)), -1)
        idx = lax.top_k(probs, K)[1]
        w = jax.random.normal(jax.random.key(1), (N, K))
        key = jax.random.randint(jax.random.key(2), (N * K,), 0, 17)

        def read(p, i):
            return jnp.take_along_axis(p, i, -1)

        rows = {
            "noop": timed(lambda p: p + 1.0, probs),
            "top_k": timed(lambda p: lax.top_k(p, K), probs),
            "take_along_axis": timed(read, probs, idx),
            "onehot_read": timed(lambda p, i: jnp.sum(jax.nn.one_hot(
                i, E, dtype=p.dtype) * p[:, None, :], -1), probs, idx),
            "take_along_axis_transpose": timed(jax.grad(
                lambda p, i, w: (read(p, i) * w).sum()), probs, idx, w),
            "onehot_scatter": timed(lambda i, w: jnp.sum(jax.nn.one_hot(
                i, E, dtype=w.dtype) * w[..., None], -2), idx, w),
            "sort_choices": timed(lambda k: lax.sort(
                (k, jnp.arange(N * K, dtype=jnp.int32)), num_keys=1), key),
        }
        print(json.dumps({"tokens": N, "device": jax.devices()[0].device_kind,
                          "us": rows}), flush=True)


if __name__ == "__main__":
    main()
