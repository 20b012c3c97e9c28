#!/usr/bin/env python3
"""Time of the dropless expert layer alone, by the load its experts get.

    chiprun -- python3 scripts/moe_layer_times.py [--loads 0.25,1,2,8] \
        [--module <other moe/layer.py>]

``hvd.moe_ffn_dropless`` forward and backward (value and gradients in x and
the three expert weights) at a model's sizes, under router logits made so
that the held experts get ``load`` x what uniform routing sends them
(N * K * held / E token-choices): a share ``load * held / E`` of the tokens
puts all K choices on held experts, the others none. Prints the mean host
time of a call once warm, for each load: whether the layer's time follows
the routing is read off the rows. ``--module FILE`` times another copy of
``moe/layer.py`` (the parent's, or a variant) beside the tree's. Defaults
are Keye-VL-2.0-30B-A3B's sizes on one chip of 64 (16,384 tokens of 2048, 16
of 128 experts of width 768 held, 8 a token). Needs a TPU (anything else:
exit 2). Rows also go to ``chiprun_out/moe_layer_times.jsonl``.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def load_module(path):
    if not path:
        from horovod_tpu.moe import layer as mod

        return mod
    # Under the package's name, so that the copy's relative imports hold.
    spec = importlib.util.spec_from_file_location(
        "horovod_tpu.moe._layer_other", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def logits_for(load, n, experts, held, k):
    """[n, experts] router logits: the first ``load * held / experts`` of
    the tokens choose k held experts (alternating halves of them), the rest
    k absent ones."""
    import numpy as np

    chosen = int(round(n * load * held / experts))
    out = np.zeros((n, experts), np.float32)
    for i in range(n):
        if i < chosen:
            at = (i % (held // k)) * k if held >= k else 0
        else:
            at = held + (i % ((experts - held) // k)) * k
        out[i, at:at + k] = 8.0 + np.linspace(0.0, 1.0, k)
    return out, chosen * k


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tokens", type=int, default=16384)
    ap.add_argument("--sizes", default="2048x768",
                    help="hidden x expert width")
    ap.add_argument("--experts", default="128x16x8",
                    help="router width x experts held x experts a token")
    ap.add_argument("--loads", default="0.25,1,2,8")
    ap.add_argument("--module", default="")
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("needs a TPU", file=sys.stderr)
        return 2
    mod = load_module(args.module)
    n = args.tokens
    c, f = (int(v) for v in args.sizes.split("x"))
    e, held, k = (int(v) for v in args.experts.split("x"))
    keys = jax.random.split(jax.random.key(0), 5)
    x = jax.random.normal(keys[0], (n, c), jnp.bfloat16)
    params = {"router": jnp.zeros((c, e), jnp.float32),
              "w1": 0.02 * jax.random.normal(keys[1], (held, c, f)),
              "w3": 0.02 * jax.random.normal(keys[2], (held, c, f)),
              "w2": 0.02 * jax.random.normal(keys[3], (held, f, c))}

    @jax.jit
    def step(x, params, logits):
        def loss(x, params):
            y, _ = mod.moe_ffn_dropless(x, params, experts_per_token=k,
                                        router_logits=logits)
            return jnp.sum(y.astype(jnp.float32) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1))(x, params)

    os.makedirs("chiprun_out", exist_ok=True)
    for load in (float(v) for v in args.loads.split(",") if v):
        logits, rows = logits_for(load, n, e, held, k)
        logits = jnp.asarray(logits)
        jax.block_until_ready(step(x, params, logits))     # compile + warm
        jax.block_until_ready(step(x, params, logits))
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = step(x, params, logits)
        jax.block_until_ready(out)
        row = {"module": args.module or "tree", "load_over_uniform": load,
               "rows_of_held_experts": rows,
               "ms_forward_and_backward": round(
                   (time.perf_counter() - t0) / args.iters * 1e3, 3)}
        print(json.dumps(row), flush=True)
        with open("chiprun_out/moe_layer_times.jsonl", "a") as fh:
            fh.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
