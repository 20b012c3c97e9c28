"""Elastic-on-TPU smoke: shutdown→init cycles on the attached chip.

The elastic path's rendezvous logic is covered on the CPU mesh by
tests/test_elastic_integration.py; what only hardware shows is the cost
of a re-init cycle, world of 1:

  cycle i:  hvd.init() → jit'd train step (compile on cycle 0, the
            compilation cache must serve later cycles) → N steps →
            hvd.shutdown()

and reports per-cycle compile/step timings as one JSON line. One process
holds the chip throughout.

Run:  python examples/elastic_tpu_smoke.py [--cycles 3] [--steps 20]
Reference anchor: the reference's elastic driver re-forms NCCL contexts
on every world change (horovod/common/operations.cc shutdown path +
elastic/driver re-rendezvous); this is the TPU analogue of that teardown
churn.
"""

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

import _path_setup  # noqa: F401  (repo root onto sys.path)
import horovod_tpu as hvd
from horovod_tpu.models import GPT, gpt_tiny


def one_cycle(cycle: int, steps: int):
    t0 = time.perf_counter()
    hvd.init()
    init_s = time.perf_counter() - t0

    cfg = gpt_tiny()
    rs = np.random.RandomState(cycle)
    toks = rs.randint(0, cfg.vocab_size, (8, 129))
    x, y = jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])
    model = GPT(cfg)
    variables = model.init(jax.random.PRNGKey(0), x[:1])
    tx = optax.adam(1e-3)
    opt = tx.init(variables["params"])

    @jax.jit
    def step(p, o, xb, yb):
        def loss_fn(p):
            out = model.apply({"params": p}, xb)
            return optax.softmax_cross_entropy_with_integer_labels(
                out, yb).mean()

        l, g = jax.value_and_grad(loss_fn)(p)
        u, o = tx.update(g, o, p)
        return jax.tree.map(lambda a, b: a + b, p, u), o, l

    t0 = time.perf_counter()
    p, opt, loss = step(variables["params"], opt, x, y)
    float(loss)
    compile_s = time.perf_counter() - t0

    t0 = time.perf_counter()
    for _ in range(steps):
        p, opt, loss = step(p, opt, x, y)
    last = float(loss)  # fetch drains the chain
    steps_s = time.perf_counter() - t0

    hvd.shutdown()
    return {"cycle": cycle, "init_s": round(init_s, 3),
            "compile_s": round(compile_s, 2),
            "steps_s": round(steps_s, 3),
            "step_ms": round(steps_s / steps * 1e3, 2),
            "loss": round(last, 4)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cycles", type=int, default=3)
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()

    # hvd.init() arms the persistent compilation cache (compile/cache.py:
    # JAX_COMPILATION_CACHE_DIR, else the checkout's .compile_cache/) —
    # jit caches are per-Python-function, so only the on-disk cache
    # survives a cycle.
    devices = jax.devices()
    platform = devices[0].platform
    print(f"platform={platform} device={devices[0].device_kind}")

    results = []
    for c in range(args.cycles):
        r = one_cycle(c, args.steps)
        results.append(r)
        print(f"cycle {c}: init {r['init_s']}s compile {r['compile_s']}s "
              f"{args.steps} steps {r['steps_s']}s "
              f"({r['step_ms']} ms/step) loss {r['loss']}")

    # Later cycles must reuse the compilation cache: a conservative 2x
    # bound (identical program; only the RNG data differs). Asserted on
    # TPU only — on the CPU mesh this is a logic check and its timings
    # say nothing about the device.
    if len(results) > 1 and platform == "tpu":
        warm = min(r["compile_s"] for r in results[1:])
        assert warm < max(2.0, 0.5 * results[0]["compile_s"]), (
            "compile cache not reused across re-init: "
            f"cold {results[0]['compile_s']}s vs warm {warm}s")
    print(json.dumps({"metric": "elastic_smoke_cycles",
                      "value": len(results), "unit": "cycles",
                      "platform": platform,
                      "cycles": results}))


if __name__ == "__main__":
    main()
