#!/usr/bin/env python3
"""Token-choices the held experts get, layer by layer, at seeded weights.

    chiprun -- python3 scripts/moe_load_by_layer.py --workload <cell> --seeds 1,2,3 [--steps 20]

One forward pass of a cell's model on its first batch for each seed (after
``--steps`` training steps of the cell's own compiled step, where given: a
family whose routers carry a balancing bias as state moves it every step,
and the load after some steps is the bias at work); prints,
per layer, the held experts' token-choices as a multiple of what uniform
routing gives them (N * K * held / E). A layer that computes the filled
row tiles alone takes time by these counts, and their spread over seeds
was the cell's spread (PERF.md, PR 26); `moe_ffn_dropless` now runs every
tile whatever they are. Runs on whatever JAX finds; no time is printed.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--steps", type=int, default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import jax
    import numpy as np

    from benchmarks.lib import manifest as mf

    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    config = mf.config_of(manifest, cell["config"])
    session = mf.load_module("builders", config["builder"]).build(
        config, mf.job_of(cell["traffic"]), jax.devices()[:cell["chips"]])
    s = session.sizes
    first, held = s["expert_first"], s["experts_held"]
    uniform = session.tokens_per_step * s["top_k"] * held / s["experts"]

    @jax.jit
    def loads(params, biases, x):
        variables = {"params": params}
        if biases is not None:      # the routers' selection biases
            variables["router_bias"] = biases
        _, state = session.model.apply(variables, x,
                                       mutable=["intermediates"])
        return {k: v["moe"]["moe_expert_load"][0]
                for k, v in state["intermediates"].items() if "moe" in v}

    for seed in (int(x) for x in args.seeds.split(",") if x):
        session.init_state(seed)
        session.place_inputs(seed)
        if args.steps:
            if session.compiled is None:
                session.compile()
            for _ in range(args.steps):
                loss = session.step()
            jax.block_until_ready(loss)
        out = jax.device_get(loads(session.params,
                                   getattr(session, "biases", None),
                                   session.pool[0][0]))
        row = {"seed": seed, "steps": args.steps,
               "held_load_over_uniform": {
                   k: round(float(np.sum(v[first:first + held]) / uniform), 3)
                   for k, v in sorted(out.items())}}
        print(json.dumps(row), flush=True)
        session.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
