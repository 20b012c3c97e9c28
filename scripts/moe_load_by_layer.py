#!/usr/bin/env python3
"""Token-choices the held experts get, layer by layer, at seeded weights.

    chiprun -- python3 scripts/moe_load_by_layer.py --workload <cell> --seeds 1,2,3 [--steps 20]

For each seed, one forward pass of a cell's model on the batch its next
step takes, before each of ``--steps`` training steps of the cell's own
compiled step and once after them (a family whose routers carry a balancing
bias as state moves it every step: the later rows are the bias at work);
prints, a step and a layer, the held experts' token-choices as a multiple
of what uniform routing gives them (N * K * held / E) and the share of the
row buffer they fill, ``moe.rows_filled / moe.rows_grouped`` (also set as
the gauge ``moe.rows_filled_share``): ``moe_ffn_dropless`` walks the filled
rows alone, so its time follows these numbers (PERF.md, PR 31). Runs on
whatever JAX finds; no time is printed.
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
    from horovod_tpu import moe
    from horovod_tpu.monitor.registry import gauge

    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    config = mf.config_of(manifest, cell["config"])
    session = mf.load_module("builders", config["builder"]).build(
        config, mf.job_of(cell["traffic"]), jax.devices()[:cell["chips"]])
    s = session.sizes
    first, held = s["expert_first"], s["experts_held"]
    choices = session.tokens_per_step * s["top_k"]
    uniform = choices * held / s["experts"]
    grouped = moe.rows_grouped(choices, held)

    @jax.jit
    def loads(params, biases, x):
        variables = {"params": params}
        if biases is not None:      # the routers' selection biases
            variables["router_bias"] = biases
        _, state = session.model.apply(variables, x,
                                       mutable=["intermediates"])
        return {k: v["moe"]["moe_expert_load"][0]
                for k, v in state["intermediates"].items() if "moe" in v}

    def row(seed, step):
        x = session.pool[session.cursor % len(session.pool)][0]
        out = dict(sorted(jax.device_get(loads(
            session.params, getattr(session, "biases", None), x)).items()))
        share = {k: float(moe.rows_filled(v, first, held)) / grouped
                 for k, v in out.items()}
        for k, v in share.items():
            gauge("moe.rows_filled_share", layer=k).set(v)
        print(json.dumps({
            "seed": seed, "step": step,
            "held_load_over_uniform": {
                k: round(float(np.sum(v[first:first + held]) / uniform), 3)
                for k, v in out.items()},
            "held_expert_least_most": {
                k: [round(float(f(v[first:first + held]) * held / uniform), 3)
                    for f in (np.min, np.max)]
                for k, v in out.items()},
            "rows_filled_share": {k: round(v, 4) for k, v in share.items()},
            "rows_grouped": grouped}), flush=True)

    for seed in (int(x) for x in args.seeds.split(",") if x):
        session.init_state(seed)
        session.place_inputs(seed)
        if args.steps and session.compiled is None:
            session.compile()
        for step in range(args.steps):
            row(seed, step)
            jax.block_until_ready(session.step())
        row(seed, args.steps)
        session.release()
    return 0


if __name__ == "__main__":
    sys.exit(main())
