#!/usr/bin/env python3
"""Read, on the chip and at the cell's own size, what the comparison that
decides ``correct`` says of two faults of SmallThinker's own, each put in
the program's place and held against the plain reference:

* ``silu``: SiLU where the experts' ReLU belongs
  (``expert_activation="silu"``);
* ``mlp_input``: the router fed the normed stream after attention, where
  the other families' routers read (``router_input="mlp_input"``).

    python3 scripts/smallthinker_faults.py --workload <cell> --seeds 1,2

One process; the step is compiled once a fault, the reference once. Prints a
JSON line a (seed, fault) with the three compared numbers beside the cell's
limits and writes them to ``chiprun_out/faults.<cell>.json``. ``--root``
and ``--any-device`` serve the CPU test at a tiny size.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTS = {"silu": {"expert_activation": "silu"},
          "mlp_input": {"router_input": "mlp_input"}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--root", default=ROOT)
    ap.add_argument("--any-device", action="store_true")
    ap.add_argument("--dir", default=os.path.join(ROOT, "chiprun_out"))
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import run as cli

    if not args.any_device:
        cli.place_cache()
    import jax

    from benchmarks.lib import compare, harness, manifest as mf
    from horovod_tpu.models import SparseMoEDecoder

    manifest = mf.load(args.root)
    cell = mf.cell(manifest, args.workload)
    devices = (jax.devices()[:cell["chips"]] if args.any_device
               else cli.chips_or_none(cell["chips"]))
    if devices is None:
        return cli.NO_CHIP
    config = mf.config_of(manifest, cell["config"], args.root)
    limits = mf.limits_of(args.workload, args.root)
    session = mf.load_module("builders", config["builder"]).build(
        config, mf.job_of(cell["traffic"], args.root), devices)
    sound_cfg = session.model_cfg
    seeds = [int(s) for s in args.seeds.split(",") if s]
    rows = []
    for fault in (f for f in args.faults.split(",") if f):
        session.model_cfg = dataclasses.replace(sound_cfg, **FAULTS[fault])
        session.model = SparseMoEDecoder(session.model_cfg)
        session._build()
        session.compiled = None
        for seed in seeds:
            session.init_state(seed)
            session.place_inputs(seed)
            if session.compiled is None:
                session.compile()
            program = harness.checked_steps(session, seed, limits["steps"])
            session.release()
            reference = session.reference(seed, limits["steps"])
            row = {"seed": seed, "fault": fault,
                   "loss_program": program["loss"],
                   "loss_reference": reference["loss"]}
            for name, value, limit, ok, note in compare.judge(
                    program, reference, limits):
                row[name] = [value, limit, ok, note]
            row["correct"] = all(row[n][2] for n in compare.NUMBERS)
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs(args.dir, exist_ok=True)
    with open(os.path.join(args.dir, f"faults.{args.workload}.json"),
              "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
