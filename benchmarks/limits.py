#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the two numbers each limit
of ``benchmarks/limits/<cell>.json`` is set from.

    python3 benchmarks/limits.py --workload <cell> --seeds 1,2,...,12 --control-seeds 1,2,3

One process, one compile. For every seed: the program's checked first steps
against the plain reference (a SOUND reading); for the control seeds also
the CONTROL: the reference computed with float8 matmul operands, the
nearest precision below the bfloat16 the configuration states, put in the
program's place. A limit goes above the sound runs' largest and below the
control's smallest (PERF.md gives the readings). The benchmark's own runs
never run this; ``tests/benchmark`` keeps the control at a size a test run
can hold.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from benchmarks import run as cli

    cli.place_cache()
    from benchmarks.lib import compare, harness, manifest as mf

    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    devices = cli.chips_or_none(cell["chips"])
    if devices is None:
        return cli.NO_CHIP
    config = mf.config_of(manifest, cell["config"])
    steps = mf.limits_of(args.workload)["steps"]
    session = mf.load_module("builders", config["builder"]).build(
        config, mf.job_of(cell["traffic"]), devices)
    seeds = [int(s) for s in args.seeds.split(",") if s]
    control_seeds = {int(s) for s in args.control_seeds.split(",") if s}
    sound: dict = {n: [] for n in compare.NUMBERS}
    control: dict = {n: [] for n in compare.NUMBERS}

    def report(row, numbers, reference, into):
        for name, (value, note) in compare.readings(numbers,
                                                    reference).items():
            row[name] = [value, note]
            into[name].append(value)
        print(json.dumps(row), flush=True)

    for seed in seeds:
        t0 = time.perf_counter()
        session.init_state(seed)
        session.place_inputs(seed)
        if session.compiled is None:
            session.compile()
        program = harness.checked_steps(session, seed, steps)
        session.release()
        t1 = time.perf_counter()
        reference = session.reference(seed, steps)
        t2 = time.perf_counter()
        row = {"seed": seed, "kind": "sound",
               "program_s": round(t1 - t0, 2),
               "reference_s": round(t2 - t1, 2),
               "loss_program": program["loss"],
               "loss_reference": reference["loss"]}
        report(row, program, reference, sound)
        if seed in control_seeds:
            low = session.reference(seed, steps, precision="float8")
            row = {"seed": seed, "kind": "control",
                   "control_s": round(time.perf_counter() - t2, 2),
                   "loss_control": low["loss"]}
            report(row, low, reference, control)
    summary = {"workload": args.workload, "seeds": seeds,
               "control_seeds": sorted(control_seeds)}
    for name in compare.NUMBERS:
        summary[name] = {
            "sound_max": max(sound[name]),
            "control_min": min(control[name]) if control[name] else None}
    print(json.dumps(summary), flush=True)
    out = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"limits.{args.workload}.json"), "w") as f:
        json.dump(summary, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
