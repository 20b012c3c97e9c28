#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the TPU(s) of the machine it is
started on and prints, as the last line of stdout, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, traced,
``breakdown``. Every other number is an earlier line. Anything but a TPU
with as many chips as the cell asks for is exit code 2 and no result line;
nothing falls back to the CPU.

Holds no configuration, job, cell or metric name (benchmarks/README.md says
where each lives). The compile cache is ``.compile_cache/`` in the checkout
whatever the environment says: the path is part of JAX's cache key, and two
checkouts must share nothing.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE = os.path.join(ROOT, ".compile_cache")
NO_CHIP = 2


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None,
                    help="length of the measured window (default: the "
                         "manifest's run_seconds)")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--presweep", action="store_true",
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def chips_or_none(chips: int):
    """The first ``chips`` TPU devices, or None where JAX finds no TPU or
    fewer than the cell asks for."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"benchmark: needs {chips} TPU chip(s), JAX found "
              f"{len(devices)} x {devices[0].platform}; nothing is measured "
              f"on anything else", file=sys.stderr)
        return None
    return devices[:chips]


def place_cache() -> None:
    """The compile cache is this checkout's own, and keeps all it is given:
    a size limit from the environment made every run evict the step that
    the next one needs (PERF.md, PR 23)."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    os.environ["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"


def presweep_marker(cell_name: str) -> str:
    return os.path.join(CACHE, f"presweep.{cell_name}.done")


def presweep(cell_name: str) -> int:
    """Child process, cold checkout only: trace the cell's step once so that
    the program's kernel block sweep (ops/kernel_autotune.py, at trace
    time) writes its choices to ``kernel_autotune.json``, then exit and
    free the chip. The measuring process then traces with blocks read from
    the file, as every later run does: a step compiled in a process that
    swept in-process has another cache key (PERF.md, PR 21) and the second
    run of the cell would compile again."""
    from benchmarks.lib import manifest as mf

    manifest = mf.load()
    cell = mf.cell(manifest, cell_name)
    devices = chips_or_none(cell["chips"])
    if devices is None:
        return NO_CHIP
    config = mf.config_of(manifest, cell["config"])
    session = mf.load_module("builders", config["builder"]).build(
        config, mf.job_of(cell["traffic"]), devices)
    session.lower(session.abstract_args())
    os.makedirs(CACHE, exist_ok=True)
    with open(presweep_marker(cell_name), "w") as f:
        f.write("swept\n")
    return 0


def main(argv=None) -> int:
    args = parse(argv)
    sys.path.insert(0, ROOT)
    place_cache()
    if args.presweep:
        return presweep(args.workload)

    from benchmarks.lib import manifest as mf

    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    if not os.path.exists(presweep_marker(args.workload)):
        # Before this process imports JAX: one process per chip at a time.
        child = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--workload",
             args.workload, "--presweep"], stdout=sys.stderr)
        if child.returncode:
            return child.returncode

    from benchmarks.lib import harness

    devices = chips_or_none(cell["chips"])
    if devices is None:
        return NO_CHIP
    seconds = manifest["run_seconds"] if args.seconds is None \
        else args.seconds
    result = harness.run_cell(args.workload, seed=args.seed, seconds=seconds,
                              trace=bool(args.trace), devices=devices,
                              manifest=manifest)
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
