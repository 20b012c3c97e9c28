#!/usr/bin/env python3
"""Cut a piece of the newest traced benchmark run into a small JSON file
that the CPU tests can read back (tests/benchmark/data/*.json.gz).

    python3 benchmarks/run.py --workload <cell> --seed <n> --trace 1
    python3 scripts/record_trace_piece.py --out chiprun_out/piece.json.gz \
        --origin "my chip run, PR n: <cell> --trace 1 --seed <n> ..." [--steps 3,4]

Reads the newest ``.xplane.pb`` under ``.bench_trace/`` (what the traced
run left), keeps the first device's ops that lie inside the traced steps
``--steps`` (two of the twelve by default), drops zero-length events, cuts
op names to 90 characters, and writes ``lib/trace.py: Trace.to_json()``
with each op's scope path under ``paths`` (what
``lib/scopes.py: ScopedOps.from_json`` reads) and ``origin``. Runs where
the trace is, on the chip's machine: only ``chiprun_out/`` comes back.
"""

from __future__ import annotations

import argparse
import gzip
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    ap.add_argument("--origin", required=True)
    ap.add_argument("--steps", default="3,4")
    ap.add_argument("--name-chars", type=int, default=90)
    args = ap.parse_args(argv)
    from benchmarks.lib import scopes, trace as tr

    path = scopes.newest_xplane()
    if path is None:
        print("record_trace_piece: no traced run under .bench_trace",
              file=sys.stderr)
        return 1
    whole = tr.load_xplane(path)
    device = min(whole.ops)
    steps = tr.steps(whole, device)
    first, last = (int(s) for s in args.steps.split(","))
    start, end = steps[first][0], steps[last][1]
    ops = [op for op in scopes.load(path)
           if op[2] > 0 and op[1] >= start and op[1] + op[2] <= end]
    piece = tr.Trace(
        {device: [(op[0][:args.name_chars], op[1], op[2]) for op in ops]},
        {device: []},
        {device: [m for m in whole.modules[device]
                  if m[1] >= start and m[1] + m[2] <= end + 1e-9]},
        [h for h in whole.host if h[1] >= start and h[1] + h[2] <= end],
        (start, end))
    d = piece.to_json()
    d["paths"] = {str(device): [op[3] for op in ops]}
    d["origin"] = (f"{args.origin}; traced steps {first}..{last} of "
                   f"{len(steps)}, device {device}; zero-length events "
                   f"dropped, op names cut to {args.name_chars} characters, "
                   f"each op's scope path under 'paths'")
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with gzip.open(args.out, "wt") as f:
        json.dump(d, f, separators=(",", ":"))
    print(f"record_trace_piece: {len(ops)} ops of steps {first}..{last} -> "
          f"{args.out} ({os.path.getsize(args.out) / 1e3:.0f} kB)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
