#!/usr/bin/env python3
"""Print the newest traced run's busy time BY OWNER (benchmarks/lib/owners.py:
the partition line a traced run prints itself where one of its cell's
readers asks for it), for a cell whose metric lists hold no owner metric.

    python3 scripts/trace_owners.py [file.xplane.pb]

Reads ``.bench_trace/``'s newest ``.xplane.pb`` (or the file given), as
``python3 -m benchmarks.lib.scopes`` does; no chip, no second compile: the
program's text is the trace's own.
"""

from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sys.path.insert(0, ROOT)
    from benchmarks.lib import owners, scopes, trace as tr

    path = argv[0] if argv else scopes.newest_xplane()
    if path is None:
        print("no .xplane.pb under .bench_trace/", file=sys.stderr)
        return 1
    if argv:     # owners reads the newest file: make the one given that
        scopes.newest_xplane = lambda: path
    run = types.SimpleNamespace(trace=tr.load_xplane(path),
                                note=lambda text: print(f"[metric] {text}"))
    return 0 if owners.of(run) is not None else 1


if __name__ == "__main__":
    sys.exit(main())
