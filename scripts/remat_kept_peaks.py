#!/usr/bin/env python3
"""What a cell's compiled step holds at each set of values its
rematerialised blocks could keep (``models/sparse_moe_decoder.py``
``kept_within``, which the mixture decoder and ``models/sambay.py`` both
call), compiled for a DESCRIBED v5e with no chip attached.

    JAX_PLATFORMS=cpu python3 scripts/remat_kept_peaks.py --workload <cell> [--sets 0,3,whole,rule]

A set is a number k (the first k candidates of the rule's own order, each
for every layer), ``rule`` (what the rule keeps at ``KEEP_SHARE``) or
``whole`` (the rule's names that it keeps for every layer they are in: the
rule as it stood before PR 47, which kept a candidate for all its layers
or for none). For each it puts the set in the rule's place and runs
``benchmarks/rehearse_compile.py`` (whose lines give
``compiled.memory_analysis()``); the model's own call of the rule says what
is kept anyway, the candidates' bytes over all layers and, by name, the
bytes kept and the layers they are kept for. A cell compiles in 1 to 3
minutes of sandbox CPU a set; nothing runs, so it gives bytes and never a
time. ``KEEP_SHARE`` was fixed from these lines (PERF.md, PR 38).
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _gb(by_name: dict) -> dict:
    return {name: round(sum(by) / 1e9, 3) for name, by in by_name.items()}


def choose(which: str, candidates: dict, ruled: dict) -> dict:
    """The set ``which`` names, in ``kept_within``'s form."""
    if which == "rule":
        return ruled
    if which == "whole":
        return {name: by for name, by in ruled.items()
                if by == candidates[name]}
    return dict(list(candidates.items())[:int(which)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", default="",
                    help="comma-separated: a number of candidates to keep "
                         "whole, 'whole', 'rule' (default: the rule, then "
                         "every prefix)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)

    from benchmarks import rehearse_compile
    from horovod_tpu.models import sambay
    from horovod_tpu.models import sparse_moe_decoder as decoder

    rule = decoder.kept_within
    sets = args.sets.split(",") if args.sets else None
    seen = {}

    def standing_in(which):
        def kept_within(candidates, anyway, memory_bytes=None):
            kept = choose(which, candidates, rule(candidates, anyway,
                                                  memory_bytes))
            if which not in seen:
                seen[which] = len(candidates)
                layers = {name: [i for i, b in enumerate(by) if b]
                          for name, by in kept.items()}
                print(f"[peaks] {args.workload}: kept anyway "
                      f"{anyway / 1e9:.3f} GB; candidates {_gb(candidates)} "
                      f"GB\n[peaks] keep {which}: {_gb(kept)} GB, "
                      f"{sum(map(sum, kept.values())):,} bytes, in layers "
                      f"{layers}", flush=True)
            return kept
        return kept_within

    def run(which):
        decoder.kept_within = sambay.kept_within = standing_in(which)
        try:
            rehearse_compile.main(["--workload", args.workload])
        finally:
            decoder.kept_within = sambay.kept_within = rule

    if sets is None:
        run("rule")
        if not seen:
            print(f"[peaks] {args.workload}: its model never asks the rule "
                  f"(kept_within), so there is no set to put in its place",
                  file=sys.stderr)
            return 1
        sets = [str(k) for k in range(seen["rule"] + 1)]
    for which in sets:
        run(which)
    return 0


if __name__ == "__main__":
    sys.exit(main())
