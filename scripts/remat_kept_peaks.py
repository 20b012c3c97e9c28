#!/usr/bin/env python3
"""What a mixture cell's compiled step holds at each set of values its
rematerialised blocks could keep (``models/sparse_moe_decoder.py``
``remat_kept``), compiled for a DESCRIBED v5e with no chip attached.

    JAX_PLATFORMS=cpu python3 scripts/remat_kept_peaks.py --workload <cell> [--sets 0,3,6]

For each k it makes the rule keep the first k candidates of its own order
and runs ``benchmarks/rehearse_compile.py`` (whose lines give
``compiled.memory_analysis()``); before that, the candidates' bytes over
all layers and the set the rule itself chooses at ``KEEP_SHARE``. A cell
compiles in 1 to 3 minutes of sandbox CPU a set; nothing runs, so it gives
bytes and never a time. ``KEEP_SHARE`` was fixed from these lines
(PERF.md, PR 38).
"""

from __future__ import annotations

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", default="",
                    help="how many candidates to keep, comma-separated "
                         "(default: every prefix)")
    args = ap.parse_args(argv)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, ROOT)

    from benchmarks import rehearse_compile
    from benchmarks.lib import manifest as mf
    from horovod_tpu.models import sparse_moe_decoder as decoder

    manifest = mf.load()
    cell = mf.cell(manifest, args.workload)
    config = mf.config_of(manifest, cell["config"])
    shape = (decoder.SparseMoEConfig.from_dict(config),
             config["per_chip_batch"], mf.job_of(cell["traffic"])["seq_len"])
    candidates = decoder.remat_candidates(*shape)
    print(f"[peaks] {args.workload}: kept anyway "
          f"{decoder.remat_kept_anyway(*shape) / 1e9:.3f} GB; candidates "
          f"{ {n: round(sum(by) / 1e9, 3) for n, by in candidates.items()} }"
          f" GB; the rule keeps {list(decoder.remat_kept(*shape))}",
          flush=True)
    rule = decoder.remat_kept
    for k in ([int(k) for k in args.sets.split(",")] if args.sets
              else range(len(candidates) + 1)):
        kept = dict(list(candidates.items())[:k])
        print(f"[peaks] keep {k}: {list(kept)}", flush=True)
        decoder.remat_kept = lambda *a, **kw: kept
        try:
            rehearse_compile.main(["--workload", args.workload])
        finally:
            decoder.remat_kept = rule
    return 0


if __name__ == "__main__":
    sys.exit(main())
