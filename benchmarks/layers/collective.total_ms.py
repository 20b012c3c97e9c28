"""Device trace, first device: summed durations of the all-reduce /
reduce-scatter / all-gather ops per step, median over the traced steps.
Nothing to read on one chip."""

import statistics

from benchmarks.lib import trace as tr

NAME, UNIT = "collective.total_ms", "ms"
LAYER, MOVES = "Collectives", "tokens_per_s_per_chip"


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    total, _ = tr.collective_seconds(run.trace, min(run.trace.ops))
    if not total:
        return None
    return statistics.median(total) * 1e3
