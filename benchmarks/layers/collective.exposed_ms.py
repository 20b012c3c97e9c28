"""Device trace, first device: the part of the collective ops' intervals in
which no other op runs on that device, per step, median over the traced
steps. Nothing to read on one chip."""

import statistics

from benchmarks.lib import trace as tr

NAME, UNIT = "collective.exposed_ms", "ms"
LAYER, MOVES = "Collectives", "tokens_per_s_per_chip"


def read(run):
    if run.trace is None or run.chips < 2:
        return None
    _, exposed = tr.collective_seconds(run.trace, min(run.trace.ops))
    if not exposed:
        return None
    return statistics.median(exposed) * 1e3
