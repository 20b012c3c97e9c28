"""Median time the call that enqueues one step takes to return to the
host (the benchmark's own span around it)."""

import statistics

NAME, UNIT = "step.dispatch_ms", "ms"
LAYER, MOVES = "Step", "tokens_per_s_per_chip"


def read(run):
    if not run.dispatch_s:
        return None
    return statistics.median(run.dispatch_s) * 1e3
