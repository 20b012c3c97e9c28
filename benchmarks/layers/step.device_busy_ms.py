"""Device trace: union of the op intervals inside each step's program on
the first device, median over the traced steps."""

import statistics

from benchmarks.lib import trace as tr

NAME, UNIT = "step.device_busy_ms", "ms"
LAYER, MOVES = "Step", "tokens_per_s_per_chip"


def read(run):
    if run.trace is None:
        return None
    busy = tr.step_busy_seconds(run.trace, min(run.trace.ops))
    if not busy:
        return None
    run.note(f"{NAME}: {len(busy)} traced steps")
    return statistics.median(busy) * 1e3
