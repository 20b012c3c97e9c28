"""90th percentile of the interval between consecutive step completions in
the traced window, in the cells whose step follows its routing: there the
timed window's ``step_ms_p90`` swings with the seed by more than any bound
can hold (PERF.md, PR 35), so it stands here, unbounded, and not among the
end-to-end metrics."""

import statistics

NAME, UNIT = "step.interval_p90_ms", "ms"
LAYER, MOVES = "Step", "tokens_per_s_per_chip"


def read(run):
    intervals = run.intervals()
    if len(intervals) < 10:
        return None
    return statistics.quantiles(intervals, n=10, method="inclusive")[8] * 1e3
