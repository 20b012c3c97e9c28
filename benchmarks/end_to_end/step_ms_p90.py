"""90th percentile of the interval between consecutive step completions in
the window: the step that hiccups (a recompile, a host stall, a garbage
collection). p90 because the slowest cell completes a little over 100 steps
in a window, which leaves ten samples beyond it."""

import statistics

NAME, UNIT = "step_ms_p90", "ms"


def read(run):
    intervals = run.intervals()
    if len(intervals) < 10:
        return None
    run.note(f"{NAME}: {len(intervals)} samples, median "
             f"{statistics.median(intervals) * 1e3:.3f} ms, max "
             f"{max(intervals) * 1e3:.3f} ms")
    return statistics.quantiles(intervals, n=10, method="inclusive")[8] * 1e3
