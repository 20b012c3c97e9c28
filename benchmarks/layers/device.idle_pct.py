"""Device trace, first device: 1 - busy / window, busy being the union of
the intervals in which an op ran."""

from benchmarks.lib import trace as tr

NAME, UNIT = "device.idle_pct", "%"
LAYER, MOVES = "Device", "tokens_per_s_per_chip"


def read(run):
    if run.trace is None:
        return None
    return 100.0 * tr.idle_share(run.trace, min(run.trace.ops))
