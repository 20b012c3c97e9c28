"""Host span around ``step.lower(...).compile()``: a compile on a cold
checkout, a load from the persistent cache afterwards."""

NAME, UNIT = "setup.compile_s", "s"
LAYER, MOVES = "Entry / compile cache", "setup_s"


def read(run):
    return run.spans.total("setup.compile") or None
