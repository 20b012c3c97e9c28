"""Host span around making parameters and optimizer state on the device
from the seed and placing the batch pool."""

NAME, UNIT = "setup.init_s", "s"
LAYER, MOVES = "Entry / compile cache", "setup_s"


def read(run):
    return run.spans.total("setup.init") or None
