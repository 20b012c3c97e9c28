"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step owned by
``hvd.gmu``: the gated memory units (models/sambay.py), which gate an
earlier state-space layer's scan output: their two projections and the
gate, all directions. A program without the scope reports nothing."""

from benchmarks.lib import owners

NAME, UNIT = "gmu.ms", "ms"
LAYER, MOVES = "Decoder block", "tokens_per_s_per_chip"
SCOPE = "hvd.gmu"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(SCOPE)
