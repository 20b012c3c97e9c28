"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step owned by
``hvd.rotary`` (models/sparse_moe_decoder.py ``rope``): angles, the split in
halves, the rotation and the concatenate, all directions."""

from benchmarks.lib import owners

NAME, UNIT = "rotary.ms", "ms"
LAYER, MOVES = "Decoder block", "tokens_per_s_per_chip"
SCOPE = "hvd.rotary"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(SCOPE)
