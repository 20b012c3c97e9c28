"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step owned by
``hvd.optimizer_update`` (parallel/optimizer.py) by the instructions INSIDE
the fusions: the clip's norms and AdamW's arithmetic, which the compiler
fuses into the unscoped ``add`` of ``optax.apply_updates``
(``step.optimizer_ms`` reads the events whose ROOT carries the scope: the
norms alone)."""

from benchmarks.lib import owners

NAME, UNIT = "optimizer.ms", "ms"
LAYER, MOVES = "Step", "tokens_per_s_per_chip"
SCOPE = "hvd.optimizer_update"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(SCOPE)
