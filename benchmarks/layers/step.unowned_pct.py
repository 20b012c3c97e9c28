"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: the share of a step's busy time
that no ``hvd.*`` scope owns after the three rules of
``horovod_tpu.monitor.hlo_owners``: ``optax.apply_updates``' own adds and
the copies of their results, the loss all-reduce, and events whose
instruction the compiled text does not name (listed in the note of
lib/owners.py)."""

from benchmarks.lib import owners

NAME, UNIT = "step.unowned_pct", "%"
LAYER, MOVES = "Device", "tokens_per_s_per_chip"


def read(run):
    owned = owners.of(run)
    if owned is None:
        return None
    unowned = owned.owner_ms(owners.hlo_owners.UNOWNED) or 0.0
    return 100.0 * unowned / owned.busy_ms()
