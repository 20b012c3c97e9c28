"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: the 512-row trips of
``moe_ffn_dropless``' backward walk a step, all layers, median over the
traced steps: the events of ``ragged-dot-metadata``, the custom call the TPU
compiler puts once in each body of the walk's ``while`` (it lays out the
groups for the body's grouped matmuls), in the bodies whose owner is
``hvd.moe_ffn`` and whose direction is backward. A step's time follows it
(PERF.md section 5): read ``moe_ffn.ms`` against it."""

from benchmarks.lib import owners, scopes

NAME, UNIT = "moe.tiles_per_step", "tiles"
LAYER, MOVES = "Experts", "tokens_per_s_per_chip"
SCOPE = "hvd.moe_ffn"
ONCE_A_TRIP = "ragged-dot-metadata"


def read(run):
    owned = owners.of(run)
    if owned is None:
        return None
    return owned.events_per_step(
        scopes.of(run).ops, ONCE_A_TRIP, (SCOPE, owners.hlo_owners.BACKWARD))
