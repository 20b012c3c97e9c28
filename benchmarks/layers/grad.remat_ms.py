"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step of direction
``remat``, any owner: the forward that ``nn.remat`` runs again inside the
backward pass (``rematted_computation`` in the path), which
``step.backward_ms`` holds unnamed."""

from benchmarks.lib import owners

NAME, UNIT = "grad.remat_ms", "ms"
LAYER, MOVES = "Decoder block", "tokens_per_s_per_chip"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.ms(
        lambda _, direction: direction == owners.hlo_owners.REMAT)
