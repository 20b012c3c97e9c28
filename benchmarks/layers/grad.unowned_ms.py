"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step under ``hvd.grad``
that no scope inside it owns: the residual adds, the attention output gate,
what ``nn.remat`` leaves between the blocks. The number a later PR shrinks
by naming or by fusing."""

from benchmarks.lib import owners

NAME, UNIT = "grad.unowned_ms", "ms"
LAYER, MOVES = "Decoder block", "tokens_per_s_per_chip"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(owners.hlo_owners.GRAD)
