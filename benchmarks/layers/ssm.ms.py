"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step owned by
``hvd.ssm``: a state-space layer's mixer (models/sambay.py) beside its
scan: the in, x, dt and out projections, the causal convolution, softplus
and the gate, all directions. The scan itself is ``ssm_scan.ms``'. A program
without the scope reports nothing."""

from benchmarks.lib import owners

NAME, UNIT = "ssm.ms", "ms"
LAYER, MOVES = "Decoder block", "tokens_per_s_per_chip"
SCOPE = "hvd.ssm"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(SCOPE)
