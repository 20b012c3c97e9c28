"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.selective_scan``
(ops/selective_scan.py): the two kernels, forward and backward, and what
stands around them (the operands' float32 views, the sums of the backward's
parts). A program without the scope reports nothing."""

from benchmarks.lib import scopes

NAME, UNIT = "ssm_scan.ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.selective_scan"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
