"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.ssd_scan`` (ops/ssd_scan.py): Mamba-2's
chunked scan, forward and backward, every Mamba layer's: the matmuls under a
chunk's diagonal, the chunk states, their recurrence and what stands between
them (the decays' cumulative sums, the masks, the casts). The mixer around
it is ``ssm.ms``. A program without the scope reports nothing."""

from benchmarks.lib import scopes

NAME, UNIT = "ssd_scan.ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.ssd_scan"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
