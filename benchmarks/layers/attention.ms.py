"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.flash_attention`` (ops/flash_attention.py),
forward and backward: the three kernels and the layout traffic of the public
wrapper around them (``[B, T, H, D]`` to ``[BH, T, D]`` and back)."""

from benchmarks.lib import scopes

NAME, UNIT = "attention.ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.flash_attention"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
