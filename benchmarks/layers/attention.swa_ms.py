"""Program scopes in the device trace (benchmarks/lib/scopes.py), first device:
time per step under ``hvd.flash_window`` in a SmallThinker step: the three
sliding layers' flash calls at 28 query heads on 4 KV heads under a window
of 4,096 keys, forward and backward: the three ``hvd_flash_*_win`` kernels
and whatever stands around them. ``attention.ms`` holds it too, with the
NoPE full call. Read only where the builder states a ``swa_attention``
shape: another family's windowed calls are ``attention.window_ms``."""

from benchmarks.lib import scopes

NAME, UNIT = "attention.swa_ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.flash_window"
ENTRY = "swa_attention"


def read(run):
    scoped = scopes.of(run)
    if scoped is None or not run.kernel_shapes.get(ENTRY):
        return None
    return scoped.scope_ms(SCOPE)
