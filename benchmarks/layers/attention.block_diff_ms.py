"""Program scopes in the device trace (benchmarks/lib/scopes.py), first device:
time per step under ``hvd.flash_block_diffusion``: the flash calls under the
block-diffusion mask (ops/flash_attention.py, inside ``hvd.flash_attention``),
forward and backward: the three ``hvd_flash_*_bd`` kernels and whatever
stands around them. ``attention.ms`` holds it too. A program without the
scope reports nothing."""

from benchmarks.lib import scopes

NAME, UNIT = "attention.block_diff_ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.flash_block_diffusion"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
