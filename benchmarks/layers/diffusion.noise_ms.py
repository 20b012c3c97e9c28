"""Program scopes in the device trace (benchmarks/lib/scopes.py), first device:
time per step under ``hvd.block_diffusion_noise`` (ops/block_diffusion.py):
the draws of a step's noise levels and masks and the noised copy of its
sequences, made inside the step. A program without the scope reports
nothing."""

from benchmarks.lib import scopes

NAME, UNIT = "diffusion.noise_ms", "ms"
LAYER, MOVES = "Objective", "tokens_per_s_per_chip"
SCOPE = "hvd.block_diffusion_noise"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
