"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step owned by
``hvd.diff_attention``: what differential attention adds around the flash
call (models/sambay.py): a query head laid into its half of a 128-wide
head, the pair's subtraction, ``lam`` and the 128-wide norm, all
directions. The flash kernels are ``attention.ms``', the projections
``proj.ms``'. A program without the scope reports nothing."""

from benchmarks.lib import owners

NAME, UNIT = "attention.diff_ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.diff_attention"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(SCOPE)
