"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step owned by
``hvd.norm``: every normalisation outside ops/layer_norm.py (flax's
LayerNorm in the GPT-2 blocks; ``rms_norm`` of the mixture decoder: block,
sandwich and per-head q / k norms), all directions, by the instructions
INSIDE the fusions: the part of a norm that rides in a matmul's fusion is
the matmul's (``proj.ms``), the reductions and elementwise passes that stand
alone are here."""

from benchmarks.lib import owners

NAME, UNIT = "norm.ms", "ms"
LAYER, MOVES = "Decoder block", "tokens_per_s_per_chip"
SCOPE = "hvd.norm"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(SCOPE)
