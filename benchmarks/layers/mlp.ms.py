"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step owned by
``hvd.mlp``: a block's dense MLP (GPT-2's ``_MLP``; the one dense layer of
``trinity-mini``), all directions. The routed experts are ``moe_ffn.ms``', a
shared expert ``shared_expert.ms``'."""

from benchmarks.lib import owners

NAME, UNIT = "mlp.ms", "ms"
LAYER, MOVES = "Decoder block", "tokens_per_s_per_chip"
SCOPE = "hvd.mlp"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(SCOPE)
