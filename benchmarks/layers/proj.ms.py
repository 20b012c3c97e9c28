"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: time per step owned by
``hvd.attn_proj`` (models/gpt.py, models/sparse_moe_decoder.py): the q / k /
v / gate / output matmuls of the attention block and the indexer's three
projections, forward, backward and rematerialised, with what the compiler
fused into them (in the GPT-2 cells the LayerNorm before the qkv matmul: a
matmul fusion goes whole to its matmul's owner)."""

from benchmarks.lib import owners

NAME, UNIT = "proj.ms", "ms"
LAYER, MOVES = "Decoder block", "tokens_per_s_per_chip"
SCOPE = "hvd.attn_proj"


def read(run):
    owned = owners.of(run)
    return None if owned is None else owned.owner_ms(SCOPE)
