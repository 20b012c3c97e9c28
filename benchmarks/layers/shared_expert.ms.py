"""Program scopes in the device trace (benchmarks/lib/scopes.py), first device:
time per step under ``hvd.shared_expert``: the shared expert every token
passes (models/sparse_moe_decoder.py), forward and backward. It stands
beside the routed experts and outside ``hvd.moe_ffn``, so ``moe_ffn.ms``
does not hold it. A program without the scope reports nothing."""

from benchmarks.lib import scopes

NAME, UNIT = "shared_expert.ms", "ms"
LAYER, MOVES = "Experts", "tokens_per_s_per_chip"
SCOPE = "hvd.shared_expert"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
