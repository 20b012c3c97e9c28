"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step of the routed experts (moe/layer.py
``moe_ffn_dropless``), forward and backward: the router, the sort of the
token-choices by expert, the gather, the combine (all under the scope
``hvd.moe_ffn``) and the grouped matmuls of the experts held. Those the TPU
compiler turns into custom calls of its own whose path is
``ragged-dot-none`` and no longer the program's (found in the trace: 283 of
549 ms a step, my chip run, PR 26), so they are read by that name."""

from benchmarks.lib import scopes

NAME, UNIT = "moe_ffn.ms", "ms"
LAYER, MOVES = "Experts", "tokens_per_s_per_chip"
SCOPE = "hvd.moe_ffn"
GROUPED_MATMUL = "ragged-dot"


def read(run):
    scoped = scopes.of(run)
    if scoped is None or scoped.scope_ms(SCOPE) is None:
        return None
    return scoped.scope_ms(SCOPE, GROUPED_MATMUL)
