"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.lm_head_loss`` (ops/softmax_xent.py),
forward and backward: the head matmul and the cross-entropy, dense or
fused."""

from benchmarks.lib import scopes

NAME, UNIT = "head_loss.ms", "ms"
LAYER, MOVES = "LM head and loss", "tokens_per_s_per_chip"
SCOPE = "hvd.lm_head_loss"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
