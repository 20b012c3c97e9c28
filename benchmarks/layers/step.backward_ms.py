"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step of the ops under ``hvd.grad`` (parallel/tape.py) whose
path holds ``transpose(``: the backward pass of the user's loss."""

from benchmarks.lib import scopes

NAME, UNIT = "step.backward_ms", "ms"
LAYER, MOVES = "Step", "tokens_per_s_per_chip"
SCOPE = "hvd.grad"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(
        SCOPE, direction="backward")
