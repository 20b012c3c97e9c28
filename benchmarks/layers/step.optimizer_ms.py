"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.optimizer_update`` (parallel/optimizer.py),
the optax transformation ``hvd.DistributedOptimizer`` wraps: here the clip
and AdamW. ``optax.apply_updates`` is the user's call and is not in it."""

from benchmarks.lib import scopes

NAME, UNIT = "step.optimizer_ms", "ms"
LAYER, MOVES = "Step", "tokens_per_s_per_chip"
SCOPE = "hvd.optimizer_update"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
