"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.moe_latent``
(models/hybrid_mamba_moe.py): the projection of the normed stream into the
experts' latent and the walk's result back out of it, forward, backward and
(where the latent is not kept) made again in the rematerialised forward.
The walk between them is ``moe_ffn.ms``, the shared expert beside them
``shared_expert.ms``. A program whose experts work at the model's width has
no such scope and reports nothing."""

from benchmarks.lib import scopes

NAME, UNIT = "moe_latent.ms", "ms"
LAYER, MOVES = "Experts", "tokens_per_s_per_chip"
SCOPE = "hvd.moe_latent"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
