"""Program scopes in the device trace (benchmarks/lib/scopes.py), first device:
time per step under ``hvd.moe_route`` (moe/layer.py ``moe_route``): a router
that reads another tensor than its experts, run where that tensor is, ahead
of attention: the router's float32 matmul, the top-k, the sort of the
token-choices by held expert, the groups' sizes and the load, forward and
backward, and the router's matmul made again in the rematerialised forward.
The experts' walk is ``moe_ffn.ms``. A program whose routing runs inside
``hvd.moe_ffn`` has no such scope and reports nothing."""

from benchmarks.lib import scopes

NAME, UNIT = "moe_route.ms", "ms"
LAYER, MOVES = "Experts", "tokens_per_s_per_chip"
SCOPE = "hvd.moe_route"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
