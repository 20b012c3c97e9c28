"""Program scopes in the device trace (benchmarks/lib/scopes.py), first device:
time per step under ``hvd.router_bias_update``: the update of the routers'
selection biases after the optimizer's (moe/layer.py
``router_bias_update``): the counts' sum over the data axis and the rule, a
few [128] vectors a routed layer. A program without the scope reports
nothing."""

from benchmarks.lib import scopes

NAME, UNIT = "router_bias.ms", "ms"
LAYER, MOVES = "Step", "tokens_per_s_per_chip"
SCOPE = "hvd.router_bias_update"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
