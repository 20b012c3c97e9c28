"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.sparse_attention``
(ops/sparse_attention.py), forward and backward: the three masked-attention
kernels and the layout traffic of the public wrapper around them
(``[B, T, H, D]`` to ``[B*Hkv, G, T, D]`` and back). The indexer's time is
``sparse_indexer.ms``."""

from benchmarks.lib import scopes

NAME, UNIT = "sparse_attention.ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.sparse_attention"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
