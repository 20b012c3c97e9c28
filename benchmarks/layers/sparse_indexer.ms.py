"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.sparse_indexer``
(ops/sparse_attention.py): the index scores of every causal pair, the exact
per-query threshold and the selection mask (kernel ``hvd_index_select``).
The indexer's projections and rotary embedding sit in the model, outside
the scope."""

from benchmarks.lib import scopes

NAME, UNIT = "sparse_indexer.ms", "ms"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
SCOPE = "hvd.sparse_indexer"


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(SCOPE)
