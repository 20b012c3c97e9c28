"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: time per step under ``hvd.bucket_pack`` or ``hvd.bucket_unpack``
(ops/fusion.py): the concatenates that fill the fusion buckets before the
gradient all-reduce and the slices that empty them after it."""

from benchmarks.lib import scopes

NAME, UNIT = "collective.pack_ms", "ms"
LAYER, MOVES = "Collectives", "tokens_per_s_per_chip"
SCOPES = ("hvd.bucket_pack", "hvd.bucket_unpack")


def read(run):
    scoped = scopes.of(run)
    return None if scoped is None else scoped.scope_ms(*SCOPES)
