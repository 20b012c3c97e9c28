"""Owners of the compiled step's instructions in the device trace
(benchmarks/lib/owners.py), first device: the share of a step's busy time in
fusions whose inner instructions have more than one owner: the part of the
owners' partition that rests on the convention "an inner instruction weighs
the bytes of its result". A reader's caution, not a target."""

from benchmarks.lib import owners

NAME, UNIT = "step.mixed_pct", "%"
LAYER, MOVES = "Device", "tokens_per_s_per_chip"


def read(run):
    owned = owners.of(run)
    if owned is None:
        return None
    return 100.0 * owned.mixed_ms() / owned.busy_ms()
