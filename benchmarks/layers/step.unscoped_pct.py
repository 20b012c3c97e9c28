"""Program scopes in the device trace (benchmarks/lib/scopes.py), first
device: the share of a step's busy time in ops that carry no ``hvd.*`` scope
(``optax.apply_updates``, the loss all-reduce, copies the compiler put in):
how much of the step the scope metrics do not explain. Prints the partition
by outermost scope beside ``step.device_busy_ms``' own number."""

import statistics

from benchmarks.lib import scopes, trace as tr

NAME, UNIT = "step.unscoped_pct", "%"
LAYER, MOVES = "Device", "tokens_per_s_per_chip"


def read(run):
    scoped = scopes.of(run)
    classes = None if scoped is None else scoped.classes_ms()
    if classes is None:
        return None
    total = sum(classes.values())
    busy = statistics.median(
        tr.step_busy_seconds(run.trace, min(run.trace.ops))) * 1e3
    run.note(f"{NAME}: " + ", ".join(
        f"{k} {v:.3f}" for k, v in sorted(classes.items()))
        + f" ms; sum {total:.3f} against busy {busy:.3f} ms "
        f"({100 * (total / busy - 1):+.3f}%)")
    return 100.0 * classes.get(scopes.UNSCOPED, 0.0) / total
