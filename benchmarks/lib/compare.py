"""The comparison that decides ``correct`` for a training cell.

The program's first steps against the plain reference's, number by number,
each against a limit of its own from ``benchmarks/limits/<cell>.json``
(PERF.md gives the readings each limit was set from):

* ``loss_gap``: the widest |program - reference| loss over the checked
  steps. Catches a part of the batch left out.
* ``grad_norm_gap``: the gradient the optimizer was handed in the first
  step, by the worst leaf. Catches a lower precision, a gradient scaled by
  the world size once too often, an exchange between chips left out.
* ``delta_norm_gap``: the parameters' change over the checked steps, by
  the worst leaf. Catches a step that returns its state unchanged.

A leaf's gap is the distance between the program's norm and the
reference's (not the norm of their difference), measured against the
reference's norm of that leaf or of the median leaf, whichever is larger:
some gradients are all but zero and would make any rounding look large.
"""

from __future__ import annotations

import math
import statistics

NUMBERS = ("loss_gap", "grad_norm_gap", "delta_norm_gap")


def worst_leaf_gap(program: dict, reference: dict) -> tuple[float, str]:
    """(gap, leaf) of the leaf whose norms differ most; a leaf missing on
    either side, or not finite, is an infinite gap."""
    if set(program) != set(reference) or not reference:
        return math.inf, "leaf sets differ"
    floor = statistics.median(reference.values())
    worst, where = 0.0, ""
    for leaf, ref in reference.items():
        got = program[leaf]
        if not (math.isfinite(got) and math.isfinite(ref)):
            return math.inf, leaf
        scale = max(ref, floor)
        gap = abs(got - ref) / scale if scale > 0 else (
            0.0 if got == ref else math.inf)
        if gap >= worst:
            worst, where = gap, leaf
    return worst, where


def readings(program: dict, reference: dict) -> dict:
    """The compared numbers: {name: (value, note)}."""
    steps = min(len(program["loss"]), len(reference["loss"]))
    loss = [abs(a - b) if math.isfinite(a) else math.inf
            for a, b in zip(program["loss"][:steps],
                            reference["loss"][:steps])]
    grad, grad_leaf = worst_leaf_gap(program["grad_norm"],
                                     reference["grad_norm"])
    delta, delta_leaf = worst_leaf_gap(program["delta_norm"],
                                       reference["delta_norm"])
    return {"loss_gap": (max(loss), f"steps 0..{steps - 1}"),
            "grad_norm_gap": (grad, grad_leaf),
            "delta_norm_gap": (delta, delta_leaf)}


def judge(program: dict, reference: dict, limits: dict) -> list:
    """[(name, value, limit, ok, note)] for every compared number."""
    rows = []
    for name, (value, note) in readings(program, reference).items():
        limit = limits[name]
        rows.append((name, value, limit, bool(value <= limit), note))
    return rows
