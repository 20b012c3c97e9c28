"""Device trace: the sparse-attention backward's share of its roofline,
whatever kernels make it up. Least time for one backward over the SELECTED
pairs only (benchmarks/lib/kernels_sparse.py) over the measured time of one:
the time of every event on the first device whose kernel name begins
``hvd_sparse_attn_bwd`` (today ``_dq`` and ``_dkv``; one fused kernel of
that name reads the same way), inside the traced steps, over the backward
calls those steps made (``calls_per_step`` of the builder's
``kernel_shapes`` entry)."""

import re

from benchmarks.lib import kernels, kernels_sparse, scopes

NAME, UNIT = "sparse_attn_bwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
PREFIX = "hvd_sparse_attn_bwd"
# The op's HLO text begins with the kernel's own name (an op that merely
# consumes a kernel's result holds the name further on and is not one).
KERNEL = re.compile(rf"^%?({PREFIX}\w*?)(\.\d+)? ")


def read(run):
    shape = dict(run.kernel_shapes.get("sparse_attention") or {})
    scoped = scopes.of(run)
    calls = shape.get("calls_per_step")
    if scoped is None or run.peak is None or not calls or not scoped.steps:
        return None
    by_kernel: dict = {}
    for text, start, seconds, _ in scoped.ops:
        m = KERNEL.match(text)
        if m and any(a <= start < b for a, b in scoped.steps):
            by_kernel.setdefault(m.group(1), []).append(seconds)
    if not by_kernel:
        return None
    least, bound = kernels.roofline(
        *kernels_sparse.sparse_attn_bwd_cost(**shape), run.peak)
    backwards = len(scoped.steps) * calls
    mean = sum(map(sum, by_kernel.values())) / backwards
    run.note(f"{NAME}: {backwards} backward calls, events "
             f"{ {k: len(v) for k, v in sorted(by_kernel.items())} }, mean "
             f"{mean * 1e6:.1f} us a call, least {least * 1e6:.1f} us over "
             f"the selected pairs, bound by {bound}")
    return 100.0 * least / mean
