"""Device trace: the sparse-attention forward kernel's share of its
roofline. Least time for one call over the SELECTED pairs only
(benchmarks/lib/kernels_sparse.py, from the shapes the builder states)
over the mean measured time of the events named ``hvd_sparse_attn_fwd`` on
the first device: a kernel that computes every causal pair under a mask
reads a low share by design."""

import re

from benchmarks.lib import kernels, kernels_sparse, scopes

NAME, UNIT = "sparse_attn_fwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
KERNEL = "hvd_sparse_attn_fwd"


def kernel_seconds(scoped, name: str) -> list:
    """Durations of the first device's events of the kernel ``name``: its
    ``pallas_call`` ``name=`` names the custom call, so the op's HLO text
    begins ``%<name>`` or ``%<name>.<n>`` (an op that merely consumes the
    kernel's result holds the name further on and is not the kernel)."""
    rx = re.compile(rf"^%?{re.escape(name)}(\.\d+)? ")
    return [op[2] for op in scoped.ops if rx.match(op[0])]


def read(run):
    shape = dict(run.kernel_shapes.get("sparse_attention") or {})
    scoped = scopes.of(run)
    if scoped is None or run.peak is None or not shape:
        return None
    secs = kernel_seconds(scoped, KERNEL)
    if not secs:
        return None
    least, bound = kernels.roofline(
        *kernels_sparse.sparse_attn_fwd_cost(**shape), run.peak)
    mean = sum(secs) / len(secs)
    run.note(f"{NAME}: {len(secs)} calls, mean {mean * 1e6:.1f} us, least "
             f"{least * 1e6:.1f} us over the selected pairs, bound by "
             f"{bound}")
    return 100.0 * least / mean
