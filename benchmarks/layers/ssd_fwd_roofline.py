"""Device trace: the chunked scan's forward as a share of its roofline.
Least time for one call (benchmarks/lib/kernels_ssd.py, from the
``ssd_scan`` shape the builder states: the pairs under each chunk's
diagonal, the chunk states and their read; the operands and the output
once) times the calls a step makes, over the measured time a step spends
under ``hvd.ssd_scan`` in that direction on the first device. The scan is
told by its scope, not by a kernel's name: whatever implements it (a Pallas
kernel, matmuls the compiler schedules) is measured whole, with what stands
between its matmuls. Masked work is not counted, so the share cannot pass
100%."""

from benchmarks.lib import kernels, kernels_ssd, scopes

NAME, UNIT = "ssd_fwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
ENTRY, SCOPE = "ssd_scan", "hvd.ssd_scan"


def share(run, name: str, direction: str, cost):
    """100 x calls x least / measured for the scans of one direction of a
    step; None where there is no trace, no ``ssd_scan`` entry or nothing
    under the scope (a program without it)."""
    shape = dict(run.kernel_shapes.get(ENTRY) or {})
    scoped = scopes.of(run)
    if scoped is None or run.peak is None or not shape:
        return None
    ms = scoped.scope_ms(SCOPE, direction=direction)
    if not ms:
        return None
    calls = shape.pop("calls", 1)
    flops, nbytes = cost(**shape)
    least, bound = kernels.roofline(flops, nbytes, run.peak)
    run.note(f"{name}: {calls} calls a step, {ms / calls * 1e3:.1f} us a "
             f"call {direction}, least {least * 1e6:.1f} us for "
             f"{flops / 1e9:.2f} GFLOP and {nbytes / 1e6:.1f} MB, bound by "
             f"{bound}")
    return 100.0 * calls * least / (ms * 1e-3)


def read(run):
    return share(run, NAME, "forward", kernels_ssd.ssd_fwd_cost)
