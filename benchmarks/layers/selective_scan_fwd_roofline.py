"""Device trace: the selective-scan forward kernel's share of its roofline.
Least time for one call (benchmarks/lib/kernels_scan.py, from the
``selective_scan`` shape the builder states) over the mean measured time of
the events named ``hvd_selective_scan_fwd`` on the first device. The scan
does no matmul and ``peaks.json`` has no vector-unit row: the least time is
the HBM time of the operands, every one once, so the share reads against
BYTES and a kernel bound by the vector unit reads low by design."""

from benchmarks.lib import kernels_scan, manifest as mf, scopes

NAME, UNIT = "selective_scan_fwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
ENTRY = "selective_scan"
KERNEL = "hvd_selective_scan_fwd"


def share(run, name: str, kernel: str, cost):
    """100 x least / measured for one call of ``kernel`` (told by the op's
    own name); None where there is no trace, no ``selective_scan`` entry or
    no such kernel (a program without them)."""
    shape = dict(run.kernel_shapes.get(ENTRY) or {})
    scoped = scopes.of(run)
    if scoped is None or run.peak is None or not shape:
        return None
    secs = mf.load_module(
        "layers", "sparse_attn_fwd_roofline").kernel_seconds(scoped, kernel)
    if not secs:
        return None
    ops, nbytes = cost(**shape)
    least = kernels_scan.least_seconds(nbytes, run.peak)
    mean = sum(secs) / len(secs)
    run.note(f"{name}: {len(secs)} calls of {kernel}, mean "
             f"{mean * 1e6:.1f} us, least {least * 1e6:.1f} us for "
             f"{nbytes / 1e6:.1f} MB (bound by bytes: no vector-unit peak; "
             f"{ops / mean / 1e12:.3f} T vector operations a second)")
    return 100.0 * least / mean


def read(run):
    return share(run, NAME, KERNEL, kernels_scan.scan_fwd_cost)
