"""Device trace: the selective-scan backward kernel's share of its
roofline. Least time for one call (benchmarks/lib/kernels_scan.py: the
operands, ``dm`` and the six gradients once each) over the mean measured
time of the events named ``hvd_selective_scan_bwd`` on the first device;
against BYTES, as ``selective_scan_fwd_roofline`` says."""

from benchmarks.lib import kernels_scan, manifest as mf

NAME, UNIT = "selective_scan_bwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
KERNEL = "hvd_selective_scan_bwd"


def read(run):
    share = mf.load_module("layers", "selective_scan_fwd_roofline").share
    return share(run, NAME, KERNEL, kernels_scan.scan_bwd_cost)
