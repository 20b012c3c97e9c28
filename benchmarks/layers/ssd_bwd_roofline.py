"""Device trace: the chunked scan's backward as a share of its roofline.
Least time for one call (benchmarks/lib/kernels_ssd.py: twice the forward's
matmuls, the operands, ``dy`` and the six gradients once each, the
chunk-entering states read once) times the calls a step makes, over the
measured time a step spends under ``hvd.ssd_scan`` in the backward
direction on the first device; by the scope, as ``ssd_fwd_roofline``
says."""

from benchmarks.lib import kernels_ssd, manifest as mf

NAME, UNIT = "ssd_bwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"


def read(run):
    share = mf.load_module("layers", "ssd_fwd_roofline").share
    return share(run, NAME, "backward", kernels_ssd.ssd_bwd_cost)
