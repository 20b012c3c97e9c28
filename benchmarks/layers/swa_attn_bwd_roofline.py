"""Device trace: the sliding layers' flash backward's share of its roofline
at SmallThinker's shape, the dq and the dk/dv kernels together. Least time
for one backward over the BAND's pairs only, four matmuls, K, V, dK and dV
moved once a KV head (benchmarks/lib/kernels_window.py, from the
``swa_attention`` shape the builder states), over the mean measured time of
one hvd_flash_bwd_dq_win event plus one hvd_flash_bwd_dkv_win event on the
first device. Masked work is not counted, so the share cannot pass 100%."""

from benchmarks.lib import kernels_window, manifest as mf

NAME, UNIT = "swa_attn_bwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
ENTRY = "swa_attention"
KERNELS = ("hvd_flash_bwd_dq_win", "hvd_flash_bwd_dkv_win")


def read(run):
    share = mf.load_module("layers", "window_attn_fwd_roofline").share
    return share(run, NAME, ENTRY, KERNELS, kernels_window.attn_bwd_cost)
