"""Device trace: the sliding layers' flash forward kernel's share of its
roofline at SmallThinker's shape (28 query heads on 4 KV heads of 128, a
window of 4,096 under 16,384 rows). Least time for one call over the BAND's
pairs only, K and V read once a KV head (benchmarks/lib/kernels_window.py,
from the ``swa_attention`` shape the builder states), over the mean measured
time of the events named ``hvd_flash_fwd_win`` on the first device: what a
tile computes under the causal or the window mask is not counted, so the
share cannot pass 100%."""

from benchmarks.lib import kernels_window, manifest as mf

NAME, UNIT = "swa_attn_fwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
ENTRY = "swa_attention"
KERNELS = ("hvd_flash_fwd_win",)


def read(run):
    share = mf.load_module("layers", "window_attn_fwd_roofline").share
    return share(run, NAME, ENTRY, KERNELS, kernels_window.attn_fwd_cost)
