"""Device trace: the NoPE full layer's flash forward kernel's share of its
roofline: one causal call over 16,384 rows, 28 query heads on 4 KV heads.
Least time over the causal pairs, t + 1 a query, K and V read once a KV
head (benchmarks/lib/kernels_window.py, from the ``nope_attention`` shape
the builder states), over the mean measured time of the events named
hvd_flash_fwd exactly (a windowed call's hvd_flash_fwd_win is not one) on
the first device. Masked work is not counted, so the share cannot pass
100%."""

from benchmarks.lib import kernels_window, manifest as mf

NAME, UNIT = "nope_attn_fwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
ENTRY = "nope_attention"
KERNELS = ("hvd_flash_fwd",)


def read(run):
    share = mf.load_module("layers", "window_attn_fwd_roofline").share
    return share(run, NAME, ENTRY, KERNELS, kernels_window.attn_fwd_cost)
