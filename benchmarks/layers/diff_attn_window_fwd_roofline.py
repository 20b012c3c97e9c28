"""Device trace: the windowed differential-attention forward's share of its
roofline. Least time for one call over the VISIBLE pairs, a score 64 wide
and a value 128 (benchmarks/lib/kernels_diff.py, from the
``diff_window_attention`` shape the builder states), over the mean measured
time of the events named exactly hvd_flash_fwd_win on the first device: the
flash kernels the other families run, called once a layer with a query head
in its half of a 128-wide head. Masked work and the zero half are not
counted, so the share cannot pass 100%."""

from benchmarks.lib import kernels_diff, manifest as mf

NAME, UNIT = "diff_attn_window_fwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
ENTRY = "diff_window_attention"
KERNELS = ("hvd_flash_fwd_win",)


def read(run):
    share = mf.load_module("layers", "window_attn_fwd_roofline").share
    return share(run, NAME, ENTRY, KERNELS, kernels_diff.attn_fwd_cost)
