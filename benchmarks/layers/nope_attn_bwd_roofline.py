"""Device trace: the NoPE full layer's flash backward's share of its
roofline, the dq and the dk/dv kernels together, by their exact names
(hvd_flash_bwd_dq, hvd_flash_bwd_dkv: the windowed calls' names are longer):
least time for one backward over the causal pairs, four matmuls, K, V, dK
and dV moved once a KV head (benchmarks/lib/kernels_window.py, from the
``nope_attention`` shape the builder states), over the mean measured time of
one event of each on the first device. Masked work is not counted, so the
share cannot pass 100%."""

from benchmarks.lib import kernels_window, manifest as mf

NAME, UNIT = "nope_attn_bwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
ENTRY = "nope_attention"
KERNELS = ("hvd_flash_bwd_dq", "hvd_flash_bwd_dkv")


def read(run):
    share = mf.load_module("layers", "window_attn_fwd_roofline").share
    return share(run, NAME, ENTRY, KERNELS, kernels_window.attn_bwd_cost)
