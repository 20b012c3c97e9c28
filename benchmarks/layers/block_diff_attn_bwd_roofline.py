"""Device trace: the block-diffusion flash backward's share of its roofline,
the dq and the dk/dv kernels together. Least time for one backward over the
mask's VISIBLE pairs only, four matmuls, K, V, dK and dV moved once a KV head
(benchmarks/lib/kernels_block_diffusion.py, from the
``block_diffusion_attention`` shape the builder states), over the mean
measured time of one hvd_flash_bwd_dq_bd event plus one hvd_flash_bwd_dkv_bd
event on the first device. Masked work is not counted, so the share cannot
pass 100%."""

from benchmarks.lib import kernels_block_diffusion as kbd, manifest as mf

NAME, UNIT = "block_diff_attn_bwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
KERNELS = ("hvd_flash_bwd_dq_bd", "hvd_flash_bwd_dkv_bd")


def read(run):
    share = mf.load_module("layers", "block_diff_attn_fwd_roofline").share
    return share(run, NAME, KERNELS, kbd.attn_bwd_cost)
