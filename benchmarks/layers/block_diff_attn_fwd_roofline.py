"""Device trace: the block-diffusion flash forward kernel's share of its
roofline. Least time for one call over the mask's VISIBLE pairs only,
``L^2 + L B`` a query head over the ``2 L`` rows, K and V read once a KV
head (benchmarks/lib/kernels_block_diffusion.py, from the
``block_diffusion_attention`` shape the builder states), over the mean
measured time of the events named ``hvd_flash_fwd_bd`` exactly on the first
device: what a tile computes under a block edge's mask is not counted, so
the share cannot pass 100%."""

from benchmarks.lib import kernels, kernels_block_diffusion as kbd
from benchmarks.lib import manifest as mf, scopes

NAME, UNIT = "block_diff_attn_fwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
ENTRY = "block_diffusion_attention"
KERNELS = ("hvd_flash_fwd_bd",)


def share(run, name: str, kernel_names: tuple, cost):
    """100 x least / measured for one call of every kernel in
    ``kernel_names`` together (told by the op's own whole name), at the
    builder's ``kernel_shapes`` entry; None where there is no trace, no
    such entry or no such kernel (a program without them)."""
    shape = dict(run.kernel_shapes.get(ENTRY) or {})
    scoped = scopes.of(run)
    if scoped is None or run.peak is None or not shape:
        return None
    kernel_seconds = mf.load_module(
        "layers", "sparse_attn_fwd_roofline").kernel_seconds
    parts = [kernel_seconds(scoped, k) for k in kernel_names]
    if not all(parts):
        return None
    least, bound = kernels.roofline(*cost(**shape), run.peak)
    mean = sum(sum(p) / len(p) for p in parts)
    run.note(f"{name}: {[len(p) for p in parts]} calls of "
             f"{'+'.join(kernel_names)}, mean "
             + " + ".join(f"{sum(p) / len(p) * 1e6:.1f}" for p in parts)
             + f" us, least {least * 1e6:.1f} us over "
             f"{kbd.visible_pairs(shape['seq'], shape['block']):.0f} visible "
             f"pairs a head, bound by {bound}")
    return 100.0 * least / mean


def read(run):
    return share(run, NAME, KERNELS, kbd.attn_fwd_cost)
