"""Device trace: the flash-attention forward kernel's share of its
roofline. Least time for one call (benchmarks/lib/kernels.py, from the
shapes the builder states) over the mean measured time of the kernel's
events on the first device."""

from benchmarks.lib import kernels, trace as tr

NAME, UNIT = "flash_fwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
# The pallas_call has no name= today, and the trace names an op by its HLO
# text, in which the kernel function's name does not appear: the forward
# kernel is the tpu_custom_call whose outputs are (bf16 o, f32 lse).
PATTERN = (r' = \(bf16\[[^\]]+\]\S*, f32\[[^\]]+\]\S*\) custom-call\(.*'
           r'custom_call_target="tpu_custom_call"')


def read(run):
    shape = dict(run.kernel_shapes.get("flash_attention") or {})
    if run.trace is None or run.peak is None or not shape:
        return None
    secs = tr.kernel_seconds(run.trace, min(run.trace.ops), PATTERN)
    if not secs:
        return None
    least, bound = kernels.roofline(*kernels.flash_fwd_cost(**shape),
                                    run.peak)
    mean = sum(secs) / len(secs)
    run.note(f"{NAME}: {len(secs)} calls, mean {mean * 1e6:.1f} us, least "
             f"{least * 1e6:.1f} us, bound by {bound}")
    return 100.0 * least / mean
