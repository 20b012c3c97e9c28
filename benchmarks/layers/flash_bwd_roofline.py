"""Device trace: the flash-attention backward's share of its roofline, the
dq and the dk/dv kernels together. Least time for one backward
(benchmarks/lib/kernels.py) over the mean measured time of one dq call plus
one dk/dv call on the first device."""

from benchmarks.lib import kernels, trace as tr

NAME, UNIT = "flash_bwd_roofline", "%"
LAYER, MOVES = "Kernels", "tokens_per_s_per_chip"
# No name= on the pallas_calls (see flash_fwd_roofline.py): the dq kernel
# is the tpu_custom_call with one bf16 output, the dk/dv kernel the one
# with two.
PATTERNS = (
    r' = bf16\[[^\]]+\]\S* custom-call\(.*custom_call_target='
    r'"tpu_custom_call"',
    r' = \(bf16\[[^\]]+\]\S*, bf16\[[^\]]+\]\S*\) custom-call\(.*'
    r'custom_call_target="tpu_custom_call"')


def read(run):
    shape = dict(run.kernel_shapes.get("flash_attention") or {})
    if run.trace is None or run.peak is None or not shape:
        return None
    dev = min(run.trace.ops)
    parts = [tr.kernel_seconds(run.trace, dev, p) for p in PATTERNS]
    if not all(parts):
        return None
    least, bound = kernels.roofline(*kernels.flash_bwd_cost(**shape),
                                    run.peak)
    mean = sum(sum(p) / len(p) for p in parts)
    run.note(f"{NAME}: {[len(p) for p in parts]} calls, mean dq + dkv "
             f"{mean * 1e6:.1f} us, least {least * 1e6:.1f} us, bound by "
             f"{bound}")
    return 100.0 * least / mean
