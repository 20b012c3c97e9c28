"""Model FLOP utilisation: tokens/s/chip x the FLOPs a trained token
requires (benchmarks/lib/flops.py, nothing recomputed counted) over the
chip's bf16 peak from benchmarks/lib/peaks.json. Only on a device the table
knows."""

NAME, UNIT = "mfu_pct", "%"


def read(run):
    rate = run.tokens_per_s_per_chip()
    if rate is None or run.peak is None:
        return None
    run.note(f"{NAME}: {run.flops_per_token} FLOPs/token, peak "
             f"{run.peak['bf16_flops_per_s']:.4g} FLOP/s")
    return 100.0 * rate * run.flops_per_token / run.peak["bf16_flops_per_s"]
