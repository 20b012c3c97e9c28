"""The FLOP and roofline functions against hand-worked numbers."""

import pytest

from benchmarks.lib import flops, kernels, manifest as mf, peaks
from benchmarks.lib import reference_gpt2

MANIFEST = mf.load()

# 6 * (layers * 12 * d^2 + vocab * d) + 6 * layers * seq * d, by hand:
# gpt2-medium: 6 * (24 * 12 * 1024^2 + 50257 * 1024) + 6 * 24 * 1024 * 1024
#            = 6 * (301,989,888 + 51,463,168) + 150,994,944
# gpt2-small:  6 * (12 * 12 * 768^2 + 50257 * 768) + 6 * 12 * 1024 * 768
#            = 6 * (84,934,656 + 38,597,376) + 56,623,104
HAND_WORKED = {"gpt2-medium": 2_271_713_280, "gpt2-small": 797_815_296}


@pytest.mark.parametrize("name", sorted(HAND_WORKED))
def test_decoder_flops_per_token(name):
    cfg = mf.config_of(MANIFEST, name)
    s = reference_gpt2.sizes_from_config(cfg)
    got = flops.decoder_train_flops_per_token(
        layers=s["layers"], d_model=s["d_model"], d_ff=s["d_ff"],
        vocab=s["vocab"], seq_len=1024)
    assert got == HAND_WORKED[name]


def test_mfu_of_the_measured_rate_is_under_100():
    # 41,607 tokens/s/chip (my chip run, PR 23) is 47.98% of 197 TFLOP/s.
    peak = peaks.for_device_kind("TPU v5 lite")["bf16_flops_per_s"]
    mfu = 100 * 41607.46 * HAND_WORKED["gpt2-medium"] / peak
    assert mfu == pytest.approx(47.98, abs=0.01)


def test_flash_costs_by_hand():
    shape = dict(batch=8, seq=1024, heads=16, head_dim=64, causal=True)
    # pairs = 8 * 16 * 1024 * 1024 / 2 = 67,108,864; fwd 4 * pairs * 64
    fwd_flops, fwd_bytes = kernels.flash_fwd_cost(**shape)
    assert fwd_flops == 4 * 67_108_864 * 64 == 17_179_869_184
    # q, k, v, o of 8 * 1024 * 16 * 64 bf16 = 16 MiB each, lse 512 KiB
    assert fwd_bytes == 4 * 16 * 2 ** 20 + 8 * 16 * 1024 * 4
    bwd_flops, bwd_bytes = kernels.flash_bwd_cost(**shape)
    assert bwd_flops == 2 * fwd_flops
    assert bwd_bytes == 7 * 16 * 2 ** 20 + 2 * 8 * 16 * 1024 * 4
    full, _ = kernels.flash_fwd_cost(**{**shape, "causal": False})
    assert full == 2 * fwd_flops


def test_roofline_says_which_bound_applies():
    peak = peaks.for_device_kind("TPU v5 lite")
    secs, bound = kernels.roofline(197e12, 1.0, peak)
    assert (secs, bound) == (1.0, "flops")
    secs, bound = kernels.roofline(1.0, 819e9, peak)
    assert (secs, bound) == (1.0, "bytes")
    # the forward kernel at the cell's shape: 87.2 us, by FLOPs
    secs, bound = kernels.roofline(17_179_869_184, 67_633_152, peak)
    assert bound == "flops" and secs == pytest.approx(87.2e-6, rel=1e-3)
