"""Analytic FLOPs accounting sanity checks against published model costs."""

import pytest

from tpudp.utils.flops import (chip_peak_flops, gpt2_fwd_flops, mfu,
                               resnet_fwd_flops, train_step_flops,
                               vgg_fwd_flops)


def test_vgg11_fwd_flops_magnitude():
    # VGG-11 at 224^2 is ~7.6 GMACs; at 32^2 that scales by (32/224)^2 to
    # ~0.155 GMACs = ~0.31 GFLOPs forward.
    f = vgg_fwd_flops(1)
    assert 0.2e9 < f < 0.4e9
    # batch linearity
    assert vgg_fwd_flops(8) == 8 * f


def test_resnet50_fwd_flops_magnitude():
    # Published ResNet-50 @224: ~4.1 GMACs = ~8.2 GFLOPs forward.
    f = resnet_fwd_flops(1)
    assert 7.0e9 < f < 9.5e9


def test_gpt2_small_fwd_flops_magnitude():
    # 12L/768d @ t=1024: ~170 MFLOPs/token of layer matmuls + ~38M of
    # quadratic attention + ~77M LM head => ~290 GFLOPs per sequence.
    f = gpt2_fwd_flops(1, 1024)
    assert 240e9 < f < 340e9


def test_train_step_is_3x_forward():
    assert train_step_flops(100) == 300


def test_chip_peak_table():
    assert chip_peak_flops("TPU v4") == 275e12
    assert chip_peak_flops("TPU v5 lite") == 197e12
    assert chip_peak_flops("TPU v5p") == 459e12
    assert chip_peak_flops("TPU v5") == 459e12  # how a v5p reports itself
    assert chip_peak_flops("cpu") is None


def test_unknown_accelerator_kind_is_an_error():
    """A device that is not in the table is an error, not a default: the
    old substring table priced any unknown "v5 ..." kind as a v5p and
    returned None (an MFU silently dropped) for everything else."""
    for kind in ("TPU v5 ultra", "TPU v7", "NVIDIA H100", "unknown-chip"):
        with pytest.raises(ValueError, match="unknown accelerator"):
            chip_peak_flops(kind)
        with pytest.raises(ValueError, match="unknown accelerator"):
            mfu(1e12, 1.0, kind)


def test_mfu():
    # 550 TFLOPs of work in 2s on one v4 chip (275 TFLOPs/s peak) = 1.0 MFU.
    assert abs(mfu(550e12, 2.0, "TPU v4", 1) - 1.0) < 1e-9
    assert mfu(1e12, 1.0, "cpu") is None  # the CPU platform has no peak
