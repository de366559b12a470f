"""The benchmark's yardstick against hand counts: FLOPs and peaks."""
import pytest

from bench.flop_count import (forward_flops, resnet18_forward_macs,
                              train_flops)
from bench.peaks import PEAKS, chip_peaks

def stage(hw, c_in, c):
    return (hw * hw * c * c_in * 9             # b0 conv1 (stride 2)
            + hw * hw * c * c * 9              # b0 conv2
            + hw * hw * c * c_in               # 1x1 projection
            + 2 * hw * hw * c * c * 9)         # b1


def test_resnet18_forward_macs_by_hand():
    hand = (32 * 32 * 64 * 27 + 4 * 32 * 32 * 64 * 64 * 9
            + stage(16, 64, 128) + stage(8, 128, 256) + stage(4, 256, 512)
            + 512 * 100)
    got = resnet18_forward_macs(32, 3, 100, 64)
    assert got == hand == 555_468_800
    assert got / 1e9 == pytest.approx(0.555, abs=1e-3)


def test_flops_per_image_and_round():
    model = {"image_size": 32, "channels": 3, "n_classes": 100, "width": 64}
    assert forward_flops(model) == 2 * 555_468_800
    assert train_flops(model) == 3 * forward_flops(model)
    # 8 clients x 5 steps x 64 images: the 8.5 TFLOP of one round
    assert train_flops(model) * 8 * 5 * 64 / 1e12 == pytest.approx(8.53,
                                                                   abs=0.01)


def test_peaks_table():
    assert chip_peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        chip_peaks("cpu")
