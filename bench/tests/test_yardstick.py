"""The benchmark's yardstick against hand counts: the ``resnet18gn``
family's FLOPs, and the peaks."""
import pytest

from bench.cells import family, resolve
from bench.peaks import PEAKS, chip_peaks

FAMILY = family(resolve("silo8.cc_power"))


def stage(hw, c_in, c):
    return (hw * hw * c * c_in * 9             # b0 conv1 (stride 2)
            + hw * hw * c * c * 9              # b0 conv2
            + hw * hw * c * c_in               # 1x1 projection
            + 2 * hw * hw * c * c * 9)         # b1


def hand_macs(hw, n_classes, w):
    return (hw * hw * w * 27 + 4 * hw * hw * w * w * 9
            + stage(hw // 2, w, 2 * w) + stage(hw // 4, 2 * w, 4 * w)
            + stage(hw // 8, 4 * w, 8 * w) + 8 * w * n_classes)


@pytest.mark.parametrize("size", ["published", "cpu"])
def test_resnet18_forward_macs_by_hand(size):
    config = resolve("silo8.cc_power").config
    if size == "cpu":
        config = FAMILY.shrink(config)
    m = config["model"]
    hand = hand_macs(m["image_size"], m["n_classes"], m["width"])
    assert FAMILY.forward_flops(config) == 2 * hand
    if size == "published":
        assert hand == 555_468_800


def test_flops_per_image_and_round():
    config = resolve("silo8.cc_power").config
    assert FAMILY.forward_flops(config) == 2 * 555_468_800
    assert FAMILY.train_flops(config) == 3 * FAMILY.forward_flops(config)
    # 8 clients x 5 steps x 64 images: the 8.5 TFLOP of one round
    assert FAMILY.train_flops(config) * 8 * 5 * 64 / 1e12 == pytest.approx(
        8.53, abs=0.01)


def test_peaks_table():
    assert chip_peaks("TPU v5 lite")["flops_bf16"] == 197e12
    assert PEAKS["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        chip_peaks("cpu")
