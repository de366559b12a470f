"""Operations a ResNet-18-GN forward pass requires, counted from its shapes.

Counts the multiply-accumulates of every convolution and of the dense
layer, as the published architecture defines them (see
``bench/reference/resnet18gn.py``), and reports FLOPs as twice that.
GroupNorm, ReLU, the residual adds and pooling are elementwise and are
left out, as model-FLOP utilisation counts them by convention. A
training step needs three forward passes' worth: the forward pass, and
the two products of the backward pass.
"""
from __future__ import annotations

STRIDES = (1, 2, 2, 2)


def _conv_macs(out_hw: int, c_in: int, c_out: int, k: int) -> int:
    return out_hw * out_hw * c_out * c_in * k * k


def resnet18_forward_macs(image_size: int, channels: int, n_classes: int,
                          width: int) -> int:
    """Multiply-accumulates of one image's forward pass."""
    macs = _conv_macs(image_size, channels, width, 3)          # stem
    hw, c_in = image_size, width
    for s, stride in enumerate(STRIDES):
        c = width * 2 ** s
        hw = hw // stride
        macs += _conv_macs(hw, c_in, c, 3)                      # b0 conv1
        macs += _conv_macs(hw, c, c, 3)                         # b0 conv2
        if stride != 1 or c_in != c:
            macs += _conv_macs(hw, c_in, c, 1)                  # projection
        macs += 2 * _conv_macs(hw, c, c, 3)                     # b1
        c_in = c
    return macs + c_in * n_classes                              # dense


def forward_flops(model: dict) -> int:
    """FLOPs of one image's forward pass for a configuration's ``model``."""
    return 2 * resnet18_forward_macs(model["image_size"], model["channels"],
                                     model["n_classes"], model["width"])


def train_flops(model: dict) -> int:
    """FLOPs one image costs in a training step: forward and backward."""
    return 3 * forward_flops(model)
