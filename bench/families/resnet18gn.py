"""ResNet-18 with GroupNorm on CIFAR-shaped images: the ``resnet18gn`` family.

A family is everything the benchmark knows about one kind of model. The
harness finds it by the ``family`` of a configuration's ``model`` block
(``bench/families/<family>.py``) and calls only the functions below;
nothing else under ``bench/`` names a model.

* Data: CIFAR-shaped images made on the device in one jitted call. Each
  class has a smooth template (a 4×4 random pattern, upsampled) and an
  image is its class's template plus Gaussian noise. Client ``i`` of
  ``N`` holds ``n_local`` images: a ``gamma`` share with labels uniform
  over all classes and the rest from its own contiguous block of
  ``n_classes / N`` classes (the paper's γ-heterogeneity,
  arXiv:2212.13679 §VI-A). Every client holds the same number of images
  whatever the seed.
* Weights: the plain reference's initialisation
  (``bench/reference/resnet18gn.py``), on the device in one jitted call.
* The program's model: ``repro.models.simple.make_classifier``.
* The loss: the plain reference's cross-entropy.
* FLOPs: the multiply-accumulates of every convolution and of the dense
  layer, as the published architecture defines them, times two.
  GroupNorm, ReLU, the residual adds and pooling are elementwise and are
  left out, as model-FLOP utilisation counts them by convention. A
  training step needs three forward passes' worth: the forward pass, and
  the two products of the backward pass.
"""
from __future__ import annotations

import copy
import functools

import jax
import jax.numpy as jnp

from bench.reference import resnet18gn


@functools.partial(jax.jit, static_argnames=(
    "n_clients", "n_local", "n_test", "hw", "channels", "n_classes",
    "gamma", "noise"))
def _images(key, *, n_clients, n_local, n_test, hw, channels, n_classes,
            gamma, noise):
    k_t, k_iid, k_sh, k_x, k_ty, k_tx = jax.random.split(key, 6)
    tmpl = jax.random.normal(k_t, (n_classes, 4, 4, channels))
    tmpl = jnp.repeat(jnp.repeat(tmpl, hw // 4, axis=1), hw // 4, axis=2)
    n_iid = int(round(gamma * n_local))
    y_iid = jax.random.randint(k_iid, (n_clients, n_iid), 0, n_classes)
    lo = (jnp.arange(n_clients) * n_classes) // n_clients
    hi = ((jnp.arange(n_clients) + 1) * n_classes) // n_clients
    u = jax.random.uniform(k_sh, (n_clients, n_local - n_iid))
    y_sh = lo[:, None] + jnp.floor(u * (hi - lo)[:, None]).astype(jnp.int32)
    y = jnp.concatenate([y_iid, y_sh], axis=1).astype(jnp.int32)
    x = tmpl[y] + noise * jax.random.normal(
        k_x, (n_clients, n_local, hw, hw, channels))
    y_test = jax.random.randint(k_ty, (n_test,), 0, n_classes
                                ).astype(jnp.int32)
    x_test = tmpl[y_test] + noise * jax.random.normal(
        k_tx, (n_test, hw, hw, channels))
    sizes = jnp.full((n_clients,), n_local, jnp.int32)
    return x, y, sizes, x_test, y_test


@functools.partial(jax.jit, static_argnames=("channels", "n_classes",
                                             "width"))
def _weights(key, *, channels, n_classes, width):
    return resnet18gn.init(key, channels, n_classes, width)


def make_data(config: dict, words) -> tuple:
    """Client images and labels, and a test split, all on the device,
    from the seed's data words (``words[0]``)."""
    fed, model = config["federation"], config["model"]
    return _images(jax.random.PRNGKey(words[0]),
                   n_clients=fed["n_clients"],
                   n_local=fed["samples_per_client"],
                   n_test=fed["test_samples"], hw=model["image_size"],
                   channels=model["channels"],
                   n_classes=model["n_classes"], gamma=fed["gamma"],
                   noise=fed["noise"])


def init_params(config: dict, key):
    """The initial global model, float32, on the device."""
    model = config["model"]
    return _weights(key, channels=model["channels"],
                    n_classes=model["n_classes"], width=model["width"])


def build_model(config: dict):
    """The program's ResNet-18-GN classifier at the configuration's sizes."""
    from repro.models.simple import make_classifier
    model = config["model"]
    return make_classifier(
        model["arch"], n_classes=model["n_classes"], width=model["width"],
        input_shape=(model["image_size"], model["image_size"],
                     model["channels"]))


def loss(params, xb, yb, config: dict):
    """Mean cross-entropy of the plain reference model."""
    return resnet18gn.xent(params, xb, yb, config["model"]["groups"])


def _conv_macs(out_hw: int, c_in: int, c_out: int, k: int) -> int:
    return out_hw * out_hw * c_out * c_in * k * k


def _forward_macs(image_size: int, channels: int, n_classes: int,
                  width: int) -> int:
    """Multiply-accumulates of one image's forward pass."""
    macs = _conv_macs(image_size, channels, width, 3)          # stem
    hw, c_in = image_size, width
    for s, stride in enumerate(resnet18gn.STRIDES):
        c = width * 2 ** s
        hw = hw // stride
        macs += _conv_macs(hw, c_in, c, 3)                      # b0 conv1
        macs += _conv_macs(hw, c, c, 3)                         # b0 conv2
        if stride != 1 or c_in != c:
            macs += _conv_macs(hw, c_in, c, 1)                  # projection
        macs += 2 * _conv_macs(hw, c, c, 3)                     # b1
        c_in = c
    return macs + c_in * n_classes                              # dense


def forward_flops(config: dict) -> int:
    """FLOPs of one image's forward pass."""
    m = config["model"]
    return 2 * _forward_macs(m["image_size"], m["channels"],
                             m["n_classes"], m["width"])


def train_flops(config: dict) -> int:
    """FLOPs one image costs in a training step: forward and backward."""
    return 3 * forward_flops(config)


def shrink(config: dict) -> dict:
    """The configuration at a size the CPU test run holds: width 16 on
    16×16 images of 10 classes, 64 images a client, batch 8; the
    structure (8 clients, strategy, executor, history) unchanged."""
    c = copy.deepcopy(config)
    c["model"].update(width=16, image_size=16, n_classes=10)
    c["federation"].update(samples_per_client=64, test_samples=64)
    c["training"].update(batch_size=8)
    return c
