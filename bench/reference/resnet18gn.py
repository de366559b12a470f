"""Plain ResNet-18 with GroupNorm, written from its published description.

He et al., "Deep Residual Learning for Image Recognition" (arXiv:1512.03385),
in the CIFAR form the CC-FedAvg paper trains (arXiv:2212.13679, §VI-A):
a 3×3 stem of ``width`` channels at the input resolution, four stages of
two basic blocks with ``width·(1, 2, 4, 8)`` channels and strides
(1, 2, 2, 2), a 1×1 projection wherever a block changes shape, global
average pooling and one dense layer. Every batch norm is a GroupNorm of
``groups`` groups (Wu & He, arXiv:1803.08494), eps 1e-5, and the
convolutions carry no bias.

Parameters are a nested dict whose keys follow the federated program's
layout (``stem``, ``gn_stem``, ``s{stage}b{block}``, ``fc``), so the same
tree can be handed to both. Nothing here imports the program.

``dtype`` selects the arithmetic: ``float32`` runs every convolution and
matrix product at ``Precision.HIGHEST``; ``bfloat16`` runs the whole
network, norms included, in bfloat16 (the lower-precision control).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

STRIDES = (1, 2, 2, 2)
EPS = 1e-5


def stage_channels(width: int) -> tuple[int, ...]:
    return (width, 2 * width, 4 * width, 8 * width)


def _precision(dtype):
    return (lax.Precision.HIGHEST if jnp.dtype(dtype) == jnp.float32
            else lax.Precision.DEFAULT)


def conv(w, x, stride: int = 1):
    """NHWC × HWIO convolution with SAME padding."""
    return lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=_precision(x.dtype))


def group_norm(p, x, groups: int):
    n, h, w, c = x.shape
    xg = x.reshape(n, h, w, groups, c // groups)
    mu = jnp.mean(xg, axis=(1, 2, 4), keepdims=True)
    var = jnp.mean(jnp.square(xg - mu), axis=(1, 2, 4), keepdims=True)
    y = ((xg - mu) / jnp.sqrt(var + EPS)).reshape(n, h, w, c)
    return y * p["scale"].astype(x.dtype) + p["bias"].astype(x.dtype)


def basic_block(p, x, stride: int, groups: int):
    y = jax.nn.relu(group_norm(p["gn1"], conv(p["conv1"]["w"], x, stride),
                               groups))
    y = group_norm(p["gn2"], conv(p["conv2"]["w"], y), groups)
    if "proj" in p:
        x = group_norm(p["gn_proj"], conv(p["proj"]["w"], x, stride), groups)
    return jax.nn.relu(x + y)


def forward(params, x, groups: int = 8):
    """Logits of a batch of NHWC images, in ``x``'s dtype."""
    x = jax.nn.relu(group_norm(params["gn_stem"],
                               conv(params["stem"]["w"], x), groups))
    for s, stride in enumerate(STRIDES):
        x = basic_block(params[f"s{s}b0"], x, stride, groups)
        x = basic_block(params[f"s{s}b1"], x, 1, groups)
    x = jnp.mean(x, axis=(1, 2))
    fc = params["fc"]
    return (jnp.dot(x, fc["w"].astype(x.dtype), precision=_precision(x.dtype))
            + fc["b"].astype(x.dtype))


def xent(params, x, y, groups: int = 8):
    """Mean softmax cross-entropy of integer labels ``y``."""
    logits = forward(params, x, groups)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def init(key, channels: int, n_classes: int, width: int):
    """He-normal convolutions, unit GroupNorm scales, zero biases, and a
    LeCun-normal dense layer, all float32."""
    def he(k, kh, c_in, c_out):
        std = math.sqrt(2.0 / (kh * kh * c_in))
        return {"w": std * jax.random.normal(k, (kh, kh, c_in, c_out))}

    def gn(c):
        return {"scale": jnp.ones((c,)), "bias": jnp.zeros((c,))}

    widths = stage_channels(width)
    keys = iter(jax.random.split(key, 32))
    p = {"stem": he(next(keys), 3, channels, width), "gn_stem": gn(width)}
    c_in = width
    for s, (c, stride) in enumerate(zip(widths, STRIDES)):
        for b in range(2):
            cin_b, st = (c_in, stride) if b == 0 else (c, 1)
            blk = {"conv1": he(next(keys), 3, cin_b, c), "gn1": gn(c),
                   "conv2": he(next(keys), 3, c, c), "gn2": gn(c)}
            if st != 1 or cin_b != c:
                blk["proj"] = he(next(keys), 1, cin_b, c)
                blk["gn_proj"] = gn(c)
            p[f"s{s}b{b}"] = blk
        c_in = c
    d = widths[-1]
    p["fc"] = {"w": jax.random.normal(next(keys), (d, n_classes))
               / math.sqrt(d),
               "b": jnp.zeros((n_classes,))}
    return p
