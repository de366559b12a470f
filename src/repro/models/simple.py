"""The paper's experiment models (§VI-A): a 3-layer MLP (FMNIST), a
2-conv + 3-fc CNN (CIFAR-10) and ResNet-18 with GroupNorm (CIFAR-100) —
re-implemented functionally so the federated engine can vmap them over a
client axis. Width/variant knobs let the synthetic-data reproductions run
within the CPU budget.

A :class:`Classifier` may carry a frozen ``base`` (LoRA over a zoo model,
a language model's weights): its ``apply``, ``loss`` and ``hits`` then take
the base as their last argument, and every jitted program of the engine
takes it as an argument, so that no program holds it as a constant and the
trainable ``params`` stay the small tree. ``loss`` and ``hits`` let a model
bring its own objective and evaluation (a next-token loss over a vocabulary
too large for one logits tensor); without them the engine uses the
cross-entropy and top-1 hits of ``apply``'s logits.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.models import nn


class Classifier(NamedTuple):
    name: str
    init: Callable          # rng -> params
    apply: Callable         # params, x[, base] -> logits
    #: frozen weights, never trained: the last argument of apply/loss/hits
    base: Any = None
    #: params, xb, yb[, base] -> mean loss over the batch's targets
    loss: Callable | None = None
    #: params, x, y[, base] -> (correct predictions, targets) for evaluation
    hits: Callable | None = None


def frozen(model: Classifier) -> tuple:
    """The trailing arguments that hand ``model`` its base: ``(base,)``,
    or nothing for a model without one."""
    return () if model.base is None else (model.base,)


# ---------------------------------------------------------------------------
# MLP (paper: 3 fully-connected layers for FMNIST)
# ---------------------------------------------------------------------------


def make_mlp(input_dim: int, n_classes: int, hidden: int = 64) -> Classifier:
    def init(rng):
        k1, k2, k3 = jax.random.split(rng, 3)
        return {
            "fc1": nn.dense_init(k1, input_dim, hidden),
            "fc2": nn.dense_init(k2, hidden, hidden),
            "fc3": nn.dense_init(k3, hidden, n_classes),
        }

    def apply(p, x):
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(nn.dense_apply(p["fc1"], x))
        x = jax.nn.relu(nn.dense_apply(p["fc2"], x))
        return nn.dense_apply(p["fc3"], x)

    return Classifier("mlp", init, apply)


# ---------------------------------------------------------------------------
# CNN (paper: two conv-pool layers + three fc layers for CIFAR-10)
# ---------------------------------------------------------------------------


def make_cnn(hw: int, channels: int, n_classes: int,
             width: int = 16) -> Classifier:
    flat = (hw // 4) * (hw // 4) * (2 * width)

    def init(rng):
        ks = jax.random.split(rng, 5)
        return {
            "conv1": nn.conv2d_init(ks[0], channels, width, 3),
            "conv2": nn.conv2d_init(ks[1], width, 2 * width, 3),
            "fc1": nn.dense_init(ks[2], flat, 4 * width),
            "fc2": nn.dense_init(ks[3], 4 * width, 2 * width),
            "fc3": nn.dense_init(ks[4], 2 * width, n_classes),
        }

    def apply(p, x):
        x = jax.nn.relu(nn.conv2d_apply(p["conv1"], x))
        x = nn.max_pool(x, 2)
        x = jax.nn.relu(nn.conv2d_apply(p["conv2"], x))
        x = nn.max_pool(x, 2)
        x = x.reshape(x.shape[0], -1)
        x = jax.nn.relu(nn.dense_apply(p["fc1"], x))
        x = jax.nn.relu(nn.dense_apply(p["fc2"], x))
        return nn.dense_apply(p["fc3"], x)

    return Classifier("cnn", init, apply)


# ---------------------------------------------------------------------------
# ResNet-18 with GroupNorm (paper: CIFAR-100); `width` scales channels
# ---------------------------------------------------------------------------


def _basic_block_init(rng, c_in, c_out, stride):
    ks = jax.random.split(rng, 3)
    p = {
        "conv1": nn.conv2d_init(ks[0], c_in, c_out, 3, bias=False),
        "gn1": nn.groupnorm_init(c_out),
        "conv2": nn.conv2d_init(ks[1], c_out, c_out, 3, bias=False),
        "gn2": nn.groupnorm_init(c_out),
    }
    if stride != 1 or c_in != c_out:
        p["proj"] = nn.conv2d_init(ks[2], c_in, c_out, 1, bias=False)
        p["gn_proj"] = nn.groupnorm_init(c_out)
    return p


def _basic_block_apply(p, x, stride, groups):
    y = nn.conv2d_apply(p["conv1"], x, stride=stride)
    y = jax.nn.relu(nn.groupnorm_apply(p["gn1"], y, groups))
    y = nn.conv2d_apply(p["conv2"], y)
    y = nn.groupnorm_apply(p["gn2"], y, groups)
    if "proj" in p:
        x = nn.groupnorm_apply(
            p["gn_proj"], nn.conv2d_apply(p["proj"], x, stride=stride),
            groups)
    return jax.nn.relu(x + y)


def make_resnet18(channels: int, n_classes: int, width: int = 16,
                  groups: int = 8) -> Classifier:
    stage_channels = [width, 2 * width, 4 * width, 8 * width]
    strides = [1, 2, 2, 2]

    def init(rng):
        ks = jax.random.split(rng, 10)
        p = {"stem": nn.conv2d_init(ks[0], channels, width, 3, bias=False),
             "gn_stem": nn.groupnorm_init(width)}
        c_in = width
        i = 1
        for s, (c, st) in enumerate(zip(stage_channels, strides)):
            p[f"s{s}b0"] = _basic_block_init(ks[i], c_in, c, st)
            p[f"s{s}b1"] = _basic_block_init(ks[i + 1], c, c, 1)
            c_in = c
            i += 2
        p["fc"] = nn.dense_init(ks[9], stage_channels[-1], n_classes)
        return p

    def apply(p, x):
        x = jax.nn.relu(nn.groupnorm_apply(
            p["gn_stem"], nn.conv2d_apply(p["stem"], x), groups))
        for s, st in enumerate(strides):
            x = _basic_block_apply(p[f"s{s}b0"], x, st, groups)
            x = _basic_block_apply(p[f"s{s}b1"], x, 1, groups)
        x = nn.avg_pool_global(x)
        return nn.dense_apply(p["fc"], x)

    return Classifier("resnet18gn", init, apply)


# ---------------------------------------------------------------------------
# classification loss / metrics
# ---------------------------------------------------------------------------


def xent_loss(model: Classifier, params, xb, yb) -> jax.Array:
    """The local objective: the model's own ``loss`` where it has one, else
    the mean cross-entropy of its logits."""
    if model.loss is not None:
        return model.loss(params, xb, yb, *frozen(model))
    logits = model.apply(params, xb, *frozen(model)).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, yb[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def accuracy(model: Classifier, params, xb, yb) -> jax.Array:
    logits = model.apply(params, xb, *frozen(model))
    return jnp.mean((jnp.argmax(logits, -1) == yb).astype(jnp.float32))


def make_classifier(kind: str, *, input_shape, n_classes: int,
                    width: int = 16) -> Classifier:
    if kind == "mlp":
        dim = 1
        for d in input_shape:
            dim *= d
        return make_mlp(dim, n_classes, hidden=4 * width)
    if kind == "cnn":
        hw, _, ch = (input_shape + (1,))[:3] if len(input_shape) >= 2 \
            else (input_shape[0], input_shape[0], 1)
        return make_cnn(hw, ch, n_classes, width=width)
    if kind == "resnet18":
        ch = input_shape[-1] if len(input_shape) == 3 else 1
        return make_resnet18(ch, n_classes, width=width)
    raise ValueError(f"unknown classifier {kind!r}")
