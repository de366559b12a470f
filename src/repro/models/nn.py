"""Functional neural-network primitives (no flax/haiku dependency).

Every layer is a pair of pure functions:
  ``<layer>_init(rng, ...) -> params``  and  ``<layer>_apply(params, x, ...)``.
Params are plain nested dicts of jnp arrays so the federated engine can treat
every model uniformly as a pytree vector space.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Sequence

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------


def lecun_normal(rng, shape, in_axis: int = 0, dtype=jnp.float32):
    fan_in = shape[in_axis] if in_axis is not None else shape[0]
    std = 1.0 / math.sqrt(max(1, fan_in))
    return (std * jax.random.normal(rng, shape)).astype(dtype)


def he_normal(rng, shape, fan_in: int, dtype=jnp.float32):
    std = math.sqrt(2.0 / max(1, fan_in))
    return (std * jax.random.normal(rng, shape)).astype(dtype)


def normal_init(rng, shape, std: float = 0.02, dtype=jnp.float32):
    return (std * jax.random.normal(rng, shape)).astype(dtype)


# ---------------------------------------------------------------------------
# dense / embedding
# ---------------------------------------------------------------------------


def dense_init(rng, d_in: int, d_out: int, *, bias: bool = True,
               std: float | None = None, dtype=jnp.float32):
    wkey, _ = jax.random.split(rng)
    if std is None:
        w = lecun_normal(wkey, (d_in, d_out), dtype=dtype)
    else:
        w = normal_init(wkey, (d_in, d_out), std=std, dtype=dtype)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype=dtype)
    return p


@functools.partial(jax.tree_util.register_dataclass,
                   data_fields=["w", "a", "b"], meta_fields=["scale"])
@dataclasses.dataclass(frozen=True)
class Adapted:
    """A frozen weight ``w`` (..., d_in, d_out) with a LoRA update
    ``scale · a @ b``, applied unmerged: ``x @ adapted`` is
    ``x @ w + scale · (x @ a) @ b``, so the backward pass needs no
    d_in × d_out weight gradient, only the factors'. A pytree (a scanned
    segment slices its stacked layers) that stands wherever a layer writes
    ``x @ p[name].astype(dtype)``."""
    w: jax.Array
    a: jax.Array
    b: jax.Array
    scale: float

    def astype(self, dtype) -> "Adapted":
        return Adapted(self.w.astype(dtype), self.a.astype(dtype),
                       self.b.astype(dtype), self.scale)

    def __rmatmul__(self, x):
        return x @ self.w + self.scale * ((x @ self.a) @ self.b)

    def merged(self) -> jax.Array:
        """``w + scale · a @ b`` as one array, in ``w``'s dtype."""
        delta = jnp.einsum("...ir,...ro->...io", self.a, self.b)
        return self.w + (self.scale * delta).astype(self.w.dtype)


def dense_weight(w):
    """``w`` as one array: an :class:`Adapted` weight merged."""
    return w.merged() if isinstance(w, Adapted) else w


def dense_apply(p, x):
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def embedding_init(rng, vocab: int, d: int, *, std: float = 0.02,
                   dtype=jnp.float32):
    return {"table": normal_init(rng, (vocab, d), std=std, dtype=dtype)}


def embedding_apply(p, ids):
    return jnp.take(p["table"], ids, axis=0)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype=dtype)}


def rmsnorm_apply(p, x, eps: float = 1e-6):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


def layernorm_init(d: int, dtype=jnp.float32):
    return {"scale": jnp.ones((d,), dtype=dtype),
            "bias": jnp.zeros((d,), dtype=dtype)}


def layernorm_apply(p, x, eps: float = 1e-5):
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["scale"] + p["bias"]).astype(dt)


def groupnorm_init(c: int, dtype=jnp.float32):
    return {"scale": jnp.ones((c,), dtype=dtype),
            "bias": jnp.zeros((c,), dtype=dtype)}


def groupnorm_apply(p, x, n_groups: int = 32, eps: float = 1e-5):
    """GroupNorm over NHWC inputs (used by ResNet-18(GN), paper §VI-A)."""
    n, h, w, c = x.shape
    g = min(n_groups, c)
    while c % g != 0:  # keep group count valid for small channel dims
        g -= 1
    x32 = x.astype(jnp.float32).reshape(n, h, w, g, c // g)
    mu = jnp.mean(x32, axis=(1, 2, 4), keepdims=True)
    var = jnp.var(x32, axis=(1, 2, 4), keepdims=True)
    y = ((x32 - mu) * jax.lax.rsqrt(var + eps)).reshape(n, h, w, c)
    return (y * p["scale"] + p["bias"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# conv / pooling (paper's CNN + ResNet-18)
# ---------------------------------------------------------------------------


def conv2d_init(rng, c_in: int, c_out: int, k: int, *, bias: bool = True,
                dtype=jnp.float32):
    w = he_normal(rng, (k, k, c_in, c_out), fan_in=k * k * c_in, dtype=dtype)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((c_out,), dtype=dtype)
    return p


def conv2d_apply(p, x, stride: int = 1, padding: str = "SAME"):
    y = jax.lax.conv_general_dilated(
        x, p["w"], window_strides=(stride, stride), padding=padding,
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    if "b" in p:
        y = y + p["b"]
    return y


def max_pool(x, k: int = 2, stride: int | None = None):
    stride = stride or k
    return jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, k, k, 1), (1, stride, stride, 1), "VALID")


def avg_pool_global(x):
    return jnp.mean(x, axis=(1, 2))


# ---------------------------------------------------------------------------
# rotary position embeddings (standard + M-RoPE sections)
# ---------------------------------------------------------------------------


def rope_frequencies(head_dim: int, theta: float = 10000.0) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def rope_cos_sin(positions: jax.Array, head_dim: int,
                 theta: float = 10000.0):
    """positions: (..., S) int -> cos/sin of shape (..., S, head_dim//2)."""
    freqs = rope_frequencies(head_dim, theta)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """Rotate pairs (x_even, x_odd). x: (B, S, H, hd); cos/sin: (B, S, hd//2)."""
    dt = x.dtype
    x1 = x[..., 0::2].astype(jnp.float32)
    x2 = x[..., 1::2].astype(jnp.float32)
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    r1 = x1 * c - x2 * s
    r2 = x2 * c + x1 * s
    out = jnp.stack([r1, r2], axis=-1).reshape(x.shape)
    return out.astype(dt)


def mrope_cos_sin(positions_3: jax.Array, head_dim: int,
                  sections: Sequence[int], theta: float = 10000.0):
    """Multimodal RoPE (Qwen2-VL, arXiv:2409.12191).

    positions_3: (3, B, S) — temporal / height / width position ids.
    sections: half-dim split, e.g. (16, 12, 12) summing to head_dim//2.
    Returns cos/sin of shape (B, S, head_dim//2) assembled per-section from
    the corresponding position row.
    """
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    freqs = rope_frequencies(head_dim, theta)  # (hd//2,)
    cos_parts, sin_parts = [], []
    off = 0
    for row, sec in enumerate(sections):
        f = freqs[off:off + sec]
        ang = positions_3[row][..., None].astype(jnp.float32) * f
        cos_parts.append(jnp.cos(ang))
        sin_parts.append(jnp.sin(ang))
        off += sec
    return jnp.concatenate(cos_parts, -1), jnp.concatenate(sin_parts, -1)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


def gelu(x: jax.Array) -> jax.Array:
    return jax.nn.gelu(x, approximate=True)
