"""Loss functions. Cross-entropy is chunked over the sequence so the
(B, S, vocab) logits tensor is never materialized — essential for the
256k-vocab architectures at 4k×256 batch.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _chunk_xent(hidden_c, targets_c, mask_c, head, bias=None):
    """hidden_c: (B, C, D); targets_c: (B, C) int; mask_c: (B, C) float.
    head: (D, V). Returns (sum_loss, sum_correct, sum_mask)."""
    logits = hidden_c.astype(jnp.float32) @ head.astype(jnp.float32)
    if bias is not None:
        logits = logits + bias
    lse = jax.nn.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets_c[..., None],
                              axis=-1)[..., 0]
    nll = (lse - tgt) * mask_c
    correct = (jnp.argmax(logits, axis=-1) == targets_c) * mask_c
    return jnp.sum(nll), jnp.sum(correct), jnp.sum(mask_c)


def chunked_causal_xent(hidden, targets, mask, head, *, chunk: int = 512):
    """Mean next-token cross-entropy with seq-chunked logits.

    hidden: (B, S, D); targets/mask: (B, S). head: (D, V).
    Returns (loss, accuracy).
    """
    b, s, d = hidden.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1
    nc = s // chunk

    if nc == 1:
        tot, cor, cnt = _chunk_xent(hidden, targets, mask, head)
        cnt = jnp.maximum(cnt, 1.0)
        return tot / cnt, cor / cnt

    hs = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, nc, chunk).transpose(1, 0, 2)
    ms = mask.reshape(b, nc, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        tot, cor, cnt = carry
        h_c, t_c, m_c = xs
        a, b_, c = jax.checkpoint(_chunk_xent)(h_c, t_c, m_c, head)
        return (tot + a, cor + b_, cnt + c), None

    (tot, cor, cnt), _ = jax.lax.scan(
        body, (jnp.zeros(()), jnp.zeros(()), jnp.zeros(())), (hs, ts, ms))
    cnt = jnp.maximum(cnt, 1.0)
    return tot / cnt, cor / cnt


def token_sums(hidden, targets, head, *, chunk: int, hits: bool = False):
    """Σ over tokens of the next-token NLL (or, with ``hits``, of the
    top-1 hits), ``chunk`` tokens' logits at a time.

    hidden: (T, D) in the compute dtype; targets: (T,) int; head: (D, V).
    The logits are computed in float32 from the compute-dtype operands,
    one (chunk, V) block at a time, and rematerialized in the backward
    pass, so no (T, V) tensor is ever held."""
    t, d = hidden.shape
    chunk = min(chunk, t)
    while t % chunk:
        chunk -= 1

    def one(h_c, y_c):
        logits = jnp.matmul(h_c, head.astype(h_c.dtype),
                            preferred_element_type=jnp.float32)
        if hits:
            return jnp.sum(jnp.argmax(logits, axis=-1) == y_c,
                           dtype=jnp.int32)
        tgt = jnp.take_along_axis(logits, y_c[:, None], axis=-1)[:, 0]
        return jnp.sum(jax.nn.logsumexp(logits, axis=-1) - tgt)

    def body(acc, xs):
        return acc + jax.checkpoint(one)(*xs), None

    zero = jnp.zeros((), jnp.int32 if hits else jnp.float32)
    out, _ = jax.lax.scan(body, zero, (hidden.reshape(t // chunk, chunk, d),
                                       targets.reshape(t // chunk, chunk)))
    return out


def multihead_codebook_xent(hidden, targets, mask, heads, *, chunk: int = 512):
    """MusicGen-style loss over K codebook heads.

    hidden: (B, S, D); targets: (B, K, S); mask: (B, S); heads: (K, D, V).
    Mean over codebooks of per-codebook xent.
    """
    k = heads.shape[0]
    losses, accs = [], []
    for j in range(k):
        l, a = chunked_causal_xent(hidden, targets[:, j], mask, heads[j],
                                   chunk=chunk)
        losses.append(l)
        accs.append(a)
    return (jnp.mean(jnp.stack(losses)), jnp.mean(jnp.stack(accs)))
