"""Moonlight-16B-A3B in plain ``jax.numpy``: the forward pass and the
next-token loss, float32, at ``"highest"`` matmul precision.

It follows the published modelling code of ``deepseek_v3``
(``modeling_deepseek.py`` of hf:moonshotai/Moonlight-16B-A3B), layer by
layer, and imports nothing of the program:

* attention: ``q_proj`` (no q_lora), ``kv_a_proj_with_mqa`` → the 512-wide
  latent, RMS-normed by ``kv_a_layernorm``, and the 64-wide shared rope key;
  ``kv_b_proj`` maps the latent to each head's 128 nope-key and 128 value
  dims; RoPE (θ from the config, no scaling) on the 64 rope dims as that
  code applies it: each head's rope dims de-interleaved (even, then odd),
  then rotated by rotate-half; softmax scale (128 + 64)^-1/2, causal;
  ``o_proj``;
* FFN: layer 0 a SwiGLU of width ``intermediate_size``; later layers the
  ``noaux_tc`` gate (sigmoid scores of float32 logits, the top-k chosen on
  score + ``e_score_correction_bias``, weights the chosen scores without
  the bias, normalized, times ``routed_scaling_factor``) over the routed
  experts plus the shared experts as one SwiGLU;
* RMSNorm with the config's eps; an untied ``lm_head``.

Where it departs from that code, or from a deployment:

* every routed expert is computed for every token and masked to the
  chosen ones, in blocks of experts (no sorting, no capacity, nothing
  dropped), each block's weights made when it runs;
* the router is frozen with the rest of the base in LoRA fine-tuning, so
  the loss has no ``seq_aux`` balance term; there are no multi-token
  prediction layers (``num_nextn_predict_layers`` is 0 anyway);
* each LoRA-adapted projection is ``W + (alpha / r) · A @ B``, with the
  adapters of ``kv_b_proj`` on its key half (``wk_b``) and value half
  (``wv_b``);
* the weights are not held: ``weights`` (see :class:`Weights`) makes each
  block of the frozen base when the computation reaches it, in bfloat16,
  and it is upcast there. So the whole step is one program without the
  base in it as a constant, and each layer and each block of experts is
  rematerialized for the backward pass, so that the reference fits on one
  chip after the program's session is freed.

The weight names are the program's tree layout (``mixer/wq`` is
``q_proj``, ``mixer/wkv_a`` ``kv_a_proj_with_mqa``, ``mixer/wk_b`` and
``mixer/wv_b`` the two halves of ``kv_b_proj``, each (512, heads · 128),
``mixer/wo`` ``o_proj``; ``ffn/score_bias`` the correction bias).
"""
from __future__ import annotations

import math
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


class Dims(NamedTuple):
    d: int            # hidden_size
    heads: int
    nope: int         # qk_nope_head_dim
    rope: int         # qk_rope_head_dim
    v: int            # v_head_dim
    kv_rank: int      # kv_lora_rank
    experts: int      # n_routed_experts
    top_k: int
    scale: float      # routed_scaling_factor
    layers: int       # layers held: layer 0 dense, the rest MoE
    eps: float
    theta: float
    lora_scale: float  # alpha / r


#: routed experts computed at once, and tokens whose logits are
EXPERT_BLOCK, TOKEN_CHUNK = 8, 512


class Weights(NamedTuple):
    """The frozen base, made on demand, in bfloat16. ``layer(l)`` gives
    layer ``l``'s weights other than its routed experts, by program path
    (``norm1/scale``, ``mixer/wq`` …, ``ffn/w_gate`` …); ``experts(l,
    name, first, count)`` gives ``count`` routed experts' ``name`` weights
    (``w_gate``, ``w_up``, ``w_down``) of layer ``l`` from ``first``
    (which may be traced), stacked; ``top()`` gives ``embed/table``,
    ``final_norm/scale`` and ``lm_head``."""
    layer: Callable
    experts: Callable
    top: Callable


def _mm(a, b):
    return jnp.matmul(a, b, precision=HIGHEST)


def _f32(x):
    return x.astype(jnp.float32)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * _f32(scale)


def _adapted(w, lora, name, l, dims: Dims):
    """Layer ``l``'s weight ``name`` with its LoRA update, if it has one."""
    w = _f32(w)
    ab = lora.get(name)
    if ab is None:
        return w
    a, b = ab
    return w + dims.lora_scale * _mm(_f32(a[l]), _f32(b[l]))


def _rotate(x, positions, theta):
    """RoPE as the published code applies it to the last axis of ``x``:
    de-interleave the pairs, then rotate-half with cos/sin of position ·
    θ^(-2i/dim)."""
    dim = x.shape[-1]
    x = x.reshape(x.shape[:-1] + (dim // 2, 2))
    x = jnp.swapaxes(x, -1, -2).reshape(x.shape[:-2] + (dim,))
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    freqs = positions[:, None].astype(jnp.float32) * inv[None, :]
    emb = jnp.concatenate([freqs, freqs], axis=-1)            # (S, dim)
    cos = jnp.cos(emb)[None, :, None, :]
    sin = jnp.sin(emb)[None, :, None, :]
    half = jnp.concatenate([-x[..., dim // 2:], x[..., :dim // 2]], -1)
    return x * cos + half * sin


def attention(h, w, lora, l, dims: Dims):
    """The MLA block's output for normed input h (B, S, d)."""
    b, s, _ = h.shape
    hd = dims.nope + dims.rope
    q = _mm(h, _adapted(w["mixer/wq"], lora, "mixer/wq", l, dims))
    q = q.reshape(b, s, dims.heads, hd)
    q_nope, q_pe = q[..., :dims.nope], q[..., dims.nope:]
    ckv = _mm(h, _adapted(w["mixer/wkv_a"], lora, "mixer/wkv_a", l, dims))
    latent, k_pe = ckv[..., :dims.kv_rank], ckv[..., dims.kv_rank:]
    latent = _rmsnorm(latent, w["mixer/kv_norm/scale"], dims.eps)
    # kv_b_proj: each head's nope-key then value dims
    wk = _adapted(w["mixer/wk_b"], lora, "mixer/wk_b", l, dims)
    wv = _adapted(w["mixer/wv_b"], lora, "mixer/wv_b", l, dims)
    kv_b = jnp.concatenate(
        [wk.reshape(dims.kv_rank, dims.heads, dims.nope),
         wv.reshape(dims.kv_rank, dims.heads, dims.v)], axis=-1)
    kv = _mm(latent, kv_b.reshape(dims.kv_rank, -1)).reshape(
        b, s, dims.heads, dims.nope + dims.v)
    k_nope, value = kv[..., :dims.nope], kv[..., dims.nope:]
    positions = jnp.arange(s)
    q_pe = _rotate(q_pe, positions, dims.theta)
    k_pe = _rotate(k_pe[:, :, None, :], positions, dims.theta)
    q = jnp.concatenate([q_nope, q_pe], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_pe, (b, s, dims.heads, dims.rope))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                        precision=HIGHEST) / math.sqrt(hd)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, value, precision=HIGHEST)
    return _mm(out.reshape(b, s, dims.heads * dims.v),
               _adapted(w["mixer/wo"], lora, "mixer/wo", l, dims))


def _swiglu(x, gate, up, down):
    return _mm(jax.nn.silu(_mm(x, _f32(gate))) * _mm(x, _f32(up)),
               _f32(down))


def route(x, router, bias, dims: Dims):
    """The ``noaux_tc`` gate: a (T, experts) matrix holding each token's
    routing weight of each expert, zero where it is not chosen."""
    scores = jax.nn.sigmoid(_mm(x, _f32(router)))
    _, idx = jax.lax.top_k(scores + _f32(bias), dims.top_k)
    chosen = jnp.take_along_axis(scores, idx, axis=-1)
    chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    chosen = chosen * dims.scale
    return jnp.sum(jax.nn.one_hot(idx, dims.experts) * chosen[..., None],
                   axis=1)


def moe(x, w, weights: Weights, l, dims: Dims):
    """The MoE FFN of layer ``l`` for tokens x (T, d): every routed expert
    for every token, a block of experts at a time, masked by the gate."""
    gate = route(x, w["ffn/router"], w["ffn/score_bias"], dims)
    size = math.gcd(dims.experts, EXPERT_BLOCK)

    @jax.checkpoint
    def block(acc, i):
        first = i * size
        wg, wu, wd = (_f32(weights.experts(l, n, first, size))
                      for n in ("w_gate", "w_up", "w_down"))
        hid = jax.nn.silu(jnp.einsum("td,edf->etf", x, wg,
                                     precision=HIGHEST)) * jnp.einsum(
            "td,edf->etf", x, wu, precision=HIGHEST)
        y = jnp.einsum("etf,efd->etd", hid, wd, precision=HIGHEST)
        g = jax.lax.dynamic_slice_in_dim(gate, first, size, axis=1)
        return acc + jnp.einsum("te,etd->td", g, y, precision=HIGHEST), None

    out, _ = jax.lax.scan(block, jnp.zeros_like(x),
                          jnp.arange(dims.experts // size))
    return out + _swiglu(x, w["ffn/shared/w_gate"], w["ffn/shared/w_up"],
                         w["ffn/shared/w_down"])


def block(x, l, weights: Weights, lora: dict, dims: Dims):
    """Layer ``l`` of the stack on hidden states x (B, S, d): attention,
    then the dense FFN (layer 0, a Python int) or the MoE FFN (any other
    layer, which may be traced)."""
    b, s, d = x.shape
    w = weights.layer(l)
    x = x + attention(_rmsnorm(x, w["norm1/scale"], dims.eps), w, lora, l,
                      dims)
    h = _rmsnorm(x, w["norm2/scale"], dims.eps)
    if isinstance(l, int) and l == 0:
        f = _swiglu(h, w["ffn/w_gate"], w["ffn/w_up"], w["ffn/w_down"])
    else:
        f = moe(h.reshape(b * s, d), w, weights, l, dims).reshape(b, s, d)
    return x + f


def hidden(weights: Weights, lora: dict, tokens, dims: Dims):
    """The final-normed hidden states (B, S, d) of token ids (B, S).
    ``lora`` maps a layer weight's name to its (A, B) factors stacked over
    the layers. Layer 0, then a loop over the MoE layers; each layer is
    rematerialized for the backward pass."""
    top = weights.top()
    x = _f32(top["embed/table"][tokens])
    x = jax.checkpoint(block, static_argnums=(1, 2, 4))(x, 0, weights, lora,
                                                         dims)

    @jax.checkpoint
    def moe_layer(x, l):
        return block(x, l, weights, lora, dims), None

    x, _ = jax.lax.scan(moe_layer, x, jnp.arange(1, dims.layers))
    return _rmsnorm(x, top["final_norm/scale"], dims.eps)


def logits(weights: Weights, lora: dict, tokens, dims: Dims):
    """The logits (B, S, vocab) of every position of token ids (B, S)."""
    with jax.default_matmul_precision("highest"):
        return _mm(hidden(weights, lora, tokens, dims),
                   _f32(weights.top()["lm_head"]))


def loss(weights: Weights, lora: dict, seqs, dims: Dims):
    """Mean next-token cross-entropy of sequences (B, S + 1), the logits
    :data:`TOKEN_CHUNK` tokens at a time."""
    with jax.default_matmul_precision("highest"):
        return _loss(weights, lora, seqs, dims)


def _loss(weights: Weights, lora: dict, seqs, dims: Dims):
    h = hidden(weights, lora, seqs[:, :-1], dims)
    targets = seqs[:, 1:].reshape(-1)
    h = h.reshape(-1, h.shape[-1])
    n = h.shape[0]
    c = min(TOKEN_CHUNK, n)
    while n % c:
        c -= 1
    head = weights.top()["lm_head"]

    @jax.checkpoint
    def chunk(total, xs):
        h_c, t_c = xs
        logits = _mm(h_c, _f32(head))
        nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, t_c[:, None], axis=-1)[:, 0]
        return total + jnp.sum(nll), None

    total, _ = jax.lax.scan(chunk, jnp.zeros((), jnp.float32),
                            (h.reshape(n // c, c, -1),
                             targets.reshape(n // c, c)))
    return total / n
