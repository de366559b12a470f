"""Unified decoder-only model covering all ten assigned architectures.

Layers are organized into config-declared segments (see
:mod:`repro.models.config`); a segment with ``repeat > 1`` is executed as one
``lax.scan`` over stacked per-super-block parameters, so the lowered HLO is
O(one super-block) regardless of depth — this is what keeps 62-layer configs
compilable and what bounds the remat carry stack.

Three entry points per model:
  * ``loss_and_metrics`` — training forward + chunked LM loss (+ MoE aux),
  * ``prefill``          — prompt forward that builds the decode caches,
  * ``decode_step``      — one token against the caches (``serve_step``).

Modality handling (the allowed frontend stubs):
  * VLM (qwen2-vl): the first ``n_vision_tokens`` positions take precomputed
    patch embeddings from the batch (vision tower is stubbed); positions are
    M-RoPE (3, B, S) ids.
  * Audio (musicgen): tokens are (B, K, S) EnCodec codebook streams; the
    embedding sums per-codebook tables and the loss averages K codebook
    heads (delay-pattern bookkeeping lives in the data pipeline).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import blocks as blk
from repro.models import losses, nn
from repro.models.config import ArchConfig, Segment
from repro.sharding.api import constrain
from repro.utils.pytree import PyTree


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------


def _segment_init(rng, cfg: ArchConfig, seg: Segment, dtype, first: int):
    """``first``: the model's index of the segment's first layer, which
    decides its FFN kind (leading dense layers fill whole segments)."""
    ffn = cfg.ffn_of(first)
    out = []
    for j, kind in enumerate(seg.pattern):
        kj = jax.random.fold_in(rng, j)
        if seg.repeat > 1:
            keys = jax.random.split(kj, seg.repeat)
            pj = jax.vmap(lambda k, kind=kind: blk.block_init(
                k, cfg, kind, dtype, ffn))(keys)
        else:
            pj = blk.block_init(kj, cfg, kind, dtype, ffn)
        out.append(pj)
    return out


def model_init(rng, cfg: ArchConfig) -> PyTree:
    dtype = jnp.dtype(cfg.param_dtype)
    k_emb, k_seg, k_head = jax.random.split(rng, 3)
    params: dict = {}
    if cfg.n_codebooks:
        params["embed"] = {"table": nn.normal_init(
            k_emb, (cfg.n_codebooks, cfg.vocab, cfg.d_model), std=0.02,
            dtype=dtype)}
    else:
        params["embed"] = nn.embedding_init(k_emb, cfg.vocab, cfg.d_model,
                                            dtype=dtype)
    firsts = [sum(s.n_layers for s in cfg.segments[:i])
              for i in range(len(cfg.segments))]
    params["segments"] = [
        _segment_init(jax.random.fold_in(k_seg, i), cfg, seg, dtype,
                      firsts[i])
        for i, seg in enumerate(cfg.segments)]
    params["final_norm"] = nn.rmsnorm_init(cfg.d_model, dtype)
    if not cfg.tie_embeddings:
        if cfg.n_codebooks:
            params["lm_head"] = nn.normal_init(
                k_head, (cfg.n_codebooks, cfg.d_model, cfg.vocab),
                std=cfg.d_model ** -0.5, dtype=dtype)
        else:
            params["lm_head"] = nn.normal_init(
                k_head, (cfg.d_model, cfg.vocab), std=cfg.d_model ** -0.5,
                dtype=dtype)
    return params


def lm_heads(params, cfg: ArchConfig):
    """Return (D, V) head or (K, D, V) stacked codebook heads."""
    if cfg.tie_embeddings:
        t = params["embed"]["table"]
        if cfg.n_codebooks:
            return jnp.swapaxes(t, 1, 2)  # (K, D, V)
        return t.T
    return params["lm_head"]


# ---------------------------------------------------------------------------
# embedding / batch handling
# ---------------------------------------------------------------------------


def embed_batch(params, cfg: ArchConfig, batch: dict):
    """Returns (embeds (B,S,D), positions (S,), pos3 or None,
    targets, loss_mask)."""
    if cfg.n_codebooks:
        tokens = batch["tokens"]                       # (B, K, S)
        b, k, s = tokens.shape
        tabs = params["embed"]["table"]                # (K, V, D)
        embeds = jnp.zeros((b, s, cfg.d_model), tabs.dtype)
        for j in range(k):
            embeds = embeds + jnp.take(tabs[j], tokens[:, j], axis=0)
        targets = jnp.roll(tokens, -1, axis=-1)        # (B,K,S)
        mask = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)
        positions = jnp.arange(s, dtype=jnp.int32)
        return embeds, positions, None, targets, mask
    tokens = batch["tokens"]                           # (B, S)
    b, s = tokens.shape
    embeds = nn.embedding_apply(params["embed"], tokens)
    pos3 = None
    if cfg.n_vision_tokens:
        nv = cfg.n_vision_tokens
        ve = batch["vision_embeds"].astype(embeds.dtype)  # (B, nv, D)
        embeds = jnp.concatenate([ve, embeds[:, nv:]], axis=1)
        pos3 = batch["pos3"]                           # (3, B, S)
        mask = jnp.concatenate(
            [jnp.zeros((b, nv)), jnp.ones((b, s - nv))], axis=1
        ).astype(jnp.float32).at[:, -1].set(0.0)
    elif cfg.mrope_sections:
        pos3 = batch.get("pos3")
        if pos3 is None:
            pos3 = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (3, b, s))
        mask = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)
    else:
        mask = jnp.ones((b, s), jnp.float32).at[:, -1].set(0.0)
    targets = jnp.roll(tokens, -1, axis=-1)
    positions = jnp.arange(s, dtype=jnp.int32)
    return embeds, positions, pos3, targets, mask


# ---------------------------------------------------------------------------
# segment execution
# ---------------------------------------------------------------------------


def _pos3_slice(pos3):
    return pos3  # positions are shared across layers; placeholder for clarity


def run_segments(params, cfg: ArchConfig, x, positions, pos3, *,
                 mode: str, caches=None, capacity: int = 0,
                 force_window: int = 0):
    """Run all segments. mode: 'train' | 'prefill' | 'decode'.

    Returns (x, new_caches, aux). ``caches`` is required for decode; prefill
    creates caches; train returns None.
    """
    aux_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for si, seg in enumerate(cfg.segments):
        seg_params = params["segments"][si]
        seg_caches = caches[si] if caches is not None else None

        if seg.repeat == 1:
            ncs = []
            for j, kind in enumerate(seg.pattern):
                c = seg_caches[j] if seg_caches is not None else None
                if mode == "prefill":
                    x, nc, a = blk.block_prefill(
                        seg_params[j], cfg, kind, x, positions=positions,
                        pos3=pos3, capacity=capacity,
                        force_window=force_window)
                else:
                    x, nc, a = blk.block_apply(
                        seg_params[j], cfg, kind, x, positions=positions,
                        pos3=pos3, cache=c, force_window=force_window)
                ncs.append(nc)
                aux_total = aux_total + a
            new_caches.append(ncs if mode != "train" else None)
            continue

        # ---- scanned segment -----------------------------------------
        if mode == "train":
            def body(carry, xs):
                h, aux = carry
                blk_params = xs
                for j, kind in enumerate(seg.pattern):
                    h, _, a = blk.block_apply(
                        blk_params[j], cfg, kind, h, positions=positions,
                        pos3=pos3, cache=None, force_window=force_window)
                    aux = aux + a
                return (h, aux), None

            if cfg.remat:
                body = jax.checkpoint(body)
            (x, aux_total), _ = jax.lax.scan(
                body, (x, aux_total), seg_params)
            new_caches.append(None)
        elif mode == "prefill":
            def body(carry, xs):
                h, aux = carry
                blk_params = xs
                ncs = []
                for j, kind in enumerate(seg.pattern):
                    h, nc, a = blk.block_prefill(
                        blk_params[j], cfg, kind, h, positions=positions,
                        pos3=pos3, capacity=capacity,
                        force_window=force_window)
                    ncs.append(nc)
                    aux = aux + a
                return (h, aux), tuple(ncs)

            (x, aux_total), seg_new = jax.lax.scan(
                body, (x, aux_total), seg_params)
            new_caches.append(list(seg_new))
        else:  # decode
            def body(carry, xs):
                h, aux = carry
                blk_params, blk_caches = xs
                ncs = []
                for j, kind in enumerate(seg.pattern):
                    h, nc, a = blk.block_apply(
                        blk_params[j], cfg, kind, h, positions=positions,
                        pos3=pos3, cache=blk_caches[j],
                        force_window=force_window)
                    ncs.append(nc)
                    aux = aux + a
                return (h, aux), tuple(ncs)

            (x, aux_total), seg_new = jax.lax.scan(
                body, (x, aux_total), (seg_params, tuple(seg_caches)))
            new_caches.append(list(seg_new))
    if mode == "train":
        new_caches = None
    return x, new_caches, aux_total


# ---------------------------------------------------------------------------
# top-level entry points
# ---------------------------------------------------------------------------


def loss_and_metrics(params, cfg: ArchConfig, batch: dict):
    embeds, positions, pos3, targets, mask = embed_batch(params, cfg, batch)
    # bf16 residual stream (master weights stay f32): halves activation
    # collectives and remat traffic (§Perf iteration 2)
    x = constrain(embeds.astype(jnp.dtype(cfg.compute_dtype)),
                  ("batch", "seq", None))
    x, _, aux = run_segments(params, cfg, x, positions, pos3, mode="train")
    x = nn.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    x = constrain(x, ("batch", "seq", None))
    heads = lm_heads(params, cfg)
    if cfg.n_codebooks:
        loss, acc = losses.multihead_codebook_xent(
            x, targets, mask, heads, chunk=cfg.loss_chunk)
    else:
        loss, acc = losses.chunked_causal_xent(
            x, targets, mask, heads, chunk=cfg.loss_chunk)
    total = loss
    if cfg.moe is not None:
        total = total + cfg.moe.aux_loss_coef * aux / max(1, cfg.n_layers)
    metrics = {"loss": loss, "acc": acc, "aux": aux}
    return total, metrics


def prefill(params, cfg: ArchConfig, batch: dict, *, capacity: int,
            force_window: int = 0):
    """Prompt forward; returns (caches, logits of the last position)."""
    embeds, positions, pos3, _, _ = embed_batch(params, cfg, batch)
    x = constrain(embeds.astype(jnp.dtype(cfg.compute_dtype)),
                  ("batch", "seq", None))
    x, caches, _ = run_segments(params, cfg, x, positions, pos3,
                                mode="prefill", capacity=capacity,
                                force_window=force_window)
    x = nn.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    heads = lm_heads(params, cfg)
    last = x[:, -1:].astype(jnp.float32)
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,kdv->bskv", last,
                            heads.astype(jnp.float32))
    else:
        logits = last @ heads.astype(jnp.float32)
    return caches, logits


def decode_step(params, cfg: ArchConfig, tokens, t, caches, *,
                force_window: int = 0, pos3=None):
    """One serving step: embed token(s) at position ``t``, attend to caches.

    tokens: (B, 1) int32 — or (B, K, 1) for codebook archs. t: () int32.
    Returns (logits, new_caches).
    """
    positions = t[None].astype(jnp.int32)
    if cfg.n_codebooks:
        b = tokens.shape[0]
        tabs = params["embed"]["table"]
        embeds = jnp.zeros((b, 1, cfg.d_model), tabs.dtype)
        for j in range(cfg.n_codebooks):
            embeds = embeds + jnp.take(tabs[j], tokens[:, j], axis=0)
    else:
        embeds = nn.embedding_apply(params["embed"], tokens)
        b = tokens.shape[0]
    if cfg.mrope_sections and pos3 is None:
        pos3 = jnp.broadcast_to(t, (3, b, 1)).astype(jnp.int32)
    x = constrain(embeds.astype(jnp.dtype(cfg.compute_dtype)),
                  ("batch", None, None))
    x, new_caches, _ = run_segments(params, cfg, x, positions, pos3,
                                    mode="decode", caches=caches,
                                    force_window=force_window)
    x = nn.rmsnorm_apply(params["final_norm"], x, cfg.norm_eps)
    heads = lm_heads(params, cfg)
    xf = x.astype(jnp.float32)
    if cfg.n_codebooks:
        logits = jnp.einsum("bsd,kdv->bskv", xf, heads.astype(jnp.float32))
    else:
        logits = xf @ heads.astype(jnp.float32)
    return logits, new_caches


def init_caches(cfg: ArchConfig, batch: int, capacity: int,
                force_window: int = 0):
    """Zero caches matching run_segments' decode structure."""
    out = []
    for seg in cfg.segments:
        seg_caches = []
        for kind in seg.pattern:
            c = blk.init_block_cache(cfg, kind, batch, capacity,
                                     force_window=force_window)
            if seg.repeat > 1:
                c = jax.tree.map(
                    lambda x: jnp.broadcast_to(
                        x[None], (seg.repeat,) + x.shape), c)
            seg_caches.append(c)
        out.append(seg_caches)
    return out
