"""Zoo models behind the federated ``Classifier`` interface.

The federated engine consumes ``Classifier(name, init, apply)`` and vmaps
``apply`` over a client axis; the zoo (decoder / MoE / xLSTM stacks in
``models/decoder.py``) speaks token batches. This adapter bridges the two:
float feature vectors are discretized into a token sequence (one token per
feature, sigmoid-binned into the vocab), run through ``run_segments`` in
train mode, and the last position's logits — tied-embedding head restricted
to the first ``n_classes`` vocab columns — are the classification output.

``sharding.api.constrain`` is the identity without an installed context, so
the same apply runs unsharded inside the federated vmap on CPU tests and
sharded under a launcher-installed mesh.

These are NOT meant to be federated densely: wrap them with
:func:`repro.models.lora.lora_classifier` (spec v7 requires ``lora_rank>=1``
for zoo models) so clients train/ship only the adapter subtree.

:func:`make_lm` is the token-level path for a registry architecture at any
size: federated LoRA fine-tuning of a frozen decoder on token sequences,
with a next-token loss and next-token accuracy (``Classifier.loss`` and
``Classifier.hits``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import lora, losses, nn
from repro.models.config import ArchConfig, MoEConfig, Segment
from repro.models.decoder import lm_heads, model_init, run_segments
from repro.models.simple import Classifier
from repro.utils.trace import LM_HEAD, scope

ZOO_KINDS = ("decoder", "moe", "xlstm")

#: make_lm's default LoRA targets: every attention projection, latent
#: attention's included
LM_TARGETS = ("wq", "wk", "wv", "wo", "wq_a", "wq_b", "wkv_a", "wk_b",
              "wv_b")
#: tokens whose head logits are computed at once, and sequences an
#: evaluation forward pass takes: at 163,840 logits a token, 512 tokens'
#: float32 logits are 336 MB
LM_TOKEN_CHUNK, LM_EVAL_SEQS = 512, 2


def zoo_arch_config(kind: str, *, width: int = 4, n_layers: int = 2,
                    vocab: int = 64) -> ArchConfig:
    """Tiny-but-real ArchConfig per zoo kind; ``d_model = 8 * width`` so the
    spec's existing ``width`` knob scales the stack (width 4 → d_model 32
    smoke configs, width 32 → d_model 256, ≈1.4M params)."""
    d = 8 * width
    common = dict(n_heads=2, n_kv_heads=2, head_dim=d // 2, vocab=vocab,
                  compute_dtype="float32", remat=False)
    if kind == "decoder":
        return ArchConfig(
            name=f"fed-decoder-{d}", arch_type="dense", d_model=d, d_ff=2 * d,
            segments=(Segment(n_layers, ("attn",)),), **common)
    if kind == "moe":
        # group_size >= any batch*seq we see -> a single dispatch group, so
        # token counts never need to divide the group size
        return ArchConfig(
            name=f"fed-moe-{d}", arch_type="moe", d_model=d, d_ff=2 * d,
            segments=(Segment(n_layers, ("attn",)),), ffn_kind="moe",
            moe=MoEConfig(n_experts=4, top_k=2, d_ff_expert=d,
                          capacity_factor=2.0, group_size=65536),
            **common)
    if kind == "xlstm":
        return ArchConfig(
            name=f"fed-xlstm-{d}", arch_type="ssm", d_model=d, d_ff=2 * d,
            segments=(Segment(n_layers, ("mlstm",)),), ffn_kind="none",
            **common)
    raise ValueError(f"unknown zoo kind {kind!r}; expected one of {ZOO_KINDS}")


def make_zoo_classifier(kind: str, *, input_shape, n_classes: int,
                        width: int = 4, n_layers: int = 2,
                        vocab: int = 64) -> Classifier:
    vocab = max(vocab, n_classes)
    cfg = zoo_arch_config(kind, width=width, n_layers=n_layers, vocab=vocab)

    def tokens_of(x):
        f = x.reshape(x.shape[0], -1).astype(jnp.float32)
        bins = jnp.floor(_sigmoid(f) * cfg.vocab)
        return jnp.clip(bins, 0, cfg.vocab - 1).astype(jnp.int32)

    def init(rng):
        return model_init(rng, cfg)

    def apply(p, x):
        toks = tokens_of(x)
        h = nn.embedding_apply(p["embed"], toks)
        positions = jnp.arange(toks.shape[1], dtype=jnp.int32)
        h, _, _ = run_segments(p, cfg, h.astype(jnp.float32), positions,
                               None, mode="train")
        h = nn.rmsnorm_apply(p["final_norm"], h)
        heads = lm_heads(p, cfg).astype(jnp.float32)
        logits = h[:, -1].astype(jnp.float32) @ heads
        return logits[:, :n_classes]

    return Classifier(f"zoo-{kind}", init, apply)


def _sigmoid(x):
    return 1.0 / (1.0 + jnp.exp(-x))


def make_lm(cfg: ArchConfig, base, *, rank: int = 16, alpha: float = 32.0,
            targets: tuple = LM_TARGETS) -> Classifier:
    """The federated :class:`Classifier` of the decoder ``cfg`` fine-tuned
    by LoRA over the frozen weights ``base`` (the layout of
    :func:`repro.models.decoder.model_init`, any dtype).

    An example's label is a sequence of S+1 token ids (int32), whose last
    S tokens are its targets, each predicted from the tokens before it;
    its features are not read. The trainable tree is ``{"lora": {path:
    {"lora_a", "lora_b"}}}`` over the ``targets`` projections, rank
    ``rank``, scale ``alpha / rank``, applied unmerged
    (:func:`repro.models.lora.attach`); ``base`` is the model's ``base``,
    handed to every program as an argument. ``loss`` is the mean
    next-token cross-entropy over the batch's targets, the head's logits
    :data:`LM_TOKEN_CHUNK` tokens at a time (``fed.lm_head``); the router
    is frozen with the rest of the base and no auxiliary loss is added.
    ``hits`` counts next-token top-1 hits, :data:`LM_EVAL_SEQS` sequences
    a forward pass. ``apply`` gives the logits of every position of a token
    batch (B, S), for tests at small sizes."""
    want = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    if (jax.tree.structure(want) != jax.tree.structure(base)
            or any(a.shape != b.shape for a, b in
                   zip(jax.tree.leaves(want), jax.tree.leaves(base)))):
        raise ValueError(f"base is not {cfg.name}'s weight tree")
    leaves = lora.target_leaves(base, targets)
    if not leaves:
        raise ValueError(f"no leaves named {targets} in {cfg.name}")
    for path in leaves:
        if "/ffn/" in f"/{path}" and cfg.ffn_kind == "moe":
            raise ValueError(f"{path}: make_lm adapts projections only, "
                             "not expert or FFN weights")
    leaves = {p: jax.ShapeDtypeStruct(l.shape, l.dtype)
              for p, l in leaves.items()}
    ranks = {p: lora.leaf_rank(l, rank) for p, l in leaves.items()}
    scales = {p: float(alpha) / r for p, r in ranks.items()}
    cdt = jnp.dtype(cfg.compute_dtype)

    def init(rng):
        return {"lora": lora.init_adapters(rng, leaves, ranks)}

    def hidden(params, tokens, frozen_base):
        w = lora.attach(frozen_base, params["lora"], scales)
        x = nn.embedding_apply(w["embed"], tokens).astype(cdt)
        positions = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        x, _, _ = run_segments(w, cfg, x, positions, None, mode="train")
        return (nn.rmsnorm_apply(w["final_norm"], x, cfg.norm_eps),
                lm_heads(w, cfg))

    def apply(params, tokens, frozen_base):
        h, head = hidden(params, tokens, frozen_base)
        return jnp.matmul(h, head.astype(h.dtype),
                          preferred_element_type=jnp.float32)

    def head_sums(params, seqs, frozen_base, hits: bool):
        h, head = hidden(params, seqs[:, :-1], frozen_base)
        with scope(LM_HEAD):
            return losses.token_sums(h.reshape(-1, h.shape[-1]),
                                     seqs[:, 1:].reshape(-1), head,
                                     chunk=LM_TOKEN_CHUNK, hits=hits)

    def loss(params, xb, yb, frozen_base):
        total = head_sums(params, yb, frozen_base, hits=False)
        return total / (yb.shape[0] * (yb.shape[1] - 1))

    def hits(params, x, y, frozen_base):
        n, k = y.shape[0], min(LM_EVAL_SEQS, y.shape[0])
        while n % k:
            k -= 1
        right = jax.lax.map(
            lambda seqs: head_sums(params, seqs, frozen_base, hits=True),
            y.reshape(n // k, k, y.shape[1]))
        return jnp.sum(right), n * (y.shape[1] - 1)

    return Classifier(f"lm[{cfg.name}]", init, apply, base=base, loss=loss,
                      hits=hits)
