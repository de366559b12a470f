"""LoRA adapters over the model zoo — the federated trainable subtree.

``lora_classifier`` wraps any :class:`~repro.models.simple.Classifier` so
that its *trainable* parameter tree contains only rank-r adapter factors
(plus, optionally, the small non-adapted leaves): the base weights are
materialized once from a fixed rng at wrap time and carried as the wrapped
model's ``base``, which every jitted program of the engine takes as an
argument (never a constant compiled into it). Because the federated engine
only ever sees ``model.init``/``model.apply``, every executor, the int8
:class:`~repro.core.history_store.HistoryStore` and the CC estimation replay
automatically operate on the O(r·d) adapter subtree instead of the O(P)
dense tree — no masking inside ``core/rounds.py``.

:func:`attach` applies the same adapters unmerged, as
:class:`repro.models.nn.Adapted` weights (``x @ W + s·(x @ A) @ B``), for a
decoder whose layers multiply by their weights
(:func:`repro.models.zoo.make_lm`).

Adapters live in a *flat* dict keyed by the '/'-joined path of the adapted
leaf in the base tree (list indices become string segments, matching
``tree_map_with_path``), so the trainable tree is plain nested dicts even
when the base tree holds lists of scanned segments::

    {"lora": {"segments/0/0/mixer/wq": {"lora_a": A, "lora_b": B}, ...},
     "base": {"final_norm/scale": s, ...}}          # freeze_base=False only

The effective weight is ``W + (alpha/r) * A @ B`` contracted over the last
two dims (``einsum("...ir,...ro->...io")``), so stacked leaves — scanned
layer repeats, MoE experts — adapt per leading index. ``B`` is
zero-initialized: the round-0 model is exactly the frozen base.

The leaf names ``lora_a``/``lora_b`` are registered in
``sharding/rules.py::_PARAM_AXES`` so ``params_pspecs`` places the rank dim
on the ``lora`` logical axis (→ ``model`` mesh axis) and, with
``client_axis=True``, the stacked per-client adapters shard over
``clients`` — the 2-D ``("clients", "model")`` federated mesh.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.simple import Classifier

# leaf names eligible for adaptation: every zoo attention/MLP projection,
# latent attention's (``wq_a``/``wq_b`` with q_lora, ``wq`` without;
# ``wkv_a``, ``wk_b``, ``wv_b``), plus the dense/conv kernels of the simple
# models
LORA_TARGETS = ("w", "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down",
                "wq_a", "wq_b", "wkv_a", "wk_b", "wv_b")


# ---------------------------------------------------------------------------
# path helpers (mirror tree_map_with_path's '/'-joined naming)
# ---------------------------------------------------------------------------


def _iter_leaves(tree, prefix=()):
    """Yield (path, leaf) depth-first with deterministic (sorted-key) order —
    the same order jax uses when flattening dicts."""
    if isinstance(tree, dict):
        for k in sorted(tree):
            yield from _iter_leaves(tree[k], prefix + (str(k),))
    elif isinstance(tree, (list, tuple)):
        for i, v in enumerate(tree):
            yield from _iter_leaves(v, prefix + (str(i),))
    else:
        yield "/".join(prefix), tree


def _get(tree, parts):
    node = tree
    for p in parts:
        node = node[int(p)] if isinstance(node, (list, tuple)) else node[p]
    return node


def _set(tree, parts, value):
    """Copy-on-write functional set along ``parts``."""
    head, rest = parts[0], parts[1:]
    if isinstance(tree, dict):
        new = dict(tree)
        new[head] = value if not rest else _set(tree[head], rest, value)
        return new
    i = int(head)
    seq = list(tree)
    seq[i] = value if not rest else _set(seq[i], rest, value)
    return tuple(seq) if isinstance(tree, tuple) else seq


# ---------------------------------------------------------------------------
# adapter construction
# ---------------------------------------------------------------------------


def target_leaves(base_params, targets) -> dict:
    """``{path: leaf}`` of the leaves named in ``targets`` (matrices or
    stacks of them), by '/'-joined path."""
    return {path: leaf for path, leaf in _iter_leaves(base_params)
            if path.split("/")[-1] in targets
            and getattr(leaf, "ndim", 0) >= 2}


def init_adapters(rng, leaves: dict, ranks: dict, *,
                  init_a: str = "normal", train_a: bool = True) -> dict:
    """``{path: {"lora_a", "lora_b"}}``, float32, one per adapted leaf:
    B zero (the first model is the base) and, where ``train_a``, A drawn
    by ``init_a`` (normal with std d_in^-1/2, or the identity)."""
    out = {}
    for i, (p, leaf) in enumerate(leaves.items()):
        out[p] = {"lora_b": jnp.zeros(leaf.shape[:-2]
                                      + (ranks[p], leaf.shape[-1]),
                                      jnp.float32)}
        if train_a:
            out[p]["lora_a"] = _init_a(jax.random.fold_in(rng, i), leaf,
                                       ranks[p], init_a)
    return out


def attach(base_params, adapters: dict, scales: dict):
    """``base_params`` with each adapted leaf replaced by an unmerged
    :class:`~repro.models.nn.Adapted` weight."""
    from repro.models.nn import Adapted
    out = base_params
    for path, ab in adapters.items():
        parts = path.split("/")
        out = _set(out, parts, Adapted(_get(base_params, parts),
                                       ab["lora_a"], ab["lora_b"],
                                       scales[path]))
    return out


def leaf_rank(leaf, rank) -> int:
    d_in = leaf.shape[-2]
    return d_in if rank == "full" else min(int(rank), d_in)


def _init_a(rng, leaf, r, kind):
    lead, d_in = leaf.shape[:-2], leaf.shape[-2]
    if kind == "identity":
        if r != d_in:
            raise ValueError("init_a='identity' needs rank == d_in "
                             f"(got r={r}, d_in={d_in})")
        return jnp.broadcast_to(jnp.eye(d_in, dtype=jnp.float32),
                                lead + (d_in, d_in))
    std = d_in ** -0.5
    return std * jax.random.normal(rng, lead + (d_in, r), dtype=jnp.float32)


def lora_classifier(base: Classifier, base_rng, rank, *,
                    alpha: float | None = None,
                    freeze_base: bool = True,
                    targets: tuple = LORA_TARGETS,
                    train_a: bool = True,
                    init_a: str = "normal") -> Classifier:
    """Wrap ``base`` so only LoRA factors (and, with ``freeze_base=False``,
    the non-adapted leaves) are trainable.

    rank: positive int, or ``"full"`` for per-leaf rank = d_in (with
        ``init_a="identity"``/``train_a=False``/``alpha=None`` this makes the
        wrapped model's SGD trajectory reproduce the dense path exactly).
    alpha: LoRA scale numerator; effective scale is ``alpha / r`` per leaf
        (``None`` → scale 1.0).
    train_a: with ``False`` the A factors are drawn once at wrap time and
        frozen; only B (and base leaves) remain trainable.
    """
    if rank != "full" and (not isinstance(rank, int) or rank < 1):
        raise ValueError(f"rank must be a positive int or 'full', got {rank!r}")
    if init_a not in ("normal", "identity"):
        raise ValueError(f"unknown init_a {init_a!r}")

    if base.base is not None:
        raise ValueError(f"{base.name!r} already carries a frozen base")
    base_params = base.init(base_rng)
    leaves = target_leaves(base_params, targets)
    paths = list(leaves)
    if not paths:
        raise ValueError(f"no adaptable leaves named {targets} in "
                         f"{base.name!r}")
    ranks = {p: leaf_rank(leaves[p], rank) for p in paths}
    scales = {p: (1.0 if alpha is None else float(alpha) / ranks[p])
              for p in paths}
    frozen_paths = frozenset(paths)

    frozen_a = None
    if not train_a:
        a_rng = jax.random.fold_in(base_rng, 1)
        frozen_a = {p: _init_a(jax.random.fold_in(a_rng, i), leaves[p],
                               ranks[p], init_a)
                    for i, p in enumerate(paths)}

    def init(rng):
        out = {"lora": init_adapters(rng, leaves, ranks, init_a=init_a,
                                     train_a=train_a)}
        if not freeze_base:
            out["base"] = {p: l for p, l in _iter_leaves(base_params)
                           if p not in frozen_paths}
        return out

    def apply(p, x, frozen_base):
        eff = frozen_base
        for path, leaf in p.get("base", {}).items():
            eff = _set(eff, path.split("/"), leaf)
        for path, ab in p["lora"].items():
            a = ab["lora_a"] if train_a else frozen_a[path]
            w = _get(eff, path.split("/"))
            delta = jnp.einsum("...ir,...ro->...io", a, ab["lora_b"])
            eff = _set(eff, path.split("/"),
                       w + (scales[path] * delta).astype(w.dtype))
        return base.apply(eff, x)

    return Classifier(f"lora[{base.name}]", init, apply, base=base_params)


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------


def lora_report(base_params, trainable_params) -> dict:
    """Dense-vs-adapter size accounting: ``p_trainable`` is what the
    federated engine trains and the HistoryStore remembers per client,
    ``p_dense`` is the frozen base the adapters ride on."""
    from repro.utils.pytree import tree_bytes, tree_count_params

    p_dense = tree_count_params(base_params)
    p_trainable = tree_count_params(trainable_params)
    return {"p_dense": p_dense,
            "p_trainable": p_trainable,
            "trainable_bytes": tree_bytes(trainable_params),
            "trainable_frac": p_trainable / max(p_dense, 1)}
