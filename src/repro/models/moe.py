"""Mixture-of-Experts FFN: GShard's grouped one-hot dispatch with capacity,
or a dropless sort + grouped-matmul path.

Routing follows the MoE cards: softmax top-k for OLMoE (64e/top-8) and
Mixtral (8e/top-2); DeepSeek-V3's ``noaux_tc`` for Moonlight (64e/top-6 + 2
shared experts): sigmoid scores, the top-k chosen on score + a per-expert
correction bias that only selects, the chosen scores normalized and scaled
by ``routed_scale`` (:func:`route`).

**Capacity path** (softmax models). Tokens are processed in groups of
``group_size``; each expert has per-group capacity
``C = ceil(group_size · top_k · capacity_factor / E)`` and the choices past
it are dropped. Dispatch/combine are dense one-hot einsums (MXU-friendly; no
scatter), so total dispatch memory is ``tokens · group_size · top_k · cf`` —
linear in group size, chosen small.

**Dropless path** (``MoEConfig.dropless``). Every (token, choice) pair is
computed: the pairs are sorted by expert, each expert's rows run through its
SwiGLU as three grouped matmuls (:func:`repro.kernels.ops.grouped_matmul`,
the ``fed.moe_gmm`` scope), and the rows go back to token order, weighted by
their routing weights. Its work is tokens × top_k rows a layer, whatever the
routing. Under a frozen base the backward pass computes only activation
gradients through the experts. Dispatch and combine are row permutations
(:func:`permute_rows`) whose backward is the gather by the inverse
permutation: autodiff would transpose each gather into a scatter-add over
the tokens × top_k rows, which on a TPU takes about ten times as long as a
gather of the same rows.

Two sharding regimes (the §Perf comparison):
* **ETP** (default): expert weights sharded on d_ff over the ``model`` axis;
  every device holds a slice of all experts; no all-to-all.
* **EP** (``expert_parallel=True``): experts sharded over ``model``; dispatch
  requires an all-to-all of (groups, E, C, D) blocks, expressed here via
  sharding constraints that force XLA to insert the collective.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.models import nn
from repro.models.config import ArchConfig, MoEConfig
from repro.sharding.api import constrain
from repro.utils.trace import MOE, MOE_GMM, scope


def moe_init(rng, cfg: ArchConfig, dtype):
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.n_experts
    ks = jax.random.split(rng, 5)
    p = {
        "router": nn.normal_init(ks[0], (d, e), std=d ** -0.5, dtype=jnp.float32),
        "w_gate": nn.normal_init(ks[1], (e, d, f), std=d ** -0.5, dtype=dtype),
        "w_up": nn.normal_init(ks[2], (e, d, f), std=d ** -0.5, dtype=dtype),
        "w_down": nn.normal_init(ks[3], (e, f, d), std=f ** -0.5, dtype=dtype),
    }
    if m.n_shared_experts:
        fs = f * m.n_shared_experts
        sk = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": nn.normal_init(sk[0], (d, fs), std=d ** -0.5, dtype=dtype),
            "w_up": nn.normal_init(sk[1], (d, fs), std=d ** -0.5, dtype=dtype),
            "w_down": nn.normal_init(sk[2], (fs, d), std=fs ** -0.5, dtype=dtype),
        }
    if m.scoring == "sigmoid":
        p["score_bias"] = jnp.zeros((e,), jnp.float32)
    return p


def expert_capacity(group_size: int, n_experts: int, top_k: int,
                    capacity_factor: float) -> int:
    c = math.ceil(group_size * top_k * capacity_factor / n_experts)
    return max(4, int(c))


def router_topk(logits: jax.Array, top_k: int):
    """Top-k routing with renormalized probabilities.

    logits: (G, S, E) float32. Returns (weights, sel) where sel: (G,S,k)
    expert ids and weights: (G,S,k) normalized gate values.
    """
    probs = jax.nn.softmax(logits, axis=-1)
    weights, sel = jax.lax.top_k(probs, top_k)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    return weights, sel


def route(logits: jax.Array, p, m: MoEConfig):
    """Each token's experts and weights: ``(weights, sel, probs)``, with
    ``probs`` the per-token distribution the load statistic reads.

    ``softmax``: :func:`router_topk`. ``sigmoid`` (DeepSeek-V3
    ``noaux_tc``, one group): scores ``sigmoid(logits)``; the top-k of
    ``scores + p["score_bias"]`` are chosen; their weights are the scores
    without the bias, normalized, times ``routed_scale``."""
    if m.scoring == "softmax":
        weights, sel = router_topk(logits, m.top_k)
        return weights, sel, jax.nn.softmax(logits, axis=-1)
    scores = jax.nn.sigmoid(logits)
    _, sel = jax.lax.top_k(scores + p["score_bias"].astype(jnp.float32),
                           m.top_k)
    weights = jnp.take_along_axis(scores, sel, axis=-1)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-20)
    probs = scores / jnp.sum(scores, axis=-1, keepdims=True)
    return weights * m.routed_scale, sel, probs


def load_balance_loss(logits: jax.Array, sel: jax.Array, n_experts: int):
    """Switch/GShard aux loss: E · Σ_e f_e · P_e."""
    probs = jax.nn.softmax(logits, axis=-1)          # (G,S,E)
    pe = jnp.mean(probs, axis=(0, 1))                # (E,)
    onehot = jax.nn.one_hot(sel, n_experts)          # (G,S,k,E)
    fe = jnp.mean(jnp.sum(onehot, axis=2), axis=(0, 1))
    return n_experts * jnp.sum(fe * pe)


def moe_apply(p, cfg: ArchConfig, x):
    """x: (B, S, D) -> (out, aux_loss), in the ``fed.moe`` device scope.
    aux_loss is the Switch statistic E · Σ_e f_e · P_e of the routing."""
    with scope(MOE):
        if cfg.moe.dropless:
            return _dropless_apply(p, cfg, x)
        return _capacity_apply(p, cfg, x)


def _shared_experts(p, cfg: ArchConfig, x):
    cdt = jnp.dtype(cfg.compute_dtype)
    sp = p["shared"]
    xs = x.astype(cdt)
    return nn.swiglu(xs @ sp["w_gate"].astype(cdt),
                     xs @ sp["w_up"].astype(cdt)) @ sp["w_down"].astype(cdt)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def permute_rows(x, perm, inv, k=1):
    """Row ``perm[i] // k`` of ``x`` as row i: each of x's n rows repeated
    ``k`` times, then permuted by ``perm`` (of n·k rows), whose inverse is
    ``inv``. The backward is the gather ``g[inv]``, summed over each row's
    ``k`` copies in float32, where autodiff of a gather would scatter-add
    the rows; the index arrays get no cotangent."""
    if k == 1:
        return x.at[perm].get(unique_indices=True)
    return x[perm // k]


def _permute_rows_fwd(x, perm, inv, k):
    return permute_rows(x, perm, inv, k), inv


def _permute_rows_bwd(k, inv, g):
    g = g.at[inv].get(unique_indices=True)
    if k > 1:
        g = jnp.sum(g.reshape(-1, k, g.shape[-1]), axis=1,
                    dtype=jnp.float32).astype(g.dtype)
    return g, None, None


permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _dropless_apply(p, cfg: ArchConfig, x):
    """Every (token, choice) pair through its expert: sort by expert,
    grouped matmuls, back to token order. The router runs in float32 at
    the highest matmul precision, so that the choice of experts does not
    rest on bfloat16 rounding of the logits. The rows go to expert order
    and back through :func:`permute_rows`, so the backward pass gathers
    them by the inverse permutation instead of scatter-adding them (a
    chip's scatter-add is about ten times slower than a gather of the
    same rows)."""
    from repro.kernels.ops import grouped_matmul
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    cdt = jnp.dtype(cfg.compute_dtype)
    xt = x.reshape(b * s, d)
    logits = jnp.matmul(xt.astype(jnp.float32),
                        p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)   # (T, E)
    weights, sel, probs = route(logits, p, m)
    f_e = jnp.mean(jnp.sum(jax.nn.one_hot(sel, e), axis=1), axis=0)
    aux = e * jnp.sum(f_e * jnp.mean(probs, axis=0))

    # the T·k (token, choice) rows sorted by expert; row t·k + j is
    # token t's j-th choice, ``order`` is a permutation of them and
    # ``back`` its inverse
    flat = sel.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    back = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=order.dtype), unique_indices=True)
    sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
    rows = permute_rows(xt.astype(cdt), order, back, k)
    with scope(MOE_GMM):
        gate = grouped_matmul(rows, p["w_gate"].astype(cdt), sizes)
        up = grouped_matmul(rows, p["w_up"].astype(cdt), sizes)
    hidden = nn.swiglu(gate, up)
    with scope(MOE_GMM):
        down = grouped_matmul(hidden, p["w_down"].astype(cdt), sizes)
    down = permute_rows(down, back, order).reshape(b * s, k, d)
    out = jnp.einsum("tk,tkd->td", weights.astype(cdt), down,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, s, d)
    if m.n_shared_experts:
        out = out + _shared_experts(p, cfg, x)
    return out.astype(x.dtype), aux


def _capacity_apply(p, cfg: ArchConfig, x):
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    cdt = jnp.dtype(cfg.compute_dtype)
    tokens = b * s
    g = min(m.group_size, tokens)
    while tokens % g:
        g -= 1
    n_groups = tokens // g
    cap = expert_capacity(g, e, k, m.capacity_factor)

    xt = x.reshape(n_groups, g, d)
    # the (B,S,D)->(G,g,D) reshape fuses the batch and seq shardings; GSPMD
    # gives up and replicates unless we re-constrain the group axis
    xt = constrain(xt, ("moe_groups", None, None))
    logits = (xt.astype(jnp.float32)
              @ p["router"].astype(jnp.float32))     # (G,g,E)
    weights, sel = router_topk(logits, k)
    aux = load_balance_loss(logits, sel, e)

    # position of each (token, choice) within its expert's capacity buffer;
    # cumulative count over the flattened (token, choice) order implements
    # first-come-first-served capacity assignment (GShard).
    onehot = jax.nn.one_hot(sel, e, dtype=jnp.int32)          # (G,g,k,E)
    flat = onehot.reshape(n_groups, g * k, e)
    pos_in_expert = jnp.cumsum(flat, axis=1) - flat           # (G,g*k,E)
    pos_in_expert = pos_in_expert.reshape(n_groups, g, k, e)
    within_cap = pos_in_expert < cap
    # accumulate dispatch/combine per routing choice to keep the largest
    # intermediate at (G, g, E, C) rather than (G, g, k, E, C)
    disp_f = jnp.zeros((n_groups, g, e, cap), cdt)
    comb = jnp.zeros((n_groups, g, e, cap), cdt)
    for j in range(k):
        oh_j = onehot[:, :, j].astype(cdt)                   # (G,g,E)
        slot_j = (oh_j[..., None]
                  * within_cap[:, :, j, :, None].astype(cdt)
                  * jax.nn.one_hot(pos_in_expert[:, :, j], cap, dtype=cdt))
        disp_f = disp_f + slot_j
        comb = comb + weights[:, :, j, None, None].astype(cdt) * slot_j
    disp_f = constrain(disp_f, ("moe_groups", None, "experts", None))
    comb = constrain(comb, ("moe_groups", None, "experts", None))

    # expert inputs: (G, E, C, D)
    ein = jnp.einsum("gtec,gtd->gecd", disp_f, xt.astype(cdt))
    ein = constrain(ein, ("moe_groups", "experts", None, None))
    wg = p["w_gate"].astype(cdt)
    wu = p["w_up"].astype(cdt)
    wd = p["w_down"].astype(cdt)
    hidden = nn.swiglu(jnp.einsum("gecd,edf->gecf", ein, wg),
                       jnp.einsum("gecd,edf->gecf", ein, wu))
    hidden = constrain(hidden, ("moe_groups", "experts", None, "ffn"))
    eout = jnp.einsum("gecf,efd->gecd", hidden, wd)
    eout = constrain(eout, ("moe_groups", "experts", None, None))
    out = jnp.einsum("gtec,gecd->gtd", comb, eout)
    out = constrain(out, ("moe_groups", None, None))
    out = out.reshape(b, s, d)

    if m.n_shared_experts:
        out = out + _shared_experts(p, cfg, x)
    return out.astype(x.dtype), aux
