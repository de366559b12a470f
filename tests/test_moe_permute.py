"""The dropless expert layer moves its (token, choice) rows to expert order
and back through :func:`repro.models.moe.permute_rows`, whose backward is
the gather by the inverse permutation.

Its gradients are those of plain autodiff through the two gathers it
replaces (``jnp.repeat(xt, k)[order]`` to dispatch, ``down[back]`` to
combine): exactly in float32, and in bfloat16 where no row has copies to
sum; where it has (top_k > 1), the new backward sums each token's k
cotangent rows in float32 and rounds once, autodiff sums them in
bfloat16, so the two differ by at most a few bfloat16 steps of the sum.

The lowered gradient of a small dropless decoder holds no scatter of the
tokens × top_k rows, and every gather of them, forward and backward,
carries the expert layer's scope, which ``moe_ms_per_round`` reads.
"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import moe
from repro.models.decoder import model_init
from repro.models.zoo import make_lm

ARCH = "moonshot-v1-16b-a3b"


def _gather_rows(x, perm, inv, k=1):
    """The formulation :func:`moe.permute_rows` replaced: a gather of the
    repeated rows, which autodiff transposes into a scatter-add."""
    return jnp.repeat(x, k, axis=0).at[perm].get(unique_indices=True)


def _layer(dtype, top_k):
    cfg = get_config(ARCH, reduced=True)
    cfg = cfg.replace(compute_dtype=dtype, moe=dataclasses.replace(
        cfg.moe, n_experts=8, top_k=top_k))
    assert cfg.moe.dropless
    p = moe.moe_init(jax.random.PRNGKey(0), cfg, jnp.dtype(dtype))
    return cfg, p


def _choices(t, top_k, one_expert):
    """Each token's top_k distinct experts of 8, as the router picks them;
    with ``one_expert``, expert 2 is among every token's choices."""
    scores = jax.random.uniform(jax.random.PRNGKey(3), (t, 8))
    if one_expert:
        scores = scores.at[:, 2].add(100.0)
    return jax.lax.top_k(scores, top_k)[1]


def _assert_matches(got, want, dtype, top_k, exact):
    """Equal where ``exact``; otherwise within ``4 · top_k`` steps of the
    dtype at the scale of ``want``."""
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        assert np.isfinite(g).all(), path
        if exact:
            np.testing.assert_array_equal(g, w, err_msg=str(path))
        else:
            eps = float(jnp.finfo(dtype).eps)
            np.testing.assert_allclose(
                g, w, rtol=0, atol=4 * top_k * eps * np.abs(w).max(),
                err_msg=str(path))


CASES = pytest.mark.parametrize(
    "dtype,top_k,one_expert",
    [(dt, k, one) for dt in ("float32", "bfloat16") for k in (1, 6)
     for one in (False, True)],
    ids=lambda v: {True: "one_expert_takes_all",
                   False: "random_routing"}.get(v, str(v))
    if isinstance(v, bool) else str(v))


@CASES
def test_dispatch_and_combine_gradients_match_autodiff_of_the_gathers(
        dtype, top_k, one_expert):
    """The rows alone: dispatch, a different function of each row, and
    the weighted combine; the gradients of the tokens and of the routing
    weights are autodiff's of the gathers, exactly in float32, and in
    bfloat16 where a token has one row."""
    t, d = 40, 16
    kx, kw, ks = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(kx, (t, d), dtype)
    weights = jax.random.uniform(kw, (t, top_k), dtype)
    scale = jax.random.normal(ks, (t * top_k, d), dtype)
    flat = _choices(t, top_k, one_expert).reshape(-1)
    order = jnp.argsort(flat, stable=True)
    back = jnp.argsort(order)

    def loss(x, weights, gather):
        rows = gather(x, order, back, top_k)
        rows = jnp.tanh(rows * scale)
        down = gather(rows, back, order).reshape(t, top_k, d)
        out = jnp.einsum("tk,tkd->td", weights, down,
                         preferred_element_type=jnp.float32)
        return jnp.sum(jnp.sin(out))

    # op by op, so that both programs round alike wherever they compute
    # alike: a jitted program fuses the ops around the gathers, and the
    # scatter, differently
    grad = jax.grad(loss, argnums=(0, 1))
    _assert_matches(grad(x, weights, moe.permute_rows),
                    grad(x, weights, _gather_rows), dtype, top_k,
                    exact=dtype == "float32" or top_k == 1)


@CASES
def test_layer_gradients_match_autodiff_of_the_gathers(
        dtype, top_k, one_expert, monkeypatch):
    """The whole dropless layer, routing and shared experts included: the
    token gradient sums the router's, the shared experts' and the
    dispatch's cotangents in another order than autodiff of the gathers
    does, so the two agree to the dtype's rounding of those sums."""
    cfg, p = _layer(dtype, top_k)
    if one_expert:
        p = dict(p, score_bias=p["score_bias"].at[2].set(100.0))
    kx, kr = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (2, 24, cfg.d_model), dtype)
    r = jax.random.normal(kr, x.shape, jnp.float32)

    def loss(x, p):
        out, aux = moe.moe_apply(p, cfg, x)
        return jnp.sum(out.astype(jnp.float32) * r) + aux

    got = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, p)
    with monkeypatch.context() as mp:
        mp.setattr(moe, "permute_rows", _gather_rows)
        want = jax.jit(jax.grad(loss, argnums=(0, 1)))(x, p)
    _assert_matches(got, want, dtype, top_k, exact=False)


def test_dispatch_puts_each_row_where_the_permutation_says():
    """Each (token, choice) row of the dispatch lands where the
    permutation puts it, and the combine undoes it."""
    t, k, d = 12, 3, 5
    x = jax.random.normal(jax.random.PRNGKey(2), (t, d))
    sel = jax.random.randint(jax.random.PRNGKey(3), (t * k,), 0, 4)
    order = jnp.argsort(sel, stable=True)
    back = jnp.argsort(order)
    rows = moe.permute_rows(x, order, back, k)
    np.testing.assert_array_equal(rows, jnp.repeat(x, k, axis=0)[order])
    tokens = moe.permute_rows(rows, back, order).reshape(t, k, d)
    np.testing.assert_array_equal(tokens, jnp.broadcast_to(
        x[:, None], (t, k, d)))


def _hlo_ops(text: str, op: str):
    """(result shape, op_name) of each ``op`` instruction of HLO text."""
    pat = re.compile(r"= (\w+)\[([\d,]*)\]\{[^}]*\} " + op + r"\(.*?"
                     r"metadata=\{[^}]*op_name=\"([^\"]*)\"")
    return [(m.group(1), tuple(int(n) for n in m.group(2).split(",") if n),
             m.group(3)) for m in pat.finditer(text)]


def test_the_gradient_scatters_no_rows_and_keeps_the_scope():
    cfg = get_config(ARCH, reduced=True)
    assert cfg.moe.dropless
    base = model_init(jax.random.PRNGKey(0), cfg)
    model = make_lm(cfg, base, rank=2)
    params = model.init(jax.random.PRNGKey(1))
    b, s = 2, 24
    seqs = jax.random.randint(jax.random.PRNGKey(2), (b, s + 1), 0,
                              cfg.vocab, jnp.int32)
    lowered = jax.jit(jax.grad(
        lambda p: model.loss(p, None, seqs, model.base))).lower(params)
    text = lowered.compiler_ir("hlo").as_hlo_module().to_string()
    rows = (b * s * cfg.moe.top_k, cfg.d_model)
    assert not [o for o in _hlo_ops(text, "scatter") if o[1] == rows]
    gathers = [o for o in _hlo_ops(text, "gather") if o[1] == rows]
    # dispatch and combine, forward and backward
    assert len(gathers) >= 4
    assert any("transpose" in name for _, _, name in gathers)
    for _, _, name in gathers:
        assert "fed.moe" in name, name
