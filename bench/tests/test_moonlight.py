"""The ``moonlight`` family's program against its plain reference, on the
CPU at the family's CPU size (``shrink``: the published structure at small
widths, float32), on weights made from the configuration's seed.

The program is ``repro.models.zoo.make_lm`` over the registry's
``moonshot-v1-16b-a3b`` cut to 3 layers (the dense layer, then two MoE
layers scanned); the reference is ``bench/reference/moonlight.py``. Both
compute in float32 here, so they differ only in the order of their sums
(blockwise online softmax against a plain one, RoPE's pairs interleaved
against de-interleaved, LoRA unmerged against merged, expert rows sorted
and computed in a grouped matmul against every expert masked): a few
float32 ulps of each accumulation. The tolerances are 1e-4 (absolute and
relative, on logits and hidden states of order 1, and on gradients
relative to their largest entry), a hundred times that rounding, and far
under what one wrong rotation, weight, routing choice or dropped token
moves (0.05 to 1 at these sizes).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.cells import family, resolve
from bench.reference import moonlight as ref
from bench.tests.tiny import SEED, run_tiny, tiny

CELL = "moonlight.cc_power"
TOL = dict(rtol=1e-4, atol=1e-4)


@pytest.fixture(scope="module")
def setup():
    """The CPU-size configuration, its family, the program's model, random
    adapters with B non-zero, and a batch of sequences."""
    c = tiny(CELL)
    fam = family(c)
    model = fam.build_model(c.config)
    params = fam.init_params(c.config, jax.random.PRNGKey(3))
    params = {"lora": {p: {"lora_a": ab["lora_a"],
                           "lora_b": 0.05 * jax.random.normal(
                               jax.random.fold_in(jax.random.PRNGKey(4), i),
                               ab["lora_b"].shape)}
                       for i, (p, ab) in enumerate(
                           sorted(params["lora"].items()))}}
    seqs = jax.random.randint(jax.random.PRNGKey(5),
                              (2, c.config["model"]["seq_len"] + 1), 0,
                              c.config["vocab_size"], jnp.int32)
    return c.config, fam, model, params, seqs


def test_the_base_is_the_published_structure(setup):
    config, fam, model, _, _ = setup
    cfg = fam.arch(config)
    assert cfg.mla.q_lora_rank is None and cfg.n_dense_layers == 1
    assert cfg.moe.scoring == "sigmoid" and cfg.moe.dropless
    seg0, seg1 = model.base["segments"]
    assert seg0[0]["ffn"]["w_gate"].shape == (
        config["hidden_size"], config["intermediate_size"])
    assert "router" not in seg0[0]["ffn"]
    assert seg1[0]["ffn"]["w_gate"].shape == (
        config["layers"] - 1, config["n_routed_experts"],
        config["hidden_size"], config["moe_intermediate_size"])
    assert "wq" in seg0[0]["mixer"] and "wq_a" not in seg0[0]["mixer"]
    # the published widths, at the cell's size
    full = fam.arch(resolve(CELL).config)
    assert (full.d_model, full.d_ff, full.vocab, full.moe.n_experts,
            full.moe.top_k, full.moe.d_ff_expert) == (
        2048, 11264, 163840, 64, 6, 1408)


def test_logits_match_the_reference(setup):
    config, fam, model, params, seqs = setup
    tokens = seqs[:, :-1]
    got = model.apply(params, tokens, model.base)
    want = ref.logits(fam.weights(config),
                      fam.reference_lora(params, config), tokens,
                      fam._dims(config))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def test_lora_gradients_match_the_reference(setup):
    config, fam, model, params, seqs = setup
    got = jax.grad(lambda p: model.loss(p, None, seqs, model.base))(params)
    want = jax.grad(lambda p: fam.loss(p, None, seqs, config))(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        scale = float(jnp.max(jnp.abs(w)))
        assert scale > 0
        np.testing.assert_allclose(np.asarray(g) / scale,
                                   np.asarray(w) / scale, **TOL)


def test_the_dense_layer_matches_the_reference(setup):
    """Layer 0 alone: latent attention and the SwiGLU of width
    ``intermediate_size`` (no router)."""
    from repro.models import blocks
    from repro.models.lora import attach
    config, fam, model, params, _ = setup
    cfg, dims = fam.arch(config), fam._dims(config)
    x = jax.random.normal(jax.random.PRNGKey(6),
                          (2, 16, config["hidden_size"]))
    scales = {p: dims.lora_scale for p in params["lora"]}
    w = attach(model.base, params["lora"], scales)["segments"][0][0]
    got, _, _ = blocks.block_apply(w, cfg, "mla", x,
                                   positions=jnp.arange(16))
    want = ref.block(x, 0, fam.weights(config),
                     fam.reference_lora(params, config), dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)


def _moe_layer(setup, bias=None):
    config, fam, model, _, _ = setup
    cfg, dims = fam.arch(config), fam._dims(config)
    p = jax.tree.map(lambda a: a[0], model.base["segments"][1][0]["ffn"])
    if bias is not None:
        p = dict(p, score_bias=bias.astype(p["score_bias"].dtype))
    ws = fam.weights(config)
    layer = ws.layer(1)
    if bias is not None:
        layer = dict(layer, **{"ffn/score_bias": bias})
    weights = ref.Weights(layer=lambda l: layer, experts=ws.experts,
                          top=ws.top)
    return cfg, dims, p, weights


def test_routing_chooses_on_the_bias_and_weighs_without_it(setup):
    from repro.models import moe
    config, *_ = setup
    e = config["n_routed_experts"]
    bias = jnp.zeros((e,)).at[0].set(0.25).at[1].set(-0.25)   # bf16-exact
    cfg, dims, p, weights = _moe_layer(setup, bias)
    x = jax.random.normal(jax.random.PRNGKey(7), (64, config["hidden_size"]))
    logits = jnp.matmul(x, p["router"].astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
    w, sel, _ = moe.route(logits, p, cfg.moe)
    _, unbiased = jax.lax.top_k(jax.nn.sigmoid(logits), cfg.moe.top_k)
    assert (np.sort(np.asarray(sel), 1)
            != np.sort(np.asarray(unbiased), 1)).any()   # a choice moved
    got = jnp.sum(jax.nn.one_hot(sel, e) * w[..., None], axis=1)
    want = ref.route(x, p["router"], bias, dims)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    # the weights are the chosen scores, normalized and scaled, no bias
    s = jnp.take_along_axis(jax.nn.sigmoid(logits), sel, axis=-1)
    np.testing.assert_allclose(np.asarray(w), np.asarray(
        s / s.sum(-1, keepdims=True) * cfg.moe.routed_scale), **TOL)


def test_one_expert_takes_every_token_and_drops_none(setup):
    """A bias that puts expert 2 among every token's choices: the dropless
    layer computes all of them, as the reference does; the capacity
    path on the same routing drops most."""
    from repro.models import moe
    config, *_ = setup
    e = config["n_routed_experts"]
    bias = jnp.zeros((e,)).at[2].set(100.0)
    cfg, dims, p, weights = _moe_layer(setup, bias)
    x = jax.random.normal(jax.random.PRNGKey(8),
                          (2, 16, config["hidden_size"]))
    got, _ = moe.moe_apply(p, cfg, x)
    want = ref.moe(x.reshape(32, -1), dict(weights.layer(1)), weights, 1,
                   dims).reshape(x.shape)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), **TOL)
    capped = cfg.replace(moe=dataclasses.replace(cfg.moe, dropless=False))
    dropped, _ = moe.moe_apply(p, capped, x)
    assert (float(jnp.max(jnp.abs(dropped - want)))
            > 0.1 * float(jnp.max(jnp.abs(want))))


def test_prefill_then_decode_matches_the_full_forward(setup):
    """MLA without q_lora through the latent cache: prefill 9 tokens, then
    decode the rest one at a time with the absorbed form; each step's
    logits are the reference's at that position. The cache is kept in
    float32 here: its bfloat16 storage, a serving choice, rounds the
    latent by 2^-9."""
    from repro.models import decoder
    config, fam, model, _, seqs = setup
    cfg = fam.arch(config).replace(kv_cache_dtype="float32")
    tokens = seqs[:1, :-1]
    s, s0 = tokens.shape[1], 9
    want = ref.logits(fam.weights(config), {}, tokens, fam._dims(config))
    caches, last = decoder.prefill(model.base, cfg, {"tokens": tokens[:, :s0]},
                                   capacity=s)
    got = [last[:, 0]]
    for t in range(s0, s):
        lg, caches = decoder.decode_step(model.base, cfg, tokens[:, t:t + 1],
                                         jnp.asarray(t, jnp.int32), caches)
        got.append(lg[:, 0])
    np.testing.assert_allclose(np.asarray(jnp.stack(got, 1)),
                               np.asarray(want[:, s0 - 1:]), **TOL)


def test_the_loss_falls_at_the_cpu_size(setup):
    """The configuration's learning rate trains: the mean loss of a batch
    falls over 20 plain SGD steps on it."""
    config, fam, model, params, seqs = setup
    params = fam.init_params(config, jax.random.PRNGKey(3))
    lr = config["training"]["lr"]
    step = jax.jit(jax.value_and_grad(
        lambda p: model.loss(p, None, seqs, model.base)))
    first = None
    for _ in range(20):
        value, g = step(params)
        first = value if first is None else first
        params = jax.tree.map(lambda a, b: a - lr * b, params, g)
    assert float(value) < float(first)


def test_the_cell_runs_through_the_harness_and_is_correct():
    out = run_tiny(CELL)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0


def test_the_control_is_not_correct():
    """The reference computed in bfloat16 (adapters, history and update)
    in the program's place fails the cell's limits."""
    from bench import check, generate
    c = tiny(CELL)
    inputs = generate.make_inputs(family(c), c.config, c.traffic, SEED)
    ref_out = check.reference_outputs(c, inputs)
    ctl = check.control_outputs(c, inputs)
    correct, compared = check.verdict(
        check.numbers(inputs.params, ctl, ref_out), c.limits)
    assert not correct, compared
