"""Sharding layer: logical-axis assignment, divisibility fallback, rule
coverage over real model parameter trees, and a 1-device end-to-end jit."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.models import decoder
from repro.sharding.api import ShardingContext, constrain, use_sharding
from repro.sharding.rules import (cache_logical_axes, make_rules,
                                  param_logical_axes, params_pspecs)
from repro.utils.pytree import tree_map_with_path


@pytest.fixture(scope="module")
def host_mesh():
    from repro.launch.mesh import make_host_mesh
    return make_host_mesh()


def _ctx(mesh, mode="train", **kw):
    return ShardingContext(mesh=mesh,
                           rules=make_rules(multi_pod=False, mode=mode, **kw))


def test_spec_divisibility_fallback(host_mesh):
    ctx = ShardingContext(
        mesh=host_mesh,
        rules={"a": ["model"], "b": [("data", "model"), "data"]})
    # everything divides on a 1×1 mesh
    assert ctx.spec(("a", None), (8, 3)) == P("model", None)


def test_spec_skips_nondivisible():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    ctx = ShardingContext(mesh=mesh, rules={"x": ["model"]})
    # 1-way axis divides everything; now simulate 16-way via fake rule check
    spec = ctx.spec(("x",), (5,))
    assert spec == P("model")      # 5 % 1 == 0


def test_spec_never_reuses_mesh_axis(host_mesh):
    ctx = ShardingContext(mesh=host_mesh,
                          rules={"r": ["model"], "s": ["model", "data"]})
    spec = ctx.spec(("r", "s"), (4, 4))
    assert spec == P("model", "data")   # s falls to data: model taken


def test_param_logical_axes_known_names():
    leaf2 = jnp.zeros((8, 4))
    assert param_logical_axes("blocks/mixer/wq", leaf2) == \
        ("embed", "heads_flat")
    leaf3 = jnp.zeros((2, 8, 4))      # layer-stacked
    assert param_logical_axes("segments/0/mixer/wq", leaf3) == \
        (None, "embed", "heads_flat")
    moe = jnp.zeros((4, 8, 16))
    assert param_logical_axes("ffn/w_gate", moe) == \
        ("experts", "embed", "expert_ffn")
    shared = jnp.zeros((8, 16))
    assert param_logical_axes("ffn/shared/w_gate", shared) == \
        ("embed", "ffn")


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_rule_coverage_all_big_params_shardable(arch, rng, host_mesh):
    """Every ≥2-D parameter leaf of every architecture must map to at least
    one sharded logical axis — unmapped big tensors would silently
    replicate on the production mesh."""
    cfg = get_config(arch, reduced=True)
    params = decoder.model_init(rng, cfg)

    problems = []
    small = ("scale", "bias", "lam", "b_a", "b_x", "b_if", "b_in", "conv_b",
             "conv_w", "r")

    def check(path, leaf):
        name = path.split("/")[-1]
        if leaf.ndim >= 2 and leaf.size >= 4096 and name not in small:
            axes = param_logical_axes(path, leaf)
            if all(a is None for a in axes):
                problems.append((path, leaf.shape))
        return leaf

    tree_map_with_path(check, params)
    assert not problems, f"unsharded params: {problems}"


def test_cache_logical_axes():
    k = jnp.zeros((2, 128, 4, 32))
    assert cache_logical_axes("caches/k", k) == \
        (None, ) * 0 + ("batch", None, "kv_heads", "kv_head_dim")
    ckv = jnp.zeros((2, 128, 32))
    assert cache_logical_axes("c/ckv", ckv) == ("batch", None, "kv_lora")
    stacked = jnp.zeros((4, 2, 128, 4, 32))   # layer-stacked
    axes = cache_logical_axes("k", stacked)
    assert axes[0] is None and axes[1] == "batch"


def test_constrain_is_identity_without_context(rng):
    x = jax.random.normal(rng, (4, 4))
    np.testing.assert_array_equal(np.asarray(constrain(x, (None, None))),
                                  np.asarray(x))


def test_constrain_rank_mismatch_raises(host_mesh):
    ctx = _ctx(host_mesh)
    with use_sharding(ctx):
        with pytest.raises(ValueError):
            constrain(jnp.zeros((2, 2)), ("batch",))


def test_train_step_jits_under_mesh(host_mesh, rng):
    """End-to-end: the sharded code path (with constrains active) runs on
    a 1-device mesh and matches the unsharded result."""
    from repro.models.steps import init_train_state, make_train_step
    from repro.optim.optimizers import sgd
    from repro.optim.schedules import constant_lr

    cfg = get_config("qwen3-1.7b", reduced=True)
    opt = sgd()
    state = init_train_state(rng, cfg, opt)
    batch = {"tokens": jax.random.randint(rng, (2, 16), 0, cfg.vocab)}
    step = make_train_step(cfg, opt, constant_lr(0.01))
    plain_state, plain_metrics = jax.jit(step)(state, batch)
    ctx = _ctx(host_mesh)
    with host_mesh, use_sharding(ctx):
        sh_state, sh_metrics = jax.jit(step)(state, batch)
    assert float(plain_metrics["loss"]) == pytest.approx(
        float(sh_metrics["loss"]), rel=1e-5)
    for a, b in zip(jax.tree.leaves(plain_state["params"]),
                    jax.tree.leaves(sh_state["params"])):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-5)


def test_params_pspecs_builds_for_all_archs(host_mesh, rng):
    ctx = _ctx(host_mesh)
    for arch in ("olmoe-1b-7b", "recurrentgemma-9b", "xlstm-125m"):
        cfg = get_config(arch, reduced=True)
        params = decoder.model_init(rng, cfg)
        specs = params_pspecs(ctx, params)
        assert len(jax.tree.leaves(specs, is_leaf=lambda x: isinstance(
            x, P))) == len(jax.tree.leaves(params))
