"""Federated LoRA (ISSUE 10 tentpole): adapter-subtree training over the
model zoo, the full-rank ≡ dense identity, spec v7 gating and the 2-D
("clients", "model") federated mesh.

The wrapped model's trainable tree holds only rank-r factors, so the
*unchanged* federated core (every executor, the int8 HistoryStore, CC
replay) operates on O(N·r·d) state instead of O(N·P). The pins:

* round 0 is bit-exactly the frozen base (B zero-init);
* rank-r LoRA on the simple model matches the python oracle ≤ 1e-5 across
  the executor matrix (the acceptance criterion);
* full-rank identity LoRA (A = I frozen, scale 1, base trainable)
  reproduces the DENSE path's metric stream and test logits ≤ 1e-5 — the
  adapter machinery adds exactly zero numerics of its own;
* spec v7 gates zoo models behind ``lora_rank >= 1`` (dense federation of
  a zoo tree would silently blow the history store back up to O(N·P));
* ``make_fed_mesh`` + ``make_fed_rules`` place stacked per-client adapters
  on ``P("clients", "model", ...)`` and the sharded executor accepts the
  2-D mesh unchanged.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, Session
from repro.api.spec import SPEC_VERSION, _FIELD_INTRO
from repro.core.rounds import (FedConfig, init_fed_state,
                               make_sharded_span_runner)
from repro.core.schedules import make_plan
from repro.data.federated import CohortSampler, build_federated
from repro.data.partition import partition_gamma
from repro.data.synthetic import make_dataset, train_test_split
from repro.launch.mesh import make_fed_mesh
from repro.models.lora import (LORA_TARGETS, lora_classifier, lora_report,
                               target_leaves)
from repro.models.simple import make_classifier
from repro.models.zoo import ZOO_KINDS, make_zoo_classifier
from repro.sharding.api import ShardingContext
from repro.sharding.rules import make_fed_rules, params_pspecs

RNG = jax.random.PRNGKey(0)
ATOL = 1e-5


def _mlp():
    return make_classifier("mlp", input_shape=(8,), n_classes=4, width=4)


def _x(shape=(4, 8)):
    return jax.random.normal(jax.random.PRNGKey(7), shape)


# ---------------------------------------------------------------------------
# adapter construction
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", ("mlp",) + ZOO_KINDS)
def test_round0_is_bit_exactly_the_base(kind):
    """B is zero-initialized, so before any training the wrapped model IS
    the frozen base — to the bit, not within a tolerance."""
    if kind == "mlp":
        base = _mlp()
    else:
        base = make_zoo_classifier(kind, input_shape=(8,), n_classes=4,
                                   width=2, n_layers=1)
    wrapped = lora_classifier(base, RNG, 2)
    x = _x()
    np.testing.assert_array_equal(
        np.asarray(wrapped.apply(wrapped.init(jax.random.PRNGKey(5)), x,
                                 wrapped.base)),
        np.asarray(base.apply(base.init(RNG), x)))


def test_adapter_tree_shape_and_freeze_semantics():
    base = make_zoo_classifier("decoder", input_shape=(8,), n_classes=4,
                               width=2, n_layers=1)
    wrapped = lora_classifier(base, RNG, 3)
    params = wrapped.init(jax.random.PRNGKey(1))
    assert set(params) == {"lora"}          # freeze_base: adapters only
    for path, ab in params["lora"].items():
        assert path.split("/")[-1] in LORA_TARGETS
        a, b = ab["lora_a"], ab["lora_b"]
        assert a.shape[-1] == b.shape[-2] <= 3      # rank dim
        assert not np.asarray(b).any()              # zero-init B
    # thawed base: the non-adapted leaves appear under "base", none of the
    # adapted kernels do (they are replaced by their factors)
    thawed = lora_classifier(base, RNG, 3, freeze_base=False)
    p2 = thawed.init(jax.random.PRNGKey(1))
    assert set(p2) == {"lora", "base"}
    assert set(p2["base"]).isdisjoint(set(p2["lora"]))
    assert any(path.endswith("final_norm/scale") for path in p2["base"])


def test_adapter_tree_is_small(capsys=None):
    base = make_zoo_classifier("decoder", input_shape=(8,), n_classes=4,
                               width=4, n_layers=2)
    wrapped = lora_classifier(base, RNG, 2)
    rep = lora_report(base.init(RNG), wrapped.init(RNG))
    assert rep["p_trainable"] < rep["p_dense"] / 5
    assert rep["trainable_frac"] == rep["p_trainable"] / rep["p_dense"]


def test_frozen_a_leaves_only_b_trainable():
    base = _mlp()
    wrapped = lora_classifier(base, RNG, 2, train_a=False)
    params = wrapped.init(jax.random.PRNGKey(2))
    for ab in params["lora"].values():
        assert set(ab) == {"lora_b"}
    # gradients flow into B through the frozen A
    from repro.models.simple import xent_loss
    x, y = _x(), jnp.zeros((4,), jnp.int32)
    g = jax.grad(lambda p: xent_loss(wrapped, p, x, y))(params)
    assert any(np.asarray(l).any() for l in jax.tree.leaves(g))


def test_identity_init_requires_matching_rank():
    with pytest.raises(ValueError, match="identity"):
        lora_classifier(_mlp(), RNG, 2, init_a="identity").init(RNG)


def test_bad_rank_rejected():
    with pytest.raises(ValueError, match="rank"):
        lora_classifier(_mlp(), RNG, 0)


# ---------------------------------------------------------------------------
# executor matrix on the simple model (the acceptance criterion)
# ---------------------------------------------------------------------------

_LORA_EXECUTORS = ("python", "scan", "sharded", "async")
_RUNS: dict = {}


def _lora_spec(executor: str) -> ExperimentSpec:
    return ExperimentSpec(
        dataset="gaussian", n_samples=256, dim=8, n_classes=4,
        n_clients=4, budget="power", beta=2, model="simple", width=4,
        lora_rank=2, strategy="cc", local_steps=2, batch_size=16, lr=0.1,
        schedule="adhoc", rounds=6, eval_every=2, seed=0,
        executor=executor)


def _run(executor: str):
    if executor not in _RUNS:
        sess = Session.from_spec(_lora_spec(executor)).run()
        _RUNS[executor] = (jax.tree.map(np.asarray, sess.state["params"]),
                           sess.metrics.series("test_acc"))
    return _RUNS[executor]


@pytest.mark.parametrize("executor", _LORA_EXECUTORS[1:])
def test_lora_matches_python_oracle(executor):
    """Rank-2 adapter federation on the simple model: every executor's
    final adapter tree and metric stream match the python oracle ≤ 1e-5."""
    o_params, o_accs = _run("python")
    params, accs = _run(executor)
    np.testing.assert_allclose(accs, o_accs, atol=ATOL)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(o_params)):
        np.testing.assert_allclose(a, b, atol=ATOL,
                                   err_msg=f"lora/{executor} params")


def test_lora_adapters_actually_train():
    params, _ = _run("python")
    b_leaves = [v["lora_b"] for v in params["lora"].values()]
    assert any(np.asarray(b).any() for b in b_leaves)


def test_history_state_is_adapter_sized():
    """The federated carry (Δ history) is the ADAPTER tree stacked over
    clients — O(N·r·d), not O(N·P)."""
    sess = Session.from_spec(_lora_spec("scan")).run()
    base = make_classifier("mlp", input_shape=(8,), n_classes=4, width=4)
    p_dense = sum(int(np.prod(l.shape))
                  for l in jax.tree.leaves(base.init(RNG)))
    p_hist = sum(int(np.prod(l.shape[1:]))
                 for l in jax.tree.leaves(sess.state["deltas"]))
    p_train = sum(int(np.prod(l.shape))
                  for l in jax.tree.leaves(sess.state["params"]))
    assert p_hist == p_train < p_dense


# ---------------------------------------------------------------------------
# full-rank identity LoRA ≡ the dense path
# ---------------------------------------------------------------------------


def test_full_rank_identity_lora_matches_dense():
    """With A = I frozen, scale 1 and a thawed base, W_eff = W + B and
    ∂L/∂B = ∂L/∂W: the wrapped model's SGD trajectory IS the dense path.
    The frozen base must come from PRNGKey(seed) — the same rng the
    Session hands to ``init_fed_state`` — so both runs start at the same
    point."""
    spec = _lora_spec("scan").replace(lora_rank=0)
    dense = Session.from_spec(spec).run()
    b = spec.build()
    wrapped = lora_classifier(b.model, jax.random.PRNGKey(spec.seed),
                              "full", init_a="identity", train_a=False,
                              freeze_base=False)
    sess = Session(wrapped, b.data, b.fed, b.plan, x_test=b.x_test,
                   y_test=b.y_test, eval_every=spec.eval_every,
                   executor="scan", policy=b.policy, profile=b.profile).run()
    np.testing.assert_allclose(sess.metrics.series("test_acc"),
                               dense.metrics.series("test_acc"), atol=ATOL)
    dense_logits = dense.model.apply(dense.state["params"], b.x_test)
    lora_logits = wrapped.apply(sess.state["params"], b.x_test, wrapped.base)
    np.testing.assert_allclose(np.asarray(lora_logits),
                               np.asarray(dense_logits), atol=ATOL)


# ---------------------------------------------------------------------------
# spec v7 gating
# ---------------------------------------------------------------------------


def test_spec_v7_fields_are_versioned():
    assert SPEC_VERSION == 7
    assert _FIELD_INTRO["lora_rank"] == 7
    assert _FIELD_INTRO["freeze_base"] == 7


def test_zoo_model_requires_lora_rank():
    with pytest.raises(ValueError, match="lora_rank"):
        ExperimentSpec(model="decoder")
    ExperimentSpec(model="decoder", lora_rank=4)      # fine


def test_freeze_base_false_requires_adapters():
    with pytest.raises(ValueError, match="freeze_base"):
        ExperimentSpec(freeze_base=False)
    ExperimentSpec(freeze_base=False, lora_rank=2)    # fine


def test_negative_lora_rank_rejected():
    with pytest.raises(ValueError, match="lora_rank"):
        ExperimentSpec(lora_rank=-1)


def test_spec_round_trip_with_lora():
    spec = ExperimentSpec(model="decoder", lora_rank=4, freeze_base=True,
                          rounds=2, eval_every=1)
    assert ExperimentSpec.from_dict(spec.to_dict()) == spec


def test_simple_is_an_mlp_alias():
    a = ExperimentSpec(model="simple", rounds=2, eval_every=1).build()
    b = ExperimentSpec(model="mlp", rounds=2, eval_every=1).build()
    for u, v in zip(jax.tree.leaves(a.model.init(RNG)),
                    jax.tree.leaves(b.model.init(RNG))):
        np.testing.assert_array_equal(np.asarray(u), np.asarray(v))


# ---------------------------------------------------------------------------
# the 2-D ("clients", "model") federated mesh
# ---------------------------------------------------------------------------


def test_make_fed_mesh_validation():
    with pytest.raises(ValueError, match="clients"):
        make_fed_mesh(axes=("data", "model"))
    with pytest.raises(ValueError, match="shape"):
        make_fed_mesh(shape=(1,))
    ndev = len(jax.devices())
    with pytest.raises(ValueError, match="mesh size"):
        make_fed_mesh(shape=(ndev + 1, 2))
    mesh = make_fed_mesh()
    assert mesh.axis_names == ("clients", "model")
    assert mesh.devices.shape == (ndev, 1)


def test_fed_rules_place_stacked_adapters():
    """Stacked per-client adapters: leading dim on 'clients', the rank dim
    on 'model', factor dims replicated."""
    mesh = make_fed_mesh(shape=(1, 1))
    ctx = ShardingContext(mesh=mesh, rules=make_fed_rules())
    wrapped = lora_classifier(_mlp(), RNG, 2)
    stacked = jax.vmap(wrapped.init)(
        jax.random.split(jax.random.PRNGKey(0), 4))
    specs = params_pspecs(ctx, stacked, client_axis=True)
    flat = {p: s for p, s in
            ((path, spec) for path, spec in _flatten(specs))}
    b_specs = [s for p, s in flat.items() if p.endswith("lora_b")]
    assert b_specs, "no lora_b leaves in the stacked tree"
    for s in b_specs:
        assert tuple(s) == ("clients", "model", None)
    a_specs = [s for p, s in flat.items() if p.endswith("lora_a")]
    for s in a_specs:
        assert tuple(s) == ("clients", None, "model")


def _flatten(tree, prefix=""):
    if isinstance(tree, dict):
        for k, v in tree.items():
            yield from _flatten(v, f"{prefix}{k}/")
    else:
        yield prefix[:-1], tree


def test_sharded_executor_on_fed_mesh():
    """The sharded span runner accepts the 2-D federated mesh and
    reproduces the default-mesh run bit-for-bit (specs never name 'model',
    so the extra axis only replicates)."""
    ds = make_dataset("gaussian", n=256, dim=8, n_classes=4, seed=0)
    tr, _ = train_test_split(ds)
    fd = build_federated(tr, partition_gamma(tr, 4, gamma=0.5, seed=0))
    model = lora_classifier(_mlp(), RNG, 2)
    fed = FedConfig(strategy="cc", local_steps=2, batch_size=16, lr=0.1)
    plan = make_plan("adhoc", np.ones(4), 4, seed=2)
    sel, train = jnp.asarray(plan.selection), jnp.asarray(plan.training)
    k = jnp.full((4,), fed.local_steps, jnp.int32)
    idx = jnp.asarray(CohortSampler(4, 2, seed=3).indices(4))

    def fresh():
        return init_fed_state(jax.random.PRNGKey(0), model, 4)

    s_1d = make_sharded_span_runner(model, fd, fed, cohort_size=2)(
        fresh(), sel, train, k, idx)
    s_2d = make_sharded_span_runner(
        model, fd, fed, cohort_size=2,
        mesh=make_fed_mesh(shape=(1, 1)))(fresh(), sel, train, k, idx)
    for a, b in zip(jax.tree.leaves(s_1d["params"]),
                    jax.tree.leaves(s_2d["params"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_target_paths_cover_zoo_attention_and_mlp():
    """Every zoo kind exposes ≥ 1 attention/MLP projection to adapt —
    including xLSTM, whose mixer leaves reuse the wq/wk/wv names."""
    for kind in ZOO_KINDS:
        base = make_zoo_classifier(kind, input_shape=(8,), n_classes=4,
                                   width=2, n_layers=1)
        paths = target_leaves(base.init(RNG), LORA_TARGETS)
        assert paths, f"{kind} has no adaptable leaves"
