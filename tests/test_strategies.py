"""Strategy registry: dispatch, round-trip, cc_decay semantics, the
Appendix-A cost-report variants, and property-based hook invariants
(replayed deterministically through the hypothesis shim when the real
package is absent)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.engine import (FedConfig, STRATEGIES, cost_report,
                               init_fed_state, make_round_fn)
from repro.core.schedules import make_plan
from repro.core.strategies import (CCDecay, RoundCtx, Strategy,
                                   available_strategies, get_strategy,
                                   register)
from repro.data.federated import build_federated
from repro.data.partition import partition_gamma
from repro.data.synthetic import make_dataset, train_test_split
from repro.models.simple import make_classifier

N = 4


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("gaussian", n=256, dim=8, n_classes=4, seed=0)
    tr, _ = train_test_split(ds)
    parts = partition_gamma(tr, N, gamma=0.5, seed=0)
    fd = build_federated(tr, parts)
    model = make_classifier("mlp", input_shape=(8,), n_classes=4, width=4)
    return model, fd


# ---------------------------------------------------------------------------
# registry dispatch
# ---------------------------------------------------------------------------


def test_unknown_strategy_raises():
    with pytest.raises(ValueError, match="unknown strategy"):
        get_strategy("definitely_not_registered")
    with pytest.raises(ValueError, match="unknown strategy"):
        FedConfig(strategy="definitely_not_registered")


def test_all_registered_names_round_trip():
    names = available_strategies()
    assert len(names) >= 8            # paper's seven + cc_decay
    for name in names:
        s = get_strategy(name)
        assert s.name == name
        # every registered name must build a valid config
        assert FedConfig(strategy=name).strategy == name


def test_paper_names_present():
    for name in ("fedavg", "dropout", "s1", "s2", "cc", "ccc", "fednova",
                 "cc_decay"):
        assert name in available_strategies()
    # back-compat module constant mirrors the registry
    assert set(STRATEGIES) == set(available_strategies())


def test_register_requires_name_and_allows_plugins():
    with pytest.raises(ValueError):
        register(Strategy(name=""))
    probe = CCDecay(name="_test_probe_gamma_half", gamma=0.5)
    try:
        register(probe)
        assert get_strategy("_test_probe_gamma_half") is probe
        assert FedConfig(strategy="_test_probe_gamma_half").resolve() is probe
    finally:
        from repro.core import strategies as S
        S._REGISTRY.pop("_test_probe_gamma_half", None)


def test_fused_capability_flags():
    """Every built-in strategy ships a ``FusedEpilogue``; only the bare
    ``Strategy`` base (custom registrations) defaults to non-capable."""
    from repro.core.strategies import Strategy, available_strategies

    for name in available_strategies():
        s = get_strategy(name)
        assert s.fused_capable, name
        assert s.needs_stale == (name in ("s2", "ccc")), name
    assert not Strategy(name="_probe").fused_capable


# ---------------------------------------------------------------------------
# cc_decay semantics: γ·Δ replay with geometric fade over consecutive skips
# ---------------------------------------------------------------------------


def test_cc_decay_skipper_contributes_decayed_delta(setup):
    model, fd = setup
    gamma = get_strategy("cc_decay").gamma
    fed = FedConfig(strategy="cc_decay", local_steps=1)
    state = init_fed_state(jax.random.PRNGKey(0), model, N)
    rf = make_round_fn(model, fd, fed)
    k = jnp.full((N,), 1, jnp.int32)
    all_on = jnp.ones(N, bool)
    state = rf(state, all_on, all_on, k)        # round 0: everyone trains
    d0 = jax.tree.map(lambda d: np.asarray(d[0]), state["deltas"])
    skip0 = jnp.asarray([False, True, True, True])
    for step in range(1, 4):
        state = rf(state, all_on, skip0, k)
        for a, b in zip(jax.tree.leaves(d0),
                        jax.tree.leaves(state["deltas"])):
            np.testing.assert_allclose(gamma ** step * a, np.asarray(b)[0],
                                       atol=1e-6)


def test_cc_decay_gamma_one_matches_cc(setup):
    model, fd = setup
    probe = CCDecay(name="_test_gamma_one", gamma=1.0)
    from repro.core import strategies as S
    register(probe)
    try:
        k = jnp.full((N,), 1, jnp.int32)
        all_on = jnp.ones(N, bool)
        train = jnp.asarray([True, False, True, False])
        outs = {}
        for name in ("cc", "_test_gamma_one"):
            fed = FedConfig(strategy=name, local_steps=1)
            state = init_fed_state(jax.random.PRNGKey(0), model, N)
            rf = make_round_fn(model, fd, fed)
            state = rf(state, all_on, all_on, k)
            state = rf(state, all_on, train, k)
            outs[name] = state
        for a, b in zip(jax.tree.leaves(outs["cc"]["params"]),
                        jax.tree.leaves(outs["_test_gamma_one"]["params"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-6)
    finally:
        S._REGISTRY.pop("_test_gamma_one", None)


# ---------------------------------------------------------------------------
# Appendix-A cost accounting
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def plan():
    p = np.array([1.0, 0.5, 0.25, 0.125])
    return make_plan("round_robin", p, 80, seed=0)


def test_cost_report_client_variant(plan):
    mb = 1000
    rep = cost_report(plan, mb, variant="client")
    trained = (plan.selection & plan.training).sum()
    estimated = (plan.selection & ~plan.training).sum()
    # Alg. 1: every selected client uploads a full model either way
    assert rep["upload_bytes"] == (trained + estimated) * mb
    assert rep["client_storage_bytes"] == mb
    assert rep["server_storage_bytes"] == 0
    assert rep["compute_saved_frac"] == pytest.approx(
        1.0 - plan.compute_fraction())


def test_cost_report_server_variant(plan):
    mb = 1000
    rep = cost_report(plan, mb, variant="server")
    trained = (plan.selection & plan.training).sum()
    estimated = (plan.selection & ~plan.training).sum()
    # Alg. 2: skippers send one bit; the server stores every client's Δ
    assert rep["upload_bytes"] == trained * mb + estimated // 8 + 1
    assert rep["client_storage_bytes"] == 0
    assert rep["server_storage_bytes"] == plan.n_clients * mb


@pytest.mark.parametrize("frac", [0.0, 0.5, 1.0])
def test_cost_report_mixed_interpolates(plan, frac):
    mb = 1000
    mixed = cost_report(plan, mb, variant="mixed", mixed_client_frac=frac)
    client = cost_report(plan, mb, variant="client")
    server = cost_report(plan, mb, variant="server")
    assert server["upload_bytes"] <= mixed["upload_bytes"] + 1
    assert mixed["upload_bytes"] <= client["upload_bytes"]
    # server-side storage shrinks as more clients hold their own Δ
    assert mixed["server_storage_bytes"] == int(
        (1 - frac) * plan.n_clients * mb)


def test_cost_report_unknown_variant_raises(plan):
    with pytest.raises(ValueError):
        cost_report(plan, 1000, variant="nonsense")


# ---------------------------------------------------------------------------
# property-based hook invariants (any strategy, any masks)
# ---------------------------------------------------------------------------


def _tree(n, scale=1.0, seed=0):
    r = np.random.default_rng(seed)
    return {"w": jnp.asarray(scale * r.standard_normal((n, 3)), jnp.float32),
            "b": jnp.asarray(scale * r.standard_normal((n,)), jnp.float32)}


def _ctx(sel, train, k, rnd=1, tau=100):
    n = len(sel)
    return RoundCtx(sel_mask=jnp.asarray(sel, bool),
                    train_mask=jnp.asarray(train, bool),
                    k_active=jnp.asarray(k, jnp.int32),
                    round=jnp.asarray(rnd, jnp.int32), tau=tau,
                    stale_delta=_tree(n, seed=1), trained_delta=_tree(n))


@pytest.fixture(scope="module", autouse=True)
def _compiled(setup):
    """Run every property test's body once per strategy before hypothesis
    times its examples: the first call of each op traces and compiles,
    which alone outlasts the 200 ms deadline on one example and not on
    the next (hypothesis then reports the test as flaky)."""
    for name in available_strategies():
        _all_train_round(setup, name)
    if not hasattr(test_aggregation_weights_sum_to_one, "hypothesis"):
        return                  # the replay shim times nothing
    agg, convex, zero, hist = (t.hypothesis.inner_test for t in (
        test_aggregation_weights_sum_to_one,
        test_merge_stale_weights_stay_convex,
        test_merge_stale_at_zero_staleness_equals_aggregate,
        test_update_history_is_mask_idempotent))
    masks = [[True, False] * (N // 2), [False] * N, [True] * N]
    for name in available_strategies():
        for sel in masks:
            for train in masks:
                agg(name=name, sel=sel, train=train, c=1.0)
                convex(name=name, sel=sel, train=train, stale=[1] * N,
                       decay=0.5, c=1.0)
                hist(name=name, sel=sel, train=train)
                for schedule in ("geometric", "polynomial"):
                    zero(schedule=schedule, name=name, sel=sel,
                         train=train, decay=0.5)


@settings(max_examples=25)
@given(name=st.sampled_from(available_strategies()),
       sel=st.lists(st.booleans(), min_size=N, max_size=N),
       train=st.lists(st.booleans(), min_size=N, max_size=N),
       c=st.floats(min_value=-3.0, max_value=3.0))
def test_aggregation_weights_sum_to_one(name, sel, train, c):
    """Under ANY sel/train mask (uniform step counts), every strategy's
    aggregation is a convex combination: aggregating identical per-client
    deltas returns that delta unchanged — the Eq.-3 weights sum to 1."""
    strategy = get_strategy(name)
    ctx = _ctx(sel, train, [3] * N)
    aggf = strategy.agg_mask(ctx).astype(jnp.float32)
    const = jax.tree.map(lambda x: jnp.full_like(x, c), _tree(N))
    out = strategy.aggregate(const, aggf, ctx)
    # empty rounds aggregate to exactly zero (eps denominator), otherwise
    # the weights are convex and the constant comes back unchanged
    expect = c if bool(aggf.sum() > 0) else 0.0
    for leaf in jax.tree.leaves(out):
        np.testing.assert_allclose(np.asarray(leaf), expect, atol=1e-5)


@settings(max_examples=25)
@given(name=st.sampled_from(available_strategies()),
       sel=st.lists(st.booleans(), min_size=N, max_size=N),
       train=st.lists(st.booleans(), min_size=N, max_size=N),
       stale=st.lists(st.integers(min_value=0, max_value=6),
                      min_size=N, max_size=N),
       decay=st.floats(min_value=0.3, max_value=1.0),
       c=st.floats(min_value=-3.0, max_value=3.0))
def test_merge_stale_weights_stay_convex(name, sel, train, stale, decay, c):
    """The async merge invariant: under ANY buffer mask and ANY staleness
    vector the staleness-decayed weights stay a convex combination —
    merging identical per-client deltas returns that delta unchanged, and
    an empty buffer merges to exactly zero (a no-op update)."""
    from repro.core.async_rounds import staleness_weights
    strategy = get_strategy(name)
    ctx = _ctx(sel, train, [3] * N)
    aggf = strategy.agg_mask(ctx).astype(jnp.float32)
    s = jnp.asarray(stale, jnp.int32)
    w = staleness_weights("geometric", decay, s)
    const = jax.tree.map(lambda x: jnp.full_like(x, c), _tree(N))
    out = strategy.merge_stale(const, aggf, s, w, ctx)
    expect = c if bool((aggf * w).sum() > 0) else 0.0
    for leaf in jax.tree.leaves(out):
        np.testing.assert_allclose(np.asarray(leaf), expect, atol=1e-5)


@pytest.mark.parametrize("schedule", ["geometric", "polynomial"])
@settings(max_examples=25)
@given(name=st.sampled_from(available_strategies()),
       sel=st.lists(st.booleans(), min_size=N, max_size=N),
       train=st.lists(st.booleans(), min_size=N, max_size=N),
       decay=st.floats(min_value=0.1, max_value=1.0))
def test_merge_stale_at_zero_staleness_equals_aggregate(schedule, name,
                                                        sel, train, decay):
    """At staleness 0 every schedule's weight is EXACTLY 1.0, so
    ``merge_stale`` must reproduce ``aggregate`` bit-for-bit for every
    registered strategy — the hook-level statement of the async
    executor's collapse-to-synchronous guarantee."""
    from repro.core.async_rounds import staleness_weights
    strategy = get_strategy(name)
    ctx = _ctx(sel, train, [3] * N)
    aggf = strategy.agg_mask(ctx).astype(jnp.float32)
    zero = jnp.zeros((N,), jnp.int32)
    w = staleness_weights(schedule, decay, zero)
    np.testing.assert_array_equal(np.asarray(w), 1.0)
    delta = _tree(N, seed=2)
    merged = strategy.merge_stale(delta, aggf, zero, w, ctx)
    plain = strategy.aggregate(delta, aggf, ctx)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{name}/{schedule}")


_ALL_TRAIN_PARAMS: dict = {}


def _all_train_round(setup, name):
    if name not in _ALL_TRAIN_PARAMS:
        model, fd = setup
        fed = FedConfig(strategy=name, local_steps=2, batch_size=16, lr=0.1)
        rf = make_round_fn(model, fd, fed)
        state = init_fed_state(jax.random.PRNGKey(0), model, N)
        on = jnp.ones(N, bool)
        state = rf(state, on, on, jnp.full((N,), 2, jnp.int32))
        _ALL_TRAIN_PARAMS[name] = jax.tree.map(np.asarray, state["params"])
    return _ALL_TRAIN_PARAMS[name]


@given(name=st.sampled_from(available_strategies()))
def test_estimation_is_noop_when_all_train(setup, name):
    """When every client really trains, estimates never enter the round:
    all strategies collapse to the same FedAvg update (FedNova included —
    uniform step counts make its normalization cancel exactly)."""
    ref = _all_train_round(setup, "fedavg")
    got = _all_train_round(setup, name)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-6, err_msg=name)


@settings(max_examples=25)
@given(name=st.sampled_from(available_strategies()),
       sel=st.lists(st.booleans(), min_size=N, max_size=N),
       train=st.lists(st.booleans(), min_size=N, max_size=N))
def test_update_history_is_mask_idempotent(name, sel, train):
    """Applying ``update_history`` twice with the same masks and round
    inputs is a no-op the second time — history written for a mask pattern
    is stable until the inputs change."""
    strategy = get_strategy(name)
    ctx = _ctx(sel, train, [3] * N)
    trained_delta, local, est = _tree(N, seed=2), _tree(N, seed=3), \
        _tree(N, seed=4)
    state = {"deltas": _tree(N, seed=5), "prev_local": _tree(N, seed=6)}
    d1, p1 = strategy.update_history(state, ctx, trained_delta, local, est)
    d2, p2 = strategy.update_history({"deltas": d1, "prev_local": p1},
                                     ctx, trained_delta, local, est)
    for a, b in zip(jax.tree.leaves((d1, p1)), jax.tree.leaves((d2, p2))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
