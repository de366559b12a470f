"""Round executors: the refactored registry engine must reproduce the
pre-refactor monolith bit-for-bit, the scan executor must match the python
loop, and the fused Pallas path must match the tree-ops path ≤1e-5.

``_legacy_round_fn`` below is a verbatim copy of the pre-refactor
``engine.make_round_fn`` round body (the seven-way if/elif monolith) and is
the golden reference the equivalence tests compare against.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import (FedConfig, init_fed_state, make_round_fn,
                               run_federated)
from repro.core.rounds import make_span_runner, span_boundaries
from repro.core.schedules import make_plan
from repro.data.federated import build_federated
from repro.data.partition import budget_law, partition_gamma
from repro.data.synthetic import make_dataset, train_test_split
from repro.models.simple import make_classifier
from repro.utils.pytree import (tree_add, tree_broadcast_clients,
                                tree_masked_mean, tree_ravel,
                                tree_ravel_clients, tree_sub,
                                tree_zeros_like)

N = 4


@pytest.fixture(scope="module")
def setup():
    ds = make_dataset("gaussian", n=256, dim=8, n_classes=4, seed=0)
    tr, te = train_test_split(ds)
    parts = partition_gamma(tr, N, gamma=0.5, seed=0)
    fd = build_federated(tr, parts)
    model = make_classifier("mlp", input_shape=(8,), n_classes=4, width=4)
    return model, fd, te


# ---------------------------------------------------------------------------
# golden reference: the pre-refactor monolithic round function
# ---------------------------------------------------------------------------


def _mask_tree(mask, a, b):
    def sel(x, y):
        m = mask.reshape((-1,) + (1,) * (x.ndim - 1))
        return jnp.where(m, x, y)
    return jax.tree.map(sel, a, b)


def _legacy_round_fn(model, data, fed):
    """Verbatim pre-refactor round body (if/elif over strategy names)."""
    from repro.core.rounds import _local_train
    n = data.n_clients

    @functools.partial(jax.jit, static_argnames=())
    def round_fn(state, sel_mask, train_mask, k_active):
        key, *keys = jax.random.split(state["key"], n + 1)
        keys = jnp.stack(keys)
        broadcast = tree_broadcast_clients(state["params"], n)
        local = jax.vmap(
            lambda p, k, cx, cy, sz, ka: _local_train(
                model, p, k, cx, cy, sz, fed.local_steps, ka,
                fed.batch_size, fed.lr)
        )(broadcast, keys, data.x, data.y, data.sizes, k_active)
        trained_delta = tree_sub(local, broadcast)

        stale_delta = tree_sub(state["prev_local"], broadcast)
        stale_delta = _mask_tree(state["trained_ever"], stale_delta,
                                 tree_zeros_like(stale_delta))
        if fed.strategy == "cc":
            est = state["deltas"]
        elif fed.strategy == "ccc":
            use_s3 = state["round"] < fed.tau
            est = jax.tree.map(
                lambda a, b: jnp.where(use_s3, a, b),
                state["deltas"], stale_delta)
        elif fed.strategy == "s2":
            est = stale_delta
        else:  # s1 / fedavg / dropout / fednova never aggregate estimates
            est = tree_zeros_like(trained_delta)

        delta_i = _mask_tree(train_mask, trained_delta, est)

        if fed.strategy in ("s1", "fedavg", "dropout", "fednova"):
            agg_mask = sel_mask & train_mask
        else:
            agg_mask = sel_mask
        aggf = agg_mask.astype(jnp.float32)
        if fed.strategy == "fednova":
            ka = jnp.maximum(k_active.astype(jnp.float32), 1.0)
            d_norm = jax.tree.map(
                lambda x: x / ka.reshape((-1,) + (1,) * (x.ndim - 1)),
                delta_i)
            coeff = jnp.sum(aggf * ka) / jnp.maximum(jnp.sum(aggf), 1e-9)
            delta = jax.tree.map(
                lambda x: coeff * x, tree_masked_mean(d_norm, aggf))
        else:
            delta = tree_masked_mean(delta_i, aggf)
        new_params = tree_add(state["params"], delta)

        upd = sel_mask & train_mask
        deltas = _mask_tree(upd, trained_delta, state["deltas"])
        prev_local = _mask_tree(upd, local, state["prev_local"])
        return {
            "params": new_params,
            "deltas": deltas,
            "prev_local": prev_local,
            "trained_ever": state["trained_ever"] | upd,
            "round": state["round"] + 1,
            "key": key,
        }

    return round_fn


MASKS = [  # (sel, train) per round: mixed selection / skip patterns
    (np.array([1, 1, 1, 1], bool), np.array([1, 1, 1, 1], bool)),
    (np.array([1, 1, 1, 1], bool), np.array([1, 0, 1, 0], bool)),
    (np.array([1, 1, 0, 1], bool), np.array([0, 1, 0, 1], bool)),
    (np.array([1, 1, 1, 0], bool), np.array([1, 1, 0, 0], bool)),
]


@pytest.mark.parametrize("strategy",
                         ["fedavg", "s1", "s2", "cc", "ccc", "fednova",
                          "dropout"])
def test_registry_engine_matches_legacy_monolith(setup, strategy):
    """≥3 rounds of the new registry-dispatched round must reproduce the
    pre-refactor monolith exactly (same seed ⇒ same state trajectory)."""
    model, fd, _ = setup
    fed = FedConfig(strategy=strategy, local_steps=2, tau=2)
    k = jnp.full((N,), fed.local_steps, jnp.int32)
    if strategy == "fednova":
        k = jnp.asarray([2, 1, 2, 1], jnp.int32)
    new_rf = make_round_fn(model, fd, fed)
    old_rf = _legacy_round_fn(model, fd, fed)
    s_new = init_fed_state(jax.random.PRNGKey(0), model, N)
    s_old = init_fed_state(jax.random.PRNGKey(0), model, N)
    for sel, train in MASKS:
        s_new = new_rf(s_new, jnp.asarray(sel), jnp.asarray(train), k)
        s_old = old_rf(s_old, jnp.asarray(sel), jnp.asarray(train), k)
        for key in ("params", "deltas", "prev_local"):
            for a, b in zip(jax.tree.leaves(s_new[key]),
                            jax.tree.leaves(s_old[key])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-7, err_msg=key)
        np.testing.assert_array_equal(np.asarray(s_new["trained_ever"]),
                                      np.asarray(s_old["trained_ever"]))


# ---------------------------------------------------------------------------
# scan executor ≡ python loop
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("strategy", ["fedavg", "s2", "cc", "ccc",
                                      "fednova"])
def test_scan_executor_matches_python_loop(setup, strategy):
    """run_federated(executor='scan') and (executor='python') must produce
    identical per-round test_acc trajectories and final state."""
    model, fd, te = setup
    p = budget_law(N, beta=2)
    plan = make_plan("adhoc", p, 12, seed=1)
    fed = FedConfig(strategy=strategy, local_steps=2, batch_size=16, lr=0.1)
    kw = dict(x_test=jnp.asarray(te.x), y_test=jnp.asarray(te.y),
              eval_every=4)
    s_py, m_py = run_federated(model, fd, fed, plan, executor="python", **kw)
    s_sc, m_sc = run_federated(model, fd, fed, plan, executor="scan", **kw)
    assert m_py.series("test_acc") == m_sc.series("test_acc")
    for a, b in zip(jax.tree.leaves(s_py["params"]),
                    jax.tree.leaves(s_sc["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)


def test_span_runner_equals_repeated_rounds(setup):
    model, fd, _ = setup
    fed = FedConfig(strategy="cc", local_steps=2)
    k = jnp.full((N,), fed.local_steps, jnp.int32)
    sel = jnp.asarray(np.stack([m[0] for m in MASKS]))
    train = jnp.asarray(np.stack([m[1] for m in MASKS]))
    rf = make_round_fn(model, fd, fed)
    runner = make_span_runner(model, fd, fed)
    s_loop = init_fed_state(jax.random.PRNGKey(0), model, N)
    for t in range(sel.shape[0]):
        s_loop = rf(s_loop, sel[t], train[t], k)
    s_scan = runner(init_fed_state(jax.random.PRNGKey(0), model, N),
                    sel, train, k)
    for a, b in zip(jax.tree.leaves(s_loop["params"]),
                    jax.tree.leaves(s_scan["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7)
    assert int(s_scan["round"]) == sel.shape[0]


def test_span_boundaries_match_legacy_eval_cadence():
    for rounds, every in [(80, 20), (30, 7), (5, 10), (1, 1), (12, 4)]:
        legacy = [t + 1 for t in range(rounds)
                  if (t + 1) % every == 0 or t == rounds - 1]
        assert span_boundaries(rounds, every) == sorted(set(legacy))


def test_span_boundaries_eval_every_beyond_rounds_is_one_span():
    # a cadence longer than the plan means exactly one span, ending at the
    # final round — no phantom boundaries
    assert span_boundaries(5, 10) == [5]
    assert span_boundaries(1, 100) == [1]
    assert span_boundaries(7, 7) == [7]


@pytest.mark.parametrize("bad", [0, -1, -100])
def test_span_boundaries_rejects_nonpositive_eval_every(bad):
    # regression: eval_every=0 used to emit a bogus round-0 boundary and
    # negative values produced negative stops
    with pytest.raises(ValueError, match="eval_every"):
        span_boundaries(10, bad)


@pytest.mark.parametrize("bad", [0, -1])
def test_span_boundaries_rejects_nonpositive_rounds(bad):
    with pytest.raises(ValueError, match="rounds"):
        span_boundaries(bad, 5)


def test_session_rejects_nonpositive_eval_every(setup):
    # the session guards eagerly (its python loop would otherwise die on a
    # modulo-by-zero mid-run)
    from repro.api import Session
    model, fd, te = setup
    plan = make_plan("full", np.ones(N), 2)
    with pytest.raises(ValueError, match="eval_every"):
        Session(model, fd, FedConfig(strategy="cc"), plan,
                x_test=jnp.asarray(te.x), y_test=jnp.asarray(te.y),
                eval_every=0)


def test_unknown_executor_raises(setup):
    model, fd, te = setup
    plan = make_plan("full", np.ones(N), 2)
    with pytest.raises(ValueError):
        run_federated(model, fd, FedConfig(strategy="cc"), plan,
                      x_test=jnp.asarray(te.x), y_test=jnp.asarray(te.y),
                      executor="warp")


# ---------------------------------------------------------------------------
# fused Pallas path ≡ tree-ops path
# ---------------------------------------------------------------------------


def test_fused_round_matches_tree_ops(setup):
    """The single-HBM-pass kernel round (interpret mode on CPU) matches the
    tree-ops round to ≤1e-5 over several rounds with mixed masks."""
    model, fd, _ = setup
    fed = FedConfig(strategy="cc", local_steps=2)
    k = jnp.full((N,), fed.local_steps, jnp.int32)
    rf_tree = make_round_fn(model, fd, fed)
    rf_fused = make_round_fn(model, fd, fed, fused=True)
    s_t = init_fed_state(jax.random.PRNGKey(0), model, N)
    s_f = init_fed_state(jax.random.PRNGKey(0), model, N)
    for sel, train in MASKS:
        s_t = rf_tree(s_t, jnp.asarray(sel), jnp.asarray(train), k)
        s_f = rf_fused(s_f, jnp.asarray(sel), jnp.asarray(train), k)
        for key in ("params", "deltas"):
            for a, b in zip(jax.tree.leaves(s_t[key]),
                            jax.tree.leaves(s_f[key])):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           atol=1e-5, err_msg=key)


def test_fused_end_to_end_matches(setup):
    model, fd, te = setup
    p = budget_law(N, beta=2)
    plan = make_plan("adhoc", p, 8, seed=2)
    fed = FedConfig(strategy="cc", local_steps=2, batch_size=16, lr=0.1)
    kw = dict(x_test=jnp.asarray(te.x), y_test=jnp.asarray(te.y),
              eval_every=4)
    s_a, m_a = run_federated(model, fd, fed, plan, executor="scan", **kw)
    s_b, m_b = run_federated(model, fd, fed, plan, executor="scan",
                             use_fused=True, **kw)
    for a, b in zip(jax.tree.leaves(s_a["params"]),
                    jax.tree.leaves(s_b["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)
    np.testing.assert_allclose(m_a.series("test_acc"),
                               m_b.series("test_acc"), atol=0.02)


def test_fused_requires_capable_strategy(setup):
    """Every built-in strategy carries a ``FusedEpilogue`` now, so the
    rejection path only triggers for custom strategies registered without
    one (``fused_capable`` defaults to False)."""
    from repro.core import strategies as strat_mod

    model, fd, _ = setup
    name = "_tmp_no_epilogue"
    strat_mod.register(strat_mod.Strategy(name=name))
    try:
        with pytest.raises(ValueError, match="not fused-capable"):
            make_round_fn(model, fd, FedConfig(strategy=name), fused=True)
    finally:
        del strat_mod._REGISTRY[name]
    for builtin in strat_mod.available_strategies():
        assert strat_mod.get_strategy(builtin).fused_capable


# ---------------------------------------------------------------------------
# flat raveling helpers
# ---------------------------------------------------------------------------


def test_tree_ravel_round_trip(rng):
    tree = {"a": jax.random.normal(rng, (3, 5)),
            "b": {"c": jax.random.normal(jax.random.fold_in(rng, 1), (7,)),
                  "d": jnp.ones((2, 2, 2), jnp.float32)}}
    flat, unravel = tree_ravel(tree)
    assert flat.shape == (3 * 5 + 7 + 8,)
    back = unravel(flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tree_ravel_clients_round_trip(rng):
    n = 3
    tree = {"w": jax.random.normal(rng, (n, 4, 2)),
            "b": jax.random.normal(jax.random.fold_in(rng, 1), (n, 5))}
    flat, unravel = tree_ravel_clients(tree)
    assert flat.shape == (n, 8 + 5)
    back = unravel(flat)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tree_ravel_layouts_agree(rng):
    """Per-client raveling of a broadcast tree stacks the single-tree
    raveling row-wise — the alignment contract of the fused kernel."""
    tree = {"w": jax.random.normal(rng, (4, 2)), "b": jnp.ones((3,))}
    flat, _ = tree_ravel(tree)
    stacked = tree_broadcast_clients(tree, 5)
    flat_c, _ = tree_ravel_clients(stacked)
    for i in range(5):
        np.testing.assert_array_equal(np.asarray(flat_c[i]),
                                      np.asarray(flat))


@pytest.mark.parametrize("executor,extra", [
    ("scan", {}),
    ("scan", {"use_fused": True}),
    ("scan", {"use_fused": True, "compress": "int8"}),
    ("sharded", {}),
    ("hierarchical", {"topology": "contiguous", "n_edges": 2,
                      "edge_period": 2}),
    ("async", {}),
])
def test_span_program_takes_the_dataset_as_an_argument(executor, extra):
    """Every span runner passes the federation's data into its jitted
    program as an argument: a dataset the program closed over would be
    compiled in as a constant, so its lowered text would outgrow the
    data (here > 10 MB of features)."""
    from repro.api import ExperimentSpec, Session
    spec = ExperimentSpec(dataset="gaussian", n_samples=8192, dim=512,
                          n_classes=4, n_clients=N, width=2,
                          local_steps=1, rounds=2, executor=executor,
                          **extra)
    sess = Session.from_spec(spec)
    run = sess._get_span_runner()
    sel = jnp.asarray(sess.plan.selection[:1])
    if executor == "sharded":
        args = (sess.state, sel, sess.k_active, sess._cohort[:1])
    elif executor == "async":
        args = (sess.state, sess.k_active,
                tuple(jnp.asarray(x[:1]) for x in sess._sched))
    else:
        args = (sess.state, sel, sess.k_active)
    text = run.func.lower(*args, **run.keywords).as_text()
    assert sess.data.x.nbytes > 10 * 10 ** 6
    assert len(text) < sess.data.x.nbytes // 8
