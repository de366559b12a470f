"""Local SGD compacted to the round's trainers.

``rounds._train_cohort`` trains only the lanes whose ``train_mask`` is
set, one trainer per trip of a loop bounded by their count. Each test runs
the same inputs through it and through the full-width reference, a vmap
that trains every lane: the trainers' local models match, the rows that do
not train hold the broadcast model, and the round's whole output state
matches, for every executor that compacts (python, scan, fused f32, fused
int8, and sharded on 4 virtual devices, in a process of its own), in mask
mode and in policy mode.

Run as a script (``python tests/test_trainer_compaction.py``) the file
prints the sharded cases' gaps as JSON; the sharded tests start it with
``--xla_force_host_platform_device_count=4``.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, Session
from repro.core import rounds
from repro.core.rounds import (FedConfig, _round_keys, _train_clients,
                               _train_cohort, init_fed_state, make_round_fn,
                               make_sharded_span_runner, make_span_runner)
from repro.data.federated import build_federated
from repro.data.partition import partition_gamma
from repro.data.synthetic import make_dataset, train_test_split
from repro.models.simple import make_classifier
from repro.utils import trace
from repro.utils.pytree import tree_broadcast_clients

N = 8
STRATEGIES = ("cc", "s2", "ccc", "fednova", "fedprox", "feddyn")
ALL = [1] * N
#: (selection, training) per case
CASES = {
    "none": (ALL, [0, 0, 0, 0, 0, 0, 0, 0]),
    "one": (ALL, [0, 0, 0, 0, 0, 1, 0, 0]),
    # round 1 of the benchmark's cc_power traffic: 5 of 8 clients train
    "cc_power_round1": (ALL, [1, 1, 0, 1, 0, 1, 0, 1]),
    "all": (ALL, ALL),
    # as policy mode leaves it: trainers among the selected, some selected
    # clients estimate, and two clients are not selected
    "partial_selection": ([1, 1, 1, 0, 1, 0, 1, 1],
                          [1, 0, 1, 0, 0, 0, 1, 0]),
}
EXECUTORS = ("python", "scan", "fused", "fused_q8")
PATHS = ("vmap", "loop")
#: per-client step counts, so that each trainer must read its own
K_ACTIVE = [2, 1, 2, 2, 1, 2, 1, 2]
TOL = dict(rtol=1e-6, atol=1e-6)


def _vmap_cohort(model, fed, params, keys, cx, cy, sizes, k_active,
                 train_mask, prox=0.0, dual=None, axis_name=None):
    """The full-width reference for ``rounds._train_cohort``: one vmap
    trains every lane of the cohort, trainer or not."""
    broadcast = tree_broadcast_clients(params, sizes.shape[0])
    if axis_name is not None:
        broadcast = jax.lax.pcast(broadcast, axis_name, to="varying")
    return broadcast, _train_clients(model, fed, broadcast, keys, cx, cy,
                                     sizes, k_active, prox, dual)


def _use(path: str, monkeypatch=None):
    """Put ``path``'s local training in place for whatever is traced
    next."""
    fn = _vmap_cohort if path == "vmap" else _train_cohort
    if monkeypatch is None:
        rounds._train_cohort = fn
    else:
        monkeypatch.setattr(rounds, "_train_cohort", fn)


def _fed(strategy: str, executor: str = "scan") -> FedConfig:
    return FedConfig(strategy=strategy, local_steps=2, batch_size=16,
                     lr=0.1, prox_mu=0.1 if strategy == "fedprox" else 0.0,
                     feddyn_alpha=0.1 if strategy == "feddyn" else 0.0,
                     compress="int8" if executor == "fused_q8" else "none")


def _setup():
    ds = make_dataset("gaussian", n=512, dim=8, n_classes=4, seed=0)
    tr, _ = train_test_split(ds)
    fd = build_federated(tr, partition_gamma(tr, N, gamma=0.5, seed=0))
    model = make_classifier("mlp", input_shape=(8,), n_classes=4, width=4)
    return model, fd


def _warm_state(model, fd, fed):
    """The state after one round in which half the clients trained, so
    that the Δ history, stale models and FedDyn's dual rows differ by
    client."""
    strategy = fed.resolve()
    state = init_fed_state(jax.random.PRNGKey(0), model, N,
                           strategy=strategy, compress=fed.compress,
                           needs_stale=strategy.needs_stale)
    warm = jnp.asarray([1, 0, 1, 0, 1, 0, 1, 0], bool)
    run = make_round_fn(model, fd, fed, fused=fed.compress == "int8")
    return run(state, jnp.ones((N,), bool), warm,
               jnp.asarray(K_ACTIVE, jnp.int32))


_RUNNERS: dict = {}


def _round(executor: str, path: str, model, fd, fed, state, case):
    """One round on ``path``; each runner is traced on its first call,
    with that path in place, and kept for the other cases."""
    key = (executor, fed.strategy, path)
    if key not in _RUNNERS:
        if executor == "python":
            run = make_round_fn(model, fd, fed)
        else:
            span = make_span_runner(model, fd, fed,
                                    fused=executor.startswith("fused"))
            run = (lambda st, s, m, k, span=span:
                   span(st, s[None], m[None], k))
        _RUNNERS[key] = run
    sel, train = (jnp.asarray(b, bool) for b in CASES[case])
    return _RUNNERS[key](state, sel, train, jnp.asarray(K_ACTIVE, jnp.int32))


def _gaps(a, b) -> float:
    """Largest elementwise gap over two trees, scaled as ``TOL`` is."""
    worst = 0.0
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        x, y = np.asarray(x, np.float64), np.asarray(y, np.float64)
        gap = np.abs(x - y) / (TOL["atol"] + TOL["rtol"] * np.abs(y))
        worst = max(worst, float(gap.max(initial=0.0)))
    return worst


@pytest.fixture(scope="module")
def setup():
    return _setup()


_STATES: dict = {}


def _state(setup, strategy, executor="scan"):
    fed = _fed(strategy, executor)
    if (strategy, fed.compress) not in _STATES:
        _STATES[strategy, fed.compress] = _warm_state(*setup, fed)
    return _STATES[strategy, fed.compress]


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_trainers_rows_match_and_the_rest_keep_the_broadcast(
        setup, strategy, case):
    model, fd = setup
    fed = _fed(strategy)
    strat = fed.resolve()
    state = _state(setup, strategy)
    train = np.asarray(CASES[case][1], bool)
    _, keys = _round_keys(state["key"], N)
    out = {}
    for path, fn in (("vmap", _vmap_cohort), ("loop", _train_cohort)):
        out[path] = jax.jit(lambda m, fn=fn: fn(
            model, fed, state["params"], keys, fd.x, fd.y, fd.sizes,
            jnp.asarray(K_ACTIVE, jnp.int32), m, prox=strat.prox_coeff(),
            dual=strat.local_dual(state)))(jnp.asarray(train))
    (_, l_all), (b_cmp, l_cmp) = out["vmap"], out["loop"]
    for full, comp, bc in zip(jax.tree.leaves(l_all),
                              jax.tree.leaves(l_cmp),
                              jax.tree.leaves(b_cmp)):
        full, comp, bc = map(np.asarray, (full, comp, bc))
        np.testing.assert_allclose(comp[train], full[train], **TOL)
        np.testing.assert_array_equal(comp[~train], bc[~train])


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("executor", EXECUTORS)
def test_round_state_matches_the_full_width_vmap(
        setup, monkeypatch, executor, strategy, case):
    model, fd = setup
    fed = _fed(strategy, executor)
    state = _state(setup, strategy, executor)
    out = {}
    for path in PATHS:
        _use(path, monkeypatch)
        out[path] = _round(executor, path, model, fd, fed, state, case)
    full, comp = out["vmap"], out["loop"]
    assert set(full) == set(comp)
    for key in full:
        assert _gaps(comp[key], full[key]) <= 1.0, key


def _session(executor: str) -> Session:
    extra = {"cohort_size": 4} if executor == "sharded" else {}
    return Session.from_spec(ExperimentSpec(
        dataset="gaussian", n_samples=256, dim=8, n_classes=4, n_clients=N,
        width=2, strategy="feddyn", feddyn_alpha=0.1, local_steps=2,
        batch_size=8, rounds=4, eval_every=2, executor=executor, **extra))


@pytest.mark.parametrize("executor", ("python", "scan", "sharded"))
def test_policy_session_matches_the_full_width_vmap(monkeypatch, executor):
    """Policy mode, which every Session runs, decides in the trace and
    ANDs the training mask with the selection; the sharded runner scatters
    each cohort's decisions back. The loop gives the vmap's state and
    ledger, and the counter books only the trainers."""
    out = {}
    for path in PATHS:
        _use(path, monkeypatch)
        sess = _session(executor)
        sess.run(n_rounds=3)
        out[path] = sess
    full, comp = out["vmap"], out["loop"]
    for key in ("energy_spent", "train_rounds", "est_rounds"):
        np.testing.assert_array_equal(comp.ledger()[key],
                                      full.ledger()[key])
    for key in full.state:
        assert _gaps(comp.state[key], full.state[key]) <= 1.0, key
    trained = int(comp.ledger()["train_rounds"].sum())
    assert 0 < trained < 3 * N
    assert comp.counters == {trace.LOCAL_SGD_CLIENT_ROUNDS: trained}
    assert comp.summary()["local_sgd_useful_share"] == 1.0


# ---- sharded on 4 virtual devices, in a process of its own ----------------


def _sharded_gaps() -> dict:
    """Per (strategy, case): the largest gap between the sharded round's
    output state on the two paths (each shard compacts its own two
    lanes), and the device count the process saw."""
    model, fd = _setup()
    out = {"devices": len(jax.devices())}
    k = jnp.asarray(K_ACTIVE, jnp.int32)
    idx = jnp.arange(N, dtype=jnp.int32)[None]
    for strategy in STRATEGIES:
        fed = _fed(strategy)
        state = _warm_state(model, fd, fed)
        runs = {}
        for path in PATHS:
            _use(path)
            run = make_sharded_span_runner(model, fd, fed)
            runs[path] = {
                case: run(state, jnp.asarray(sel, bool)[None],
                          jnp.asarray(train, bool)[None], k, idx)
                for case, (sel, train) in CASES.items()}
        for case in CASES:
            full, comp = runs["vmap"][case], runs["loop"][case]
            out[f"{strategy}/{case}"] = max(
                _gaps(comp[key], full[key]) for key in full)
    return out


@pytest.fixture(scope="module")
def sharded_gaps():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = os.pathsep.join(
        [src] + [p for p in [os.environ.get("PYTHONPATH")] if p])
    done = subprocess.run([sys.executable, os.path.abspath(__file__)],
                          env=env, capture_output=True, text=True,
                          timeout=600)
    assert done.returncode == 0, done.stderr[-4000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


def test_sharded_process_sees_four_devices(sharded_gaps):
    assert sharded_gaps["devices"] == 4


@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("strategy", STRATEGIES)
def test_sharded_round_state_matches_the_full_width_vmap(
        sharded_gaps, strategy, case):
    assert sharded_gaps[f"{strategy}/{case}"] <= 1.0


if __name__ == "__main__":
    print(json.dumps(_sharded_gaps()))
