"""Vectorized-client federated engine — the paper's Algorithm 1/2/3 plus all
baselines, with every client's state stacked along a leading axis so one
jitted round function executes the whole federation (vmap local training,
mask-based skip/estimate decisions, masked-mean aggregation).

The engine is three composable layers:

* :mod:`repro.core.strategies` — the estimation strategies of paper §III as
  a pluggable registry (``fedavg``/``dropout``/``s1``/``s2``/``cc``/``ccc``/
  ``fednova`` + extensions such as ``cc_decay``); new schemes register by
  name and never touch this file.
* :mod:`repro.core.rounds` — round executors: one jitted round, a
  ``lax.scan`` span runner (eval-free spans run as ONE program), and the
  fused Pallas fast path over flat (N, P) params.
* this module — the legacy host-side driver (:func:`run_federated`, now a
  back-compat shim over :class:`repro.api.Session`), Fig.-2 probes and the
  Appendix-A cost accounting (:func:`cost_report`).

Algorithm variants (Appendix A) are numerically identical by construction;
``variant`` ∈ {client, server, mixed} drives the storage/communication cost
accounting (:func:`cost_report`) and which side of the simulation holds Δ.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.evaluation import evaluate  # noqa: F401  (re-exported)
from repro.core.rounds import (  # noqa: F401  (re-exported public API)
    FedConfig,
    _bind,
    _local_train,
    init_fed_state,
    make_round_body,
    make_round_fn,
    make_sharded_span_runner,
    make_span_runner,
    span_boundaries,
)
from repro.core.schedules import Plan
from repro.core.strategies import available_strategies, get_strategy
from repro.data.federated import FederatedData
from repro.models.simple import Classifier
from repro.utils.logging import MetricLogger
from repro.utils.pytree import PyTree, tree_add, tree_sub

#: registered strategy names (kept as a module constant for back-compat;
#: the registry in :mod:`repro.core.strategies` is the source of truth)
STRATEGIES = available_strategies()


def make_probe_fn(model: Classifier, data: FederatedData, fed: FedConfig,
                  client: int):
    """Fig. 2 instrumentation: distance between the estimated local models
    (Strategies 2/3) and the true locally-trained model for one client."""
    from repro.utils.pytree import tree_euclidean, tree_cosine

    @jax.jit
    def probe(state, key, data, base):
        cx = data.x[client]
        cy = data.y[client]
        sz = data.sizes[client]
        true_local = _local_train(model._replace(base=base),
                                  state["params"], key, cx, cy, sz,
                                  fed.local_steps,
                                  jnp.asarray(fed.local_steps),
                                  fed.batch_size, fed.lr)
        true_delta = tree_sub(true_local, state["params"])
        est3 = jax.tree.map(lambda d: d[client], state["deltas"])
        prev = jax.tree.map(lambda p: p[client], state["prev_local"])
        est2_model = prev
        est3_model = tree_add(state["params"], est3)
        s2_delta = tree_sub(prev, state["params"])
        return {
            "euclid_s2": tree_euclidean(true_local, est2_model),
            "euclid_s3": tree_euclidean(true_local, est3_model),
            "cos_s2": tree_cosine(true_delta, s2_delta),
            "cos_s3": tree_cosine(true_delta, est3),
        }

    return _bind(probe, data=data, base=model.base)


def run_federated(model: Classifier, data: FederatedData, fed: FedConfig,
                  plan: Plan, *, x_test, y_test, eval_every: int = 10,
                  probe_client: int | None = None,
                  verbose: bool = False, executor: str = "scan",
                  use_fused: bool = False) -> tuple[PyTree, MetricLogger]:
    """Run the whole federation per ``plan``; returns final state + metrics.

    .. deprecated::
        ``run_federated`` is now a thin back-compat shim over the
        experiment API — prefer :class:`repro.api.Session` (stepwise,
        resumable) and :class:`repro.api.ExperimentSpec` (declarative,
        serializable). Return values and metric streams are identical
        (pinned by ``tests/test_api.py``).

    ``executor`` selects how eval-free spans execute: ``"scan"`` (default)
    runs each span as one jitted ``lax.scan``; ``"python"`` is the classic
    one-dispatch-per-round loop; ``"sharded"`` shard_maps each round's
    cohort over the client mesh (all numerically interchangeable — see
    ``tests/test_executor_matrix.py``). Per-round probing forces the
    python loop. ``use_fused`` routes rounds through the fused Pallas
    kernel (only for ``fused_capable`` strategies such as ``cc``).
    """
    from repro.api.callbacks import ProbeCallback, VerboseLogger
    from repro.api.session import Session

    callbacks = []
    if probe_client is not None:
        callbacks.append(ProbeCallback(probe_client))
    if verbose:
        callbacks.append(VerboseLogger())
    session = Session(model, data, fed, plan, x_test=x_test, y_test=y_test,
                      eval_every=eval_every, executor=executor,
                      use_fused=use_fused, callbacks=callbacks)
    session.run()
    return session.state, session.metrics


def cost_report(plan: Plan, model_bytes: int, variant: str = "client",
                mixed_client_frac: float = 0.5) -> dict:
    """Appendix-A accounting from a static plan's tables (see
    :func:`cost_report_from_counts` for the count-based core — sessions
    running a *runtime* budget policy account from their realized ledger
    instead, since the plan's training table never executed)."""
    trained = int((plan.selection & plan.training).sum())
    estimated = int((plan.selection & ~plan.training).sum())
    return cost_report_from_counts(
        trained, estimated, plan.n_clients, model_bytes, variant=variant,
        mixed_client_frac=mixed_client_frac,
        per_client=plan.compute_fraction(per_client=True))


def cost_report_from_counts(trained: int, estimated: int, n: int,
                            model_bytes: int, variant: str = "client",
                            mixed_client_frac: float = 0.5,
                            per_client=None) -> dict:
    """Appendix-A accounting from raw train/estimate round counts.

    ``trained``/``estimated`` are federation-wide counts of sel∧train and
    sel∧¬train client-rounds; ``per_client`` the (N,) trained-when-selected
    fractions. Works identically for precompiled plans and realized
    ledgers.
    """
    if variant == "client":        # Alg. 1
        up = (trained + estimated) * model_bytes
        client_store = model_bytes          # each client keeps its Δ
        server_store = 0
    elif variant == "server":      # Alg. 2
        up = trained * model_bytes + estimated // 8 + 1
        client_store = 0
        server_store = n * model_bytes
    elif variant == "mixed":       # Alg. 3
        c = mixed_client_frac
        up = int(trained * model_bytes
                 + estimated * c * model_bytes + estimated * (1 - c) / 8)
        client_store = model_bytes
        server_store = int((1 - c) * n * model_bytes)
    else:
        raise ValueError(variant)
    grad_steps_saved = 1.0 - trained / max(1, trained + estimated)
    if per_client is None:
        per_client = []
    return {
        "upload_bytes": int(up),
        "client_storage_bytes": int(client_store),
        "server_storage_bytes": int(server_store),
        "compute_saved_frac": grad_steps_saved,
        # per-client breakdown: how much of its FedAvg(full) work each
        # client actually performed (the scalar hides exactly the
        # heterogeneity the budget law creates)
        "compute_frac_per_client": [float(v) for v in per_client],
    }
