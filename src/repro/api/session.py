"""Stepwise, resumable federated sessions.

A :class:`Session` owns one federated run: the materialized model/data/
plan, the full round state, the metric history and the eval cadence. It
wraps the executors of :mod:`repro.core.rounds` — per-round jit,
``lax.scan`` spans (``use_fused=True`` routes rounds through the Pallas
kernel), ``executor="sharded"`` spans that ``shard_map`` each round's
sampled cohort over the client mesh, or ``executor="async"`` spans that
replay a precomputed arrival schedule through the staleness-tolerant
buffered executor (:mod:`repro.core.async_rounds`) — behind
``run(n_rounds)`` / ``step()`` / ``eval()`` / ``save()`` / ``restore()``.

Determinism contract (pinned by ``tests/test_api.py``):

* a Session run and the legacy ``run_federated`` produce identical final
  params and metric streams;
* ``save()`` checkpoints the FULL state (params, Δ history, stale local
  models, RNG key, round counter, metrics — plus the budget-policy rows,
  simulated device state and energy ledger), so a killed run restored with
  :meth:`Session.restore_from` continues bit-identically — evaluation
  points follow the *absolute* round cadence, never the resume point.

Every session runs the budget-policy engine (:mod:`repro.core.budget`):
train/estimate decisions happen inside the traced round loop against
simulated device state (:mod:`repro.system.devices`). A session built
without an explicit ``policy`` replays its plan's training table through
``PrecompiledPolicy`` — bit-for-bit the legacy static-plan behaviour.

Tracing (:mod:`repro.utils.trace`): ``run`` is a ``fed.run`` host span,
each span-runner or round-fn call a ``fed.dispatch``, each evaluation a
``fed.eval`` and each firing of callback hooks a ``fed.callbacks``; they
show in a ``jax.profiler`` trace and cost about a microsecond without
one. ``counters`` is worked out from the round count and the round
carry's ledger, so reading it waits for the device; nothing in a run
reads it.
"""
from __future__ import annotations

from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.callbacks import Callback
from repro.checkpoint.store import CheckpointManager
from repro.core.async_rounds import AsyncConfig, make_async_span_runner
from repro.core.budget import PrecompiledPolicy
from repro.core.evaluation import evaluate
from repro.core.rounds import (EXECUTORS, FedConfig, init_fed_state,
                               make_hierarchical_span_runner,
                               make_policy_round_fn,
                               make_policy_span_runner,
                               make_sharded_span_runner, span_boundaries)
from repro.core.schedules import Plan, fednova_local_steps
from repro.data.federated import CohortSampler, FederatedData
from repro.models.simple import Classifier
from repro.system.devices import make_profile, simulate_arrivals
from repro.utils.logging import MetricLogger
from repro.utils.pytree import PyTree, tree_bytes
from repro.utils.trace import (CALLBACKS, DISPATCH, EVAL,
                               LOCAL_SGD_CLIENT_ROUNDS, RUN, span)


def plan_k_active(data: FederatedData, fed: FedConfig,
                  plan: Plan) -> jax.Array:
    """Per-client local-step counts: FedNova spends its budget as fewer
    iterations every round; everyone else runs the full K."""
    if fed.strategy == "fednova":
        k_active_all = fednova_local_steps(plan.p, fed.local_steps)
    else:
        k_active_all = np.full(data.n_clients, fed.local_steps, np.int32)
    return jnp.asarray(k_active_all)


class Session:
    """One federated run with explicit control over its lifecycle."""

    def __init__(self, model: Classifier, data: FederatedData,
                 fed: FedConfig, plan: Plan, *, x_test=None, y_test=None,
                 eval_every: int = 10, executor: str = "scan",
                 use_fused: bool = False,
                 callbacks: Iterable[Callback] = (),
                 ckpt_dir: str | None = None, keep: int = 3,
                 spec=None, policy=None, profile=None, topology=None,
                 async_cfg=None):
        if executor not in EXECUTORS:
            raise ValueError(f"unknown executor {executor!r}; "
                             f"available: {EXECUTORS}")
        if executor in ("sharded", "hierarchical", "async") and use_fused:
            raise ValueError(f"use_fused is not supported by the "
                             f"{executor} executor; pick one fast path")
        if async_cfg is not None and executor != "async":
            raise ValueError("async_cfg requires executor='async' (only "
                             "the async executor runs the arrival process)")
        if executor == "async" and async_cfg is None:
            async_cfg = AsyncConfig()
        if (executor == "hierarchical") != (topology is not None):
            raise ValueError(
                "the hierarchical executor needs an EdgeTopology (pass "
                "topology=...), and a topology needs "
                "executor='hierarchical'")
        if eval_every < 1:
            raise ValueError(f"eval_every must be >= 1, got {eval_every}")
        if fed.compress == "int8" and not use_fused:
            raise ValueError(
                "compress='int8' carries the Δ history in the fused "
                "kernels' flat int8 layout, which only the fused executor "
                "consumes; pass use_fused=True (executor 'scan' or "
                "'python'), or compress='none'")
        if (policy is None) != (profile is None):
            raise ValueError("pass policy and profile together (or neither "
                             "for the plan-replaying default)")
        if policy is None:
            # every session runs the budget-policy engine; a bare plan is
            # replayed bit-for-bit through PrecompiledPolicy over a
            # budget-shaped device profile
            policy = PrecompiledPolicy.from_plan(plan)
            profile = make_profile("budget", plan.p, seed=fed.seed)
        self.model = model
        self.data = data
        self.fed = fed
        self.plan = plan
        self.policy = policy
        self.profile = profile
        self.topology = topology
        self.async_cfg = async_cfg
        self.x_test = x_test
        self.y_test = y_test
        self.eval_every = eval_every
        self.executor = executor
        self.use_fused = use_fused
        self.callbacks: list[Callback] = list(callbacks)
        self.spec = spec
        self.metrics = MetricLogger()
        self.k_active = plan_k_active(data, fed, plan)
        self.state: PyTree = init_fed_state(jax.random.PRNGKey(fed.seed),
                                            model, data.n_clients,
                                            policy=policy, profile=profile,
                                            topology=topology,
                                            compress=fed.compress,
                                            async_cfg=async_cfg,
                                            needs_stale=fed.resolve()
                                            .needs_stale,
                                            strategy=fed.resolve())
        self._t = 0                              # completed rounds
        # the round and the ledger's trained client-rounds when counting
        # began (construction or the last restore): ``counters`` and
        # ``summary`` leave out what came before
        self._count_base = (0, 0)
        self._sel = jnp.asarray(plan.selection)
        self._cohort = None
        self._sched = None
        if executor == "async":
            # the arrival process is precomputed host-side from the device
            # profile (load dynamics never depend on training decisions),
            # keyed by absolute round — a resumed session replays the same
            # dispatch/delivery/merge events
            sel_np = np.asarray(plan.selection)
            if fed.cohort_size is not None:
                if fed.cohort_size < async_cfg.buffer_size:
                    raise ValueError(
                        f"cohort_size={fed.cohort_size} < async_buffer="
                        f"{async_cfg.buffer_size} can never fill the merge "
                        "buffer — the merge loop deadlocks; raise "
                        "cohort_size or lower async_buffer")
                # absolute-round-keyed cohort thinning: only sampled
                # cohort members may dispatch each round (same sampler
                # contract as the sharded executor, so a resumed session
                # replays the identical arrival stream)
                sampler = CohortSampler(data.n_clients, fed.cohort_size,
                                        seed=fed.seed)
                idx = np.asarray(sampler.indices(plan.rounds))
                member = np.zeros(sel_np.shape, dtype=bool)
                np.put_along_axis(member, idx, True, axis=1)
                sel_np = sel_np & member
            self._sched = simulate_arrivals(
                profile, sel_np,
                buffer_size=async_cfg.buffer_size,
                latency=async_cfg.latency, jitter=async_cfg.jitter)
        if executor == "sharded":
            # absolute-round-keyed cohorts: resumed sessions sample the
            # same participants, mirroring the plan-mask contract
            sampler = CohortSampler(data.n_clients,
                                    fed.cohort_size or data.n_clients,
                                    seed=fed.seed)
            self._cohort = jnp.asarray(sampler.indices(plan.rounds))
        self._round_fn = None
        self._span_runner = None
        self._mgr = (CheckpointManager(ckpt_dir, keep=keep)
                     if ckpt_dir else None)

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def from_spec(cls, spec, *, callbacks: Iterable[Callback] = (),
                  ckpt_dir: str | None = None, keep: int = 3) -> "Session":
        """Materialize an :class:`~repro.api.spec.ExperimentSpec`."""
        b = spec.build()
        return cls(b.model, b.data, b.fed, b.plan, x_test=b.x_test,
                   y_test=b.y_test, eval_every=spec.eval_every,
                   executor=spec.executor, use_fused=spec.use_fused,
                   callbacks=callbacks, ckpt_dir=ckpt_dir, keep=keep,
                   spec=spec, policy=b.policy, profile=b.profile,
                   topology=b.topology, async_cfg=b.async_cfg)

    @classmethod
    def restore_from(cls, ckpt_dir: str, *, step: int | None = None,
                     callbacks: Iterable[Callback] = ()) -> "Session":
        """Rebuild a session purely from a checkpoint directory: the spec
        stored in the checkpoint reconstructs data/model/plan, then the
        full state and metric history are restored."""
        from repro.api.spec import ExperimentSpec
        mgr = CheckpointManager(ckpt_dir)
        extra = mgr.read_extra(step)
        if not extra.get("spec"):
            raise ValueError(
                f"checkpoint in {ckpt_dir!r} carries no spec; restore it "
                "through a Session constructed from the original objects")
        spec = ExperimentSpec.from_dict(extra["spec"])
        sess = cls.from_spec(spec, callbacks=callbacks, ckpt_dir=ckpt_dir)
        sess.restore(step=step)
        return sess

    # ------------------------------------------------------------------
    # execution
    # ------------------------------------------------------------------

    @property
    def t(self) -> int:
        """Completed rounds (== ``int(state['round'])``)."""
        return self._t

    @property
    def done(self) -> bool:
        return self._t >= self.plan.rounds

    def _get_round_fn(self):
        if self._round_fn is None:
            self._round_fn = make_policy_round_fn(
                self.model, self.data, self.fed, self.policy, self.profile,
                fused=self.use_fused)
        return self._round_fn

    def _get_span_runner(self):
        if self._span_runner is None:
            if self.executor == "sharded":
                self._span_runner = make_sharded_span_runner(
                    self.model, self.data, self.fed, policy=self.policy,
                    profile=self.profile)
            elif self.executor == "hierarchical":
                self._span_runner = make_hierarchical_span_runner(
                    self.model, self.data, self.fed, self.topology,
                    policy=self.policy, profile=self.profile)
            elif self.executor == "async":
                self._span_runner = make_async_span_runner(
                    self.model, self.data, self.fed, self.async_cfg,
                    policy=self.policy, profile=self.profile)
            else:
                self._span_runner = make_policy_span_runner(
                    self.model, self.data, self.fed, self.policy,
                    self.profile, fused=self.use_fused)
        return self._span_runner

    def _advance_span(self, stop: int) -> None:
        """Run rounds ``self._t .. stop`` as one span with the configured
        span runner (the sharded runner additionally takes its cohort
        table slice). Training decisions are made in-trace by the budget
        policy; only the selection masks are staged."""
        t, run_span = self._t, self._get_span_runner()
        with span(DISPATCH):
            if self.executor == "sharded":
                self.state = run_span(self.state, self._sel[t:stop],
                                      self.k_active, self._cohort[t:stop])
            elif self.executor == "async":
                sched = tuple(jnp.asarray(x[t:stop]) for x in self._sched)
                self.state = run_span(self.state, self.k_active, sched)
            else:
                self.state = run_span(self.state, self._sel[t:stop],
                                      self.k_active)
        self._t = stop

    def step(self) -> PyTree:
        """Advance exactly one round (per-round executor; the sharded and
        hierarchical executors run a one-round span so cohort sampling /
        edge-tier state still apply) and fire ``on_round_end``. Evaluation
        stays on the absolute cadence and is driven by :meth:`run`; a bare
        ``step()`` never records metrics."""
        t = self._t
        if t >= self.plan.rounds:
            raise RuntimeError(
                f"plan exhausted: {t}/{self.plan.rounds} rounds done")
        if self.executor in ("sharded", "hierarchical", "async"):
            self._advance_span(t + 1)
        else:
            round_fn = self._get_round_fn()
            with span(DISPATCH):
                self.state = round_fn(self.state, self._sel[t],
                                      self.k_active)
            self._t = t + 1
        self._fire("on_round_end", self._t)
        return self.state

    def _fire(self, hook: str, *args) -> None:
        """Call ``hook`` of every callback, in one ``fed.callbacks``
        span."""
        with span(CALLBACKS):
            for cb in self.callbacks:
                getattr(cb, hook)(self, *args)

    def _eval_due(self, t: int) -> bool:
        return t % self.eval_every == 0 or t == self.plan.rounds

    def _run_eval(self) -> float:
        with span(EVAL):
            acc = self.eval()
        self.metrics.record(self._t, test_acc=acc)
        self._fire("on_eval", self._t, acc)
        return acc

    def run(self, n_rounds: int | None = None) -> "Session":
        """Advance ``n_rounds`` (default: to the end of the plan),
        evaluating on the absolute ``eval_every`` cadence plus the final
        plan round. Uses the scan executor between host-sync points unless
        ``executor='python'`` or a callback needs the per-round loop."""
        with span(RUN):
            return self._run(n_rounds)

    def _run(self, n_rounds: int | None) -> "Session":
        total = self.plan.rounds
        target = (total if n_rounds is None
                  else min(total, self._t + n_rounds))
        if target <= self._t:               # nothing to do; never re-fires
            return self                     # hooks or re-records an eval
        per_round_cbs = any(cb.needs_python_loop for cb in self.callbacks)
        # the sharded/hierarchical/async executors have no python-loop
        # fallback (it would drop cohort sampling / the edge tier / the
        # arrival buffer); per-round callbacks split their spans instead
        needs_python = (self.executor == "python"
                        or (per_round_cbs and self.executor
                            not in ("sharded", "hierarchical", "async")))
        if needs_python:
            while self._t < target:
                self.step()
                if self._eval_due(self._t):
                    self._run_eval()
            return self

        eval_stops = set(span_boundaries(total, self.eval_every))
        stops = set(eval_stops)
        for cb in self.callbacks:
            if cb.sync_every:
                stops.update(range(cb.sync_every, total + 1, cb.sync_every))
        if per_round_cbs:                   # sharded + per-round callbacks
            stops.update(range(self._t + 1, target + 1))
        stops = sorted(s for s in stops if self._t < s <= target)
        if not stops or stops[-1] != target:
            stops.append(target)
        for stop in stops:
            if stop > self._t:
                self._advance_span(stop)
            self._fire("on_round_end", self._t)
            if self._t in eval_stops:
                self._run_eval()
        return self

    def eval(self) -> float:
        """Test-set accuracy of the current global model (no recording)."""
        if self.x_test is None or self.y_test is None:
            raise ValueError("session has no test set; pass x_test/y_test")
        return evaluate(self.model, self.state["params"],
                        self.x_test, self.y_test)

    # ------------------------------------------------------------------
    # persistence
    # ------------------------------------------------------------------

    def save(self, ckpt_dir: str | None = None) -> str:
        """Checkpoint the full federated state + metrics + spec; the file
        alone suffices for :meth:`restore_from` to continue the run."""
        mgr = self._require_mgr(ckpt_dir)
        extra = {
            "round": self._t,
            "metrics": self.metrics.history,
            "spec": self.spec.to_dict() if self.spec is not None else None,
        }
        path = mgr.save_fed(self._t, self.state, extra=extra)
        self._fire("on_checkpoint", self._t, path)
        return path

    def restore(self, step: int | None = None,
                ckpt_dir: str | None = None) -> "Session":
        """Restore full state + metric history from a checkpoint written
        by :meth:`save` (in-place; session config must match)."""
        mgr = self._require_mgr(ckpt_dir)
        like = init_fed_state(jax.random.PRNGKey(self.fed.seed),
                              self.model, self.data.n_clients,
                              policy=self.policy, profile=self.profile,
                              topology=self.topology,
                              compress=self.fed.compress,
                              async_cfg=self.async_cfg,
                              needs_stale=self.fed.resolve().needs_stale,
                              strategy=self.fed.resolve())
        state, extra = mgr.restore(like, step=step)
        self.state = state
        self._t = int(extra.get("round", extra.get("step", 0)))
        history = extra.get("metrics") or {}
        self.metrics = MetricLogger(history={
            k: [(int(s), float(v)) for s, v in series]
            for k, series in history.items()})
        self._count_base = (self._t, self._trained())
        return self

    def _require_mgr(self, ckpt_dir: str | None) -> CheckpointManager:
        if ckpt_dir is not None:
            self._mgr = CheckpointManager(ckpt_dir)
        if self._mgr is None:
            raise ValueError("no checkpoint directory: pass ckpt_dir to the "
                             "Session or to save()/restore()")
        return self._mgr

    # ------------------------------------------------------------------
    # reporting
    # ------------------------------------------------------------------

    def cost_report(self, variant: str | None = None,
                    mixed_client_frac: float = 0.5) -> dict:
        """Appendix-A storage/upload accounting from the REALIZED ledger —
        the train/estimate decisions the policy actually made, not the
        static plan's table (for ``PrecompiledPolicy`` over a fully-run
        plan the two coincide; for runtime policies only the ledger is
        truthful).

        Every report carries the int8-quantized upload figure
        (:mod:`repro.core.compress`). With ``compress="none"`` it is
        *accounted* (``upload_bytes / 4``, the what-if estimate); with
        ``compress="int8"`` it is *measured* from the carried wire format —
        tile-padded int8 payload rows plus one f32 scale per upload —
        flagged by ``upload_bytes_int8_measured``. Two-tier sessions
        additionally break uploads down per hop under ``"tiers"`` —
        client→edge bytes every decided round vs edge→server bytes only on
        the ``edge_period``-boundary syncs.

        Async sessions account uploads per REALIZED arrival: the ledger
        books each dispatched update exactly once, at the round its
        delivery lands on the server (a stale update in flight for s
        rounds is still one upload), so ``upload_rounds`` = arrivals so
        far — in-flight work is not yet an upload. The report then also
        carries the raw ``arrivals``/``merges`` counters."""
        from repro.core.compress import (BYTES_PER_PARAM_F32,
                                         tier_upload_report)
        from repro.core.engine import cost_report_from_counts
        led = self.ledger()
        decided = led["train_rounds"] + led["est_rounds"]
        per_client = led["train_rounds"] / np.maximum(1, decided)
        model_bytes = tree_bytes(self.state["params"])
        rep = cost_report_from_counts(
            int(led["train_rounds"].sum()), int(led["est_rounds"].sum()),
            self.data.n_clients, model_bytes,
            variant=variant or self.fed.variant,
            mixed_client_frac=mixed_client_frac, per_client=per_client)
        if self.fed.compress == "int8":
            q = self.state["deltas"]
            wire_bytes = (q["payload"].shape[1] * q["payload"].dtype.itemsize
                          + q["scales"].dtype.itemsize)
            rep["upload_bytes_int8"] = int(
                rep["upload_bytes"] / model_bytes * wire_bytes)
            rep["upload_bytes_int8_measured"] = True
        else:
            rep["upload_bytes_int8"] = (rep["upload_bytes"]
                                        // BYTES_PER_PARAM_F32)
            rep["upload_bytes_int8_measured"] = False
        if self.topology is not None:
            rep["tiers"] = tier_upload_report(
                client_upload_bytes=rep["upload_bytes"],
                n_syncs=self.topology.sync_count(self._t),
                n_edges=self.topology.n_edges, model_bytes=model_bytes)
        if "async" in self.state:
            stats = self.state["async"]["stats"]
            rep["arrivals"] = int(stats["arrivals"])
            rep["merges"] = int(stats["merges"])
        return rep

    def staleness_summary(self) -> dict:
        """Arrival/staleness statistics of an async session's ledger-side
        counters (carried in the round state, so they survive a resume):
        realized arrivals and merges, mean/max staleness over all arrivals,
        mean buffer occupancy at merge time, and the updates currently
        buffered awaiting the next merge."""
        if "async" not in self.state:
            raise ValueError("staleness_summary() needs executor='async' "
                             "(synchronous executors have no arrival "
                             "process)")
        a = self.state["async"]
        s = a["stats"]
        arrivals = int(s["arrivals"])
        merges = int(s["merges"])
        return {
            "arrivals": arrivals,
            "merges": merges,
            "mean_staleness": float(s["stale_sum"]) / max(1, arrivals),
            "max_staleness": int(s["stale_max"]),
            "mean_buffer_occupancy":
                int(s["occupancy_sum"]) / max(1, merges),
            "pending_now": int(np.asarray(a["pending_mask"]).sum()),
        }

    def ledger(self) -> dict:
        """Per-client energy/cost books accumulated in the round carry:
        ``energy_spent`` / ``train_rounds`` / ``est_rounds`` numpy arrays
        (checkpointed with the state, so they survive a resume)."""
        return {k: np.asarray(v) for k, v in self.state["ledger"].items()}

    def _trained(self) -> int:
        return int(self.ledger()["train_rounds"].sum())

    @property
    def counters(self) -> dict:
        """The counters (:data:`repro.utils.trace.COUNTERS`) since
        construction or the last restore: ``local_sgd_client_rounds`` is
        the client-rounds of local SGD the executor ran. The flat
        executors train only each round's trainers, so it is the ledger's
        trained client-rounds; the hierarchical and async executors train
        every client, rounds × N. Reading it waits for the device."""
        t0, trained0 = self._count_base
        if self.executor in ("hierarchical", "async"):
            ran = (self._t - t0) * self.data.n_clients
        else:
            ran = self._trained() - trained0
        return {LOCAL_SGD_CLIENT_ROUNDS: ran}

    def summary(self) -> dict:
        out = {"rounds_done": self._t, "strategy": self.fed.strategy,
               "policy": self.policy.name}
        if "test_acc" in self.metrics.history:
            out["test_acc"] = self.metrics.last("test_acc")
            out["test_acc_best"] = self.metrics.best("test_acc")
        led = self.ledger()
        decided = int(led["train_rounds"].sum() + led["est_rounds"].sum())
        out["train_fraction"] = (
            float(led["train_rounds"].sum()) / max(1, decided))
        out["energy_spent"] = float(led["energy_spent"].sum())
        # the share of the local SGD run since counting began whose result
        # was kept: below 1 where an executor trains clients that estimate
        ran = self.counters[LOCAL_SGD_CLIENT_ROUNDS]
        if ran:
            out["local_sgd_useful_share"] = (
                float(led["train_rounds"].sum()) - self._count_base[1]) / ran
        return out
