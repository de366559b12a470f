"""Asynchronous staleness-tolerant executor (FedBuff-style buffered merge).

The sixth executor (``executor="async"``): the server never blocks on a
cohort. Clients *pull* the global model when dispatched, work for a
simulated latency (:func:`repro.system.devices.simulate_arrivals` — slow
or loaded devices deliver late), and their updates arrive tagged with a
staleness counter ``s`` = rounds elapsed since the pull. Arrivals land in
a pending buffer; every K-th arrival (``buffer_size``) the server merges
the buffered cohort with staleness-decayed weights ``w(s)``
(:func:`staleness_weights`, ``γ^s`` by default) through the strategy's
:meth:`~repro.core.strategies.Strategy.merge_stale` hook — CC-FedAvg
estimation-replay semantics apply unchanged at each arrival.

The whole loop is still ONE traced ``lax.scan``: the arrival process is
precomputed host-side into (T, N) dispatch/deliver tables plus a (T,)
merge flag (valid because device load dynamics never depend on training
decisions — the same contract that lets plans precompute selection), and
each scan step trains the full federation vmapped from its per-client
pulled models, buffers the round's arrivals and conditionally flushes the
buffer. Merging via ``lax.cond`` keeps non-merge rounds aggregation-free.

Collapse guarantee (the differential oracle pinned in
``tests/test_executor_matrix.py``): with zero latency and jitter every
update delivers in its dispatch round, so at ``buffer_size=1`` each merge
is exactly one synchronous round's aggregation with staleness identically
0 and ``w(0) = 1.0`` exactly — the async executor equals the synchronous
scan executor bit-for-bit, full history and metric streams included.

The Δ history rides a :class:`repro.core.history_store.HistoryStore`:
``history_store="dense"`` keeps the plain f32 client tree;
``history_store="int8"`` carries the quantized (N, P) payload + per-row
scales and requantizes only delivered rows, so estimation replay scales
to N = 10⁵ clients without an O(N·P) f32 carry.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from repro.core.channel import TAG_MERGE, uplink_channel
from repro.core.history_store import STORE_KINDS, HistoryStore
from repro.core.rounds import (_BASE_KEYS, FedConfig, _check_profile,
                               _bind, _round_keys, _train_clients)
from repro.core.strategies import RoundCtx, masked_select
from repro.data.federated import FederatedData
from repro.models.simple import Classifier
from repro.utils.pytree import (PyTree, tree_add, tree_broadcast_clients,
                                tree_ravel_clients, tree_sub,
                                tree_zeros_like)
from repro.utils.trace import (AGGREGATE, ESTIMATE, HISTORY, LOCAL_SGD,
                               POLICY, scope)

#: staleness-decay schedules: w(s) for an arrival s rounds stale. Both are
#: exactly 1.0 at s = 0 (the collapse-to-synchronous requirement).
STALENESS_SCHEDULES = ("geometric", "polynomial")

#: the async carry key added to the round state (see ``init_async_carry``)
ASYNC_KEY = "async"

#: mask-mode state keys the policy-mode wrapper passes to the base round
_ASYNC_BASE_KEYS = _BASE_KEYS + (ASYNC_KEY,)


@dataclass(frozen=True)
class AsyncConfig:
    """Runtime knobs of the async executor (spec v5 ``async_*`` fields)."""

    #: merge every K-th arrival (FedBuff buffer size); 1 = merge on every
    #: round with arrivals
    buffer_size: int = 1
    #: γ of the staleness decay w(s) — w(1) under the geometric schedule
    staleness_decay: float = 0.9
    #: decay shape: "geometric" w(s) = γ^s, "polynomial"
    #: w(s) = 1 / (1 + (1 − γ)·s)
    schedule: str = "geometric"
    #: nominal rounds-in-flight of a unit-rate, unloaded device; the
    #: realized latency divides by flops_rate · (1 − load)
    latency: float = 0.0
    #: uniform noise amplitude added to the realized latency (rounds)
    jitter: float = 0.0
    #: Δ-history carry layout: "dense" f32 tree | "int8" quantized store
    history_store: str = "dense"

    def __post_init__(self):
        if not isinstance(self.buffer_size, int) or self.buffer_size < 1:
            raise ValueError(f"async buffer size K must be an int >= 1, "
                             f"got {self.buffer_size!r}")
        if not 0.0 < self.staleness_decay <= 1.0:
            raise ValueError(f"staleness_decay must be in (0, 1], got "
                             f"{self.staleness_decay}")
        if self.schedule not in STALENESS_SCHEDULES:
            raise ValueError(
                f"staleness schedule must be one of {STALENESS_SCHEDULES}, "
                f"got {self.schedule!r}")
        if self.latency < 0:
            raise ValueError(f"latency must be >= 0, got {self.latency}")
        if self.jitter < 0:
            raise ValueError(f"latency jitter must be >= 0, got "
                             f"{self.jitter}")
        if self.history_store not in STORE_KINDS:
            raise ValueError(f"history_store must be one of {STORE_KINDS}, "
                             f"got {self.history_store!r}")


def staleness_weights(schedule: str, decay: float,
                      staleness: jax.Array) -> jax.Array:
    """Per-client merge weights w(s) ≥ 0; w(0) == 1.0 exactly for every
    schedule, so a zero-staleness merge reduces to the synchronous
    aggregation bit-for-bit."""
    s = staleness.astype(jnp.float32)
    if schedule == "geometric":
        return jnp.power(jnp.float32(decay), s)
    if schedule == "polynomial":
        return 1.0 / (1.0 + (1.0 - decay) * s)
    raise ValueError(f"staleness schedule must be one of "
                     f"{STALENESS_SCHEDULES}, got {schedule!r}")


def init_async_carry(state: PyTree, params: PyTree, n_clients: int,
                     cfg: AsyncConfig, *,
                     needs_stale: bool = True) -> PyTree:
    """Extend a fresh federated state with the async executor's carry.

    ``state["async"]`` holds the FedBuff machinery — the in-flight pulled
    models, the per-client pull-round (staleness) counters, the pending
    delta buffer with its masks/staleness/step-count rows, and the scalar
    arrival/merge statistics ``Session.staleness_summary()`` reports. With
    ``history_store="int8"`` the dense ``deltas`` tree is replaced by the
    quantized store carry (and replay-only strategies drop ``prev_local``,
    exactly like the fused q8 carry).
    """
    zeros = tree_broadcast_clients(tree_zeros_like(params), n_clients)
    state[ASYNC_KEY] = {
        "inflight": tree_broadcast_clients(params, n_clients),
        "inflight_train": jnp.zeros((n_clients,), bool),
        "pull_round": jnp.zeros((n_clients,), jnp.int32),
        "pending": zeros,
        "pending_mask": jnp.zeros((n_clients,), bool),
        "pending_train": jnp.zeros((n_clients,), bool),
        "pending_stale": jnp.zeros((n_clients,), jnp.int32),
        "pending_k": jnp.ones((n_clients,), jnp.int32),
        "stats": {
            "arrivals": jnp.zeros((), jnp.int32),
            "merges": jnp.zeros((), jnp.int32),
            "stale_sum": jnp.zeros((), jnp.float32),
            "stale_max": jnp.zeros((), jnp.int32),
            "occupancy_sum": jnp.zeros((), jnp.int32),
        },
    }
    if cfg.history_store == "int8":
        flat, _ = tree_ravel_clients(zeros)
        store = HistoryStore.for_flat(n_clients, flat.shape[1], kind="int8")
        state["deltas"] = store.init()
        if not needs_stale:
            state.pop("prev_local", None)
    return state


def make_async_round_body(model: Classifier, fed: FedConfig,
                          cfg: AsyncConfig):
    """The traceable async round transition ``(state, train_row, dispatch,
    deliver, merge_flag, k_active, data) → state``. One scan step:

    1. **dispatch** — flagged clients pull the current global model and
       record their train/estimate decision and pull round;
    2. **compute** — the whole federation trains vmapped from its pulled
       models (idle clients' work is masked out downstream, exactly like
       unselected clients of a synchronous round), with the delivery
       round's per-client keys;
    3. **deliver** — arriving clients materialize their update via the
       synchronous train-or-estimate semantics (``strategy.estimate``
       against the stored history), the update lands in the pending
       buffer tagged with its staleness, and the Δ history rolls forward
       for exactly the delivered rows;
    4. **merge** — if the round's merge flag is set, the buffered cohort
       aggregates through ``strategy.merge_stale`` with the schedule's
       w(s) weights and the buffer clears; otherwise params carry a
       zero update (numerically what an empty synchronous round applies).
    """
    strategy = fed.resolve()
    channel = uplink_channel(fed)

    def round_body(state, train_row, dispatch, deliver, merge_flag,
                   k_active, data: FederatedData, energy=None, base=None):
        n = data.n_clients
        a = state[ASYNC_KEY]
        params, rnd = state["params"], state["round"]
        with scope(LOCAL_SGD):
            key, keys = _round_keys(state["key"], n)

            # ---- 1. dispatch: pull the current global model ------------
            bcast = tree_broadcast_clients(params, n)
            start = masked_select(dispatch, bcast, a["inflight"])
            pull_round = jnp.where(dispatch, rnd, a["pull_round"])
            inflight_train = jnp.where(dispatch, train_row,
                                       a["inflight_train"])

            # ---- 2. compute from the pulled models ---------------------
            local = _train_clients(model._replace(base=base), fed, start,
                                   keys, data.x, data.y,
                                   data.sizes, k_active,
                                   prox=strategy.prox_coeff(),
                                   dual=strategy.local_dual(state))
            trained_delta = tree_sub(local, start)

        with scope(ESTIMATE):
            # ---- 3. deliveries: synchronous round semantics at arrival -
            flat_pending, unravel_clients = tree_ravel_clients(a["pending"])
            p = flat_pending.shape[1]
            q8 = (isinstance(state["deltas"], dict)
                  and set(state["deltas"]) == {"payload", "scales"})
            if q8:
                store = HistoryStore(n, state["deltas"]["payload"].shape[1],
                                     kind="int8", logical_width=p)
                hist_deltas = unravel_clients(
                    store.read_logical(state["deltas"]))
            else:
                store = None
                hist_deltas = state["deltas"]
            if "prev_local" in state:
                stale_delta = tree_sub(state["prev_local"], start)
                stale_delta = masked_select(state["trained_ever"],
                                            stale_delta,
                                            tree_zeros_like(stale_delta))
                hist_prev = state["prev_local"]
            else:
                # replay-only int8 carry: nothing reads the stale model;
                # the update_history output for it is discarded below
                stale_delta = tree_zeros_like(trained_delta)
                hist_prev = local
            hist = {"deltas": hist_deltas, "prev_local": hist_prev,
                    "trained_ever": state["trained_ever"]}
            for hk in strategy.extra_history_keys():
                if hk in state:
                    hist[hk] = state[hk]
            t_mask = deliver & inflight_train
            ctx = RoundCtx(sel_mask=deliver, train_mask=t_mask,
                           k_active=k_active, round=rnd, tau=fed.tau,
                           stale_delta=stale_delta,
                           trained_delta=trained_delta, energy=energy)
            est = strategy.estimate(hist, ctx)
            delta_i = masked_select(t_mask, trained_delta, est)

        with scope(AGGREGATE):
            staleness = rnd - pull_round
            pending = masked_select(deliver, delta_i, a["pending"])
            pending_mask = a["pending_mask"] | deliver
            pending_train = jnp.where(deliver, t_mask, a["pending_train"])
            pending_stale = jnp.where(deliver, staleness, a["pending_stale"])
            pending_k = jnp.where(deliver, k_active, a["pending_k"])

        with scope(HISTORY):
            deltas_tree, prev_local = strategy.update_history(
                hist, ctx, trained_delta, local, est)
            if store is None:
                new_deltas = deltas_tree
            else:
                flat_new, _ = tree_ravel_clients(deltas_tree)
                new_deltas = store.write(state["deltas"], deliver,
                                         store.pad_rows(flat_new))
            trained_ever = state["trained_ever"] | (deliver & t_mask)

        with scope(AGGREGATE):
            # ---- 4. buffered merge (only the K-arrival boundary pays) --
            decay_w = staleness_weights(cfg.schedule, cfg.staleness_decay,
                                        pending_stale)
            mctx = RoundCtx(sel_mask=pending_mask, train_mask=pending_train,
                            k_active=pending_k, round=rnd, tau=fed.tau,
                            stale_delta=tree_zeros_like(pending),
                            trained_delta=pending, energy=energy)
            occ = jnp.sum(pending_mask.astype(jnp.int32))

            def _merge(_):
                aggf = strategy.agg_mask(mctx).astype(jnp.float32)
                up = pending
                if channel is not None:
                    # merge-time uplink: the buffered cohort transmits
                    # over the air NOW — gains and AWGN key on the MERGE
                    # round
                    up = channel.fade(up, rnd,
                                      jnp.arange(n, dtype=jnp.int32), n,
                                      TAG_MERGE)
                d = strategy.merge_stale(up, aggf, pending_stale, decay_w,
                                         mctx)
                if channel is not None:
                    d = channel.corrupt(d, rnd, TAG_MERGE)
                return (tree_add(params, d), jnp.zeros((n,), bool),
                        jnp.ones((), jnp.int32), occ)

            def _hold(_):
                return (tree_add(params, tree_zeros_like(params)),
                        pending_mask, jnp.zeros((), jnp.int32),
                        jnp.zeros((), jnp.int32))

            new_params, new_pending_mask, merge_inc, occ_inc = \
                jax.lax.cond(merge_flag, _merge, _hold, operand=None)

        stats = a["stats"]
        arrived_stale = jnp.where(deliver, staleness, 0)
        new_stats = {
            "arrivals": stats["arrivals"]
            + jnp.sum(deliver.astype(jnp.int32)),
            "merges": stats["merges"] + merge_inc,
            "stale_sum": stats["stale_sum"]
            + jnp.sum(arrived_stale.astype(jnp.float32)),
            "stale_max": jnp.maximum(stats["stale_max"],
                                     jnp.max(arrived_stale)),
            "occupancy_sum": stats["occupancy_sum"] + occ_inc,
        }

        out = {
            "params": new_params,
            "deltas": new_deltas,
            "trained_ever": trained_ever,
            "round": rnd + 1,
            "key": key,
            ASYNC_KEY: {
                "inflight": start,
                "inflight_train": inflight_train,
                "pull_round": pull_round,
                "pending": pending,
                "pending_mask": new_pending_mask,
                "pending_train": pending_train,
                "pending_stale": pending_stale,
                "pending_k": pending_k,
                "stats": new_stats,
            },
        }
        if "prev_local" in state:
            out["prev_local"] = prev_local
        with scope(HISTORY):
            # strategy extras (e.g. feddyn's dual) roll on DELIVERED
            # trained rows — ctx's sel∧train is deliver∧inflight_train,
            # exactly the rows whose Δ history advanced above
            out.update(strategy.update_extra_history(
                hist, ctx, trained_delta, local, est))
        return out

    return round_body


def make_async_span_runner(model: Classifier, data: FederatedData,
                           fed: FedConfig, cfg: AsyncConfig, *,
                           policy=None, profile=None):
    """Async executor span: ``run_span(state, train_chunk, k_active,
    sched)`` advances a (C, N) span of plan *training* rows against the
    span's slice of the arrival schedule ``sched`` — a (dispatch,
    deliver, merge) tuple of (C, N)/(C, N)/(C,) event tables from
    :func:`repro.system.devices.simulate_arrivals` — as one jitted
    ``lax.scan`` over arrival events.

    With ``policy`` + ``profile`` (policy mode, the Session default) the
    signature drops the train chunk — ``run_span(state, k_active,
    sched)`` — and the budget policy decides at each client's DISPATCH
    round (when the work is actually started and its energy drained),
    while the ledger books the upload at the DELIVERY round: a stale
    update counts exactly once, when it realizes as an arrival.
    """
    if (policy is None) != (profile is None):
        raise ValueError("policy mode needs BOTH policy and profile "
                         "(got exactly one)")
    round_body = make_async_round_body(model, fed, cfg)

    if policy is None:
        @jax.jit
        def run_span(state, train_chunk, k_active, sched, data, base):
            dispatch_c, deliver_c, merge_c = sched

            def step(st, xs):
                train, disp, dlv, mrg = xs
                return round_body(st, train, disp, dlv, mrg, k_active,
                                  data, base=base), None

            state, _ = jax.lax.scan(
                step, state, (train_chunk, dispatch_c, deliver_c, merge_c))
            return state

        return _bind(run_span, data=data, base=model.base)

    # ---- policy mode: decide at dispatch, account at delivery -----------
    from repro.core.budget import budget_ctx
    from repro.system.devices import advance_devices, update_ledger

    _check_profile(profile, data)
    # strategy extras (e.g. feddyn's dual rows) ride the base round state
    base_keys = _ASYNC_BASE_KEYS + fed.resolve().extra_history_keys()

    def policy_round(state, dispatch, deliver, merge_flag, k_active, data,
                     rows, base):
        ids = jnp.arange(data.n_clients, dtype=jnp.int32)
        dev = state["device"]
        with scope(POLICY):
            bctx = budget_ctx(rows, dev, state["round"], ids, dispatch,
                              profile.seed)
            train_row, new_rows = policy.decide(state["policy"], bctx)
            train_row = train_row & dispatch
        base_state = {k: state[k] for k in base_keys if k in state}
        new_base = round_body(base_state, train_row, dispatch, deliver,
                              merge_flag, k_active, data,
                              energy=dev["energy"], base=base)
        # energy drains when the work is dispatched (the compute happens
        # then); uploads/estimates are booked per realized ARRIVAL — the
        # recalled in-flight decision classifies each delivery
        with scope(POLICY):
            spent = dispatch & train_row
            new_base["policy"] = new_rows
            new_base["device"] = advance_devices(rows, dev, spent,
                                                 state["round"], ids,
                                                 profile.seed)
            new_base["ledger"] = update_ledger(
                state["ledger"], rows, deliver,
                new_base[ASYNC_KEY]["inflight_train"])
        return new_base

    @jax.jit
    def run_span(state, k_active, sched, data, rows, base):
        dispatch_c, deliver_c, merge_c = sched

        def step(st, xs):
            disp, dlv, mrg = xs
            return policy_round(st, disp, dlv, mrg, k_active, data,
                                rows, base), None

        state, _ = jax.lax.scan(step, state, (dispatch_c, deliver_c,
                                              merge_c))
        return state

    return _bind(run_span, data=data, rows=profile.rows(), base=model.base)
