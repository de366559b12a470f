"""Round executors for the vectorized-client federation.

Six ways to run the same round semantics, all built from one traceable
cohort-round core (:func:`_cohort_round` and the shared training/masking
helpers) so they are numerically interchangeable:

Two decision modes feed every executor:

* **mask mode** (the seed-era contract) — the caller passes precomputed
  ``sel``/``train`` masks per round;
* **policy mode** (:func:`make_policy_round_body` and friends) — a
  :class:`repro.core.budget.BudgetPolicy` decides ``train`` *inside the
  trace* from simulated device state (:mod:`repro.system.devices`), whose
  energy/load/ledger rows advance in the round carry. Eval-free spans stay
  a single ``lax.scan``; the sharded executor decides per-shard on the
  gathered device rows. ``PrecompiledPolicy`` makes mask mode a special
  case, bit-for-bit (pinned in ``tests/test_executor_matrix.py``).

* :func:`make_round_fn` — one jitted round (the classic per-round API);
* :func:`make_span_runner` — ``jax.lax.scan`` over a stacked (C, N) chunk
  of plan masks, so an eval-free span of C rounds executes as ONE jitted
  program instead of C separate dispatches (the dominant cost at small
  model sizes is host→device round-trips, not FLOPs — see
  ``benchmarks/round_loop.py``);
* :func:`make_sharded_span_runner` — the scan span with every round's
  cohort ``shard_map``'ed over a ``("clients",)`` mesh: each round gathers
  only the sampled participants' history rows
  (:class:`repro.data.federated.CohortSampler`), splits them across
  devices, reduces the aggregation with ``lax.psum`` and scatters the
  updated rows back — N ≫ devices cross-device cohorts;
* ``fused=True`` — route the train-or-estimate + masked-mean + global
  update through the single-HBM-pass Pallas kernel
  (:func:`repro.kernels.ops.cc_delta_update`) on flat (N, P) parameters;
  interpret mode on CPU, Mosaic on TPU (its v5e lowering is compiled by
  ``tests/test_tpu_compile.py``). Only strategies whose estimate is affine
  in the stored Δ and the stale delta (``fused_capable``) qualify;
* :func:`make_hierarchical_span_runner` — the two-tier client→edge→server
  executor: clients train against their edge aggregator's model
  (:class:`repro.core.hierarchy.EdgeTopology`), edges run ``edge_period``
  rounds of masked intra-edge aggregation, and the server folds the edge
  models back every period. Edges — and their member clients — shard over
  the ``("edges",)`` mesh axis (:func:`repro.launch.mesh.make_edge_mesh`):
  intra-edge rounds are entirely shard-local, only the sync rounds
  all-gather the uploads. A single edge, or ``edge_period=1``, collapses
  to flat FedAvg bit-for-bit, so the flat executors are its oracle;
* :mod:`repro.core.async_rounds` — the staleness-tolerant buffered-async
  executor: clients pull/deliver on a precomputed arrival schedule
  (:func:`repro.system.devices.simulate_arrivals`), updates merge every
  K arrivals with staleness-decayed weights through
  ``Strategy.merge_stale``, and the Δ history can ride the sharded int8
  :class:`repro.core.history_store.HistoryStore`. Zero latency + K = 1
  collapses to the scan executor bit-for-bit, so it too is
  differential-testable against the flat oracle.

Strategy semantics themselves live in :mod:`repro.core.strategies`; this
module never branches on a strategy name.

Every jitted runner takes the :class:`~repro.data.federated.FederatedData`
(and, in policy mode, the device-profile rows) as an argument, bound with
:func:`_bind`, so no dataset-sized constant is compiled into a program. So
does a model's frozen ``base`` (LoRA over a large decoder): the runner
takes it as an argument and the round body rebinds the model to it inside
the trace (``model._replace(base=base)``), so the base lives on the device
once and never enters ``state["params"]``; a model without one passes
``None``, an empty pytree, and traces exactly as before.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import (TAG_C2E, TAG_E2S, TAG_UPLINK,
                                uplink_channel)
from repro.core.strategies import (RoundCtx, Strategy, get_strategy,
                                   masked_select)
from repro.data.federated import FederatedData
from repro.models.simple import Classifier, xent_loss
from repro.utils.pytree import (
    PyTree,
    tree_add,
    tree_broadcast_clients,
    tree_index,
    tree_ravel,
    tree_ravel_clients,
    tree_stack,
    tree_sub,
    tree_where,
    tree_zeros_like,
)
from repro.utils.trace import (AGGREGATE, ESTIMATE, HISTORY, LOCAL_SGD,
                               POLICY, scope)

_FUSED_PAD = 512               # flat params padded to a tile-friendly multiple

#: every registered round executor — the Session dispatch table and the
#: spec/CLI ``choices`` derive from this tuple, so adding an executor here
#: (plus its Session branch) makes it reachable everywhere at once
EXECUTORS = ("scan", "python", "sharded", "hierarchical", "async")

#: Δ-history wire/storage formats accepted by ``FedConfig.compress``
COMPRESS_KINDS = ("none", "int8")

#: mesh axis name the sharded executor splits the client dimension over
CLIENT_AXIS = "clients"

#: mesh axis name the hierarchical executor splits edge aggregators over
EDGE_AXIS = "edges"

#: the mask-mode federated state keys (policy mode adds policy/device/ledger)
_BASE_KEYS = ("params", "deltas", "prev_local", "trained_ever", "round",
              "key")


@dataclass(frozen=True)
class FedConfig:
    strategy: str = "cc"
    variant: str = "client"        # Alg.1 client | Alg.2 server | Alg.3 mixed
    local_steps: int = 5           # K
    batch_size: int = 32
    lr: float = 0.05
    tau: int = 100                 # CC-FedAvg(c) switch round
    seed: int = 0
    #: participants sampled per round by the sharded executor
    #: (None = the full federation every round)
    cohort_size: int | None = None
    #: Δ-history wire/storage format: "none" keeps f32, "int8" stores the
    #: (N, P) history quantized per client row (fused executor only)
    compress: str = "none"
    #: μ of fedprox's proximal term (0.0 = plain FedAvg local objective)
    prox_mu: float = 0.0
    #: α of feddyn's dynamic regularizer (0.0 = dual state switched off)
    feddyn_alpha: float = 0.0
    #: uplink model applied to the stacked uploads before aggregation
    #: (:mod:`repro.core.channel`): "noiseless" keeps the exact masked
    #: mean, "aircomp" models analog over-the-air superposition
    channel: str = "noiseless"
    #: aircomp receive SNR in dB relative to the aggregated signal's rms
    channel_snr_db: float = 20.0
    #: draw per-client Rayleigh fading gains on every uplink
    channel_fading: bool = False

    def __post_init__(self):
        from repro.core.channel import CHANNEL_KINDS
        strategy = get_strategy(self.strategy)  # raises on unknown names
        if self.cohort_size is not None and self.cohort_size < 1:
            raise ValueError(
                f"cohort_size must be >= 1, got {self.cohort_size}")
        if self.compress not in COMPRESS_KINDS:
            raise ValueError(
                f"compress must be one of {COMPRESS_KINDS}, got "
                f"{self.compress!r}")
        if self.compress == "int8" and not strategy.fused_capable:
            raise ValueError(
                f"compress='int8' stores the Δ history in int8, which only "
                f"the fused kernel path consumes; strategy "
                f"{self.strategy!r} is not fused-capable — use "
                f"compress='none'")
        if self.channel not in CHANNEL_KINDS:
            raise ValueError(
                f"channel must be one of {CHANNEL_KINDS}, got "
                f"{self.channel!r}")
        if self.prox_mu < 0:
            raise ValueError(f"prox_mu must be >= 0, got {self.prox_mu}")
        if self.feddyn_alpha < 0:
            raise ValueError(
                f"feddyn_alpha must be >= 0, got {self.feddyn_alpha}")

    def resolve(self) -> Strategy:
        """The registered strategy, with this config's hyperparameters
        bound via :meth:`repro.core.strategies.Strategy.configure`."""
        return get_strategy(self.strategy).configure(self)


def _local_train(model: Classifier, params, key, cx, cy, size,
                 k_steps: int, k_active, batch_size: int, lr: float,
                 prox: float = 0.0, dual=None):
    """K local SGD steps on one client (Eq. 2). ``k_active`` ≤ k_steps masks
    steps off for FedNova's reduced-iteration budget.

    ``prox`` > 0 adds FedProx/FedDyn's proximal gradient μ(w − x_t) toward
    the start params; ``dual`` (a params-shaped tree) subtracts FedDyn's
    per-client gradient correction h_i. Both default OFF at the Python
    level, leaving the base trace bit-identical."""
    x0 = params
    def step(carry, k):
        p, key = carry
        key, sk = jax.random.split(key)
        idx = jax.random.randint(sk, (batch_size,), 0, 2 ** 30) % size
        g = jax.grad(lambda q: xent_loss(model, q, cx[idx], cy[idx]))(p)
        if prox:
            g = jax.tree.map(lambda gv, pv, ov: gv + prox * (pv - ov),
                             g, p, x0)
        if dual is not None:
            g = jax.tree.map(lambda gv, hv: gv - hv, g, dual)
        new = jax.tree.map(lambda a, b: a - lr * b, p, g)
        do = k < k_active
        p = jax.tree.map(
            lambda a, b: jnp.where(do, a, b), new, p)
        return (p, key), None

    (params, _), _ = jax.lax.scan(step, (params, key),
                                  jnp.arange(k_steps))
    return params


def init_fed_state(rng, model: Classifier, n_clients: int, *,
                   policy=None, profile=None, topology=None,
                   compress: str = "none", async_cfg=None,
                   needs_stale: bool = True, strategy=None) -> PyTree:
    """Fresh federated state. With ``policy`` + ``profile`` the carry also
    holds the budget-policy rows, the simulated device state and the
    energy/cost ledger (policy mode); without, the seed-era 6-key state.
    With ``topology`` (an :class:`repro.core.hierarchy.EdgeTopology`) the
    carry additionally holds the edge tier's models (``edge_params``, an
    (E,)-stacked params tree initialized to the global model — every edge
    period starts from an exact sync).

    ``compress="int8"`` (fused executor only) stores the (N, P) Δ history
    as a flat tile-padded int8 payload + per-row f32 scales instead of the
    f32 client tree; with ``needs_stale=False`` (every strategy whose
    estimate never reads the stale model) the O(N, P) f32 ``prev_local``
    is dropped from the carry entirely.

    ``async_cfg`` (an :class:`repro.core.async_rounds.AsyncConfig`) adds
    the async executor's FedBuff carry under ``state["async"]`` and, with
    ``history_store="int8"``, swaps the Δ history for the quantized
    :class:`repro.core.history_store.HistoryStore` carry (the async
    analogue of ``compress="int8"``, same prev_local-dropping rule).

    ``strategy`` (a resolved :class:`repro.core.strategies.Strategy`)
    additionally creates the strategy's extra history rows (e.g. feddyn's
    per-client ``dual`` tree); omitted, the state carries only the base
    keys — exactly the pre-extension layout."""
    params = model.init(rng)
    zeros = tree_broadcast_clients(tree_zeros_like(params), n_clients)
    state = {
        "params": params,
        "deltas": zeros,                       # Δ_{t−1}^i  (Strategy 3)
        "prev_local": tree_broadcast_clients(params, n_clients),
        "trained_ever": jnp.zeros((n_clients,), bool),
        "round": jnp.zeros((), jnp.int32),
        "key": rng,
    }
    if strategy is not None:
        state.update(strategy.init_extra_history(params, n_clients))
    if compress not in COMPRESS_KINDS:
        raise ValueError(
            f"compress must be one of {COMPRESS_KINDS}, got {compress!r}")
    if compress == "int8":
        from repro.core.compress import quantize_rows
        flat, _ = tree_ravel(params)
        p_pad = flat.shape[0] + (-flat.shape[0]) % _FUSED_PAD
        # zero deltas quantized: payload 0, the clamp-floor scale — exactly
        # quantize_rows of the zero history, so resume round-trips bit-wise
        payload, scales = quantize_rows(jnp.zeros((n_clients, p_pad)))
        state["deltas"] = {"payload": payload, "scales": scales}
        if not needs_stale:
            del state["prev_local"]
    if (policy is None) != (profile is None):
        raise ValueError("policy mode needs BOTH policy and profile "
                         "(got exactly one)")
    if policy is not None:
        from repro.system.devices import init_device_state, init_ledger
        state["policy"] = policy.init_rows(n_clients)
        state["device"] = init_device_state(profile)
        state["ledger"] = init_ledger(n_clients)
    if topology is not None:
        if topology.n_clients != n_clients:
            raise ValueError(
                f"topology covers {topology.n_clients} clients, state has "
                f"{n_clients}")
        state["edge_params"] = tree_broadcast_clients(params,
                                                      topology.n_edges)
    if async_cfg is not None:
        from repro.core.async_rounds import init_async_carry
        state = init_async_carry(state, params, n_clients, async_cfg,
                                 needs_stale=needs_stale)
    return state


def _round_keys(key, n: int):
    """Split the round key into (next round key, per-client keys).

    Keys are always derived for the FULL federation (``n`` = total clients)
    and cohort members take ``keys[idx]`` — client i sees the same training
    randomness whether it runs in a full round or a sampled cohort, which
    is what makes the sharded executor differential-testable against the
    others.
    """
    ks = jax.random.split(key, n + 1)
    return ks[0], ks[1:]


def _train_clients(model: Classifier, fed: FedConfig, start, keys,
                   cx, cy, sizes, k_active, prox: float = 0.0, dual=None):
    """vmap local training over a client-stacked tree of start params —
    each client's edge aggregator model under a two-tier topology, or the
    async executor's pulled models (the flat executors train only their
    trainers, :func:`_train_cohort`). ``dual`` is an optional
    client-stacked tree of FedDyn correction rows, vmapped alongside."""
    if dual is None:
        return jax.vmap(
            lambda p, k, x, y, sz, ka: _local_train(
                model, p, k, x, y, sz, fed.local_steps, ka,
                fed.batch_size, fed.lr, prox)
        )(start, keys, cx, cy, sizes, k_active)
    return jax.vmap(
        lambda p, k, x, y, sz, ka, h: _local_train(
            model, p, k, x, y, sz, fed.local_steps, ka,
            fed.batch_size, fed.lr, prox, h)
    )(start, keys, cx, cy, sizes, k_active, dual)


def _train_cohort(model: Classifier, fed: FedConfig, params, keys,
                  cx, cy, sizes, k_active, train_mask, prox: float = 0.0,
                  dual=None, axis_name=None):
    """Broadcast the global model and run local training for the cohort's
    lanes whose ``train_mask`` is set (full federation or gathered
    participants). Under ``shard_map`` the replicated model is cast to
    varying over ``axis_name``, and each shard runs its own trainers.

    A ``lax.fori_loop`` over the trainers' ids in ascending order, bounded
    by their count, trains one client per trip: it reads the client's own
    key, data rows, ``k_active`` and ``dual`` row and writes its row of the
    carried client stack. Each client has its own weights, so a vmap over
    clients buys no weight reuse (its convolutions are grouped, not wider):
    on a TPU v5e at ResNet-18-GN width the loop trains 8 clients in 205 ms
    where an 8-wide vmap takes 302 ms. Rows that do not train keep the
    broadcast model, so their trained delta is exactly 0; every executor
    reads a lane's local model only where ``train_mask`` is set.

    Returns ``(broadcast, local)``."""
    n = sizes.shape[0]
    if axis_name is not None:
        params = jax.lax.pcast(params, axis_name, to="varying")
    broadcast = tree_broadcast_clients(params, n)
    ids = jnp.nonzero(train_mask, size=n, fill_value=0)[0]

    def one(i, local):
        c = ids[i]
        row = _local_train(model, params, keys[c], cx[c], cy[c], sizes[c],
                           fed.local_steps, k_active[c], fed.batch_size,
                           fed.lr, prox,
                           None if dual is None else tree_index(dual, c))
        return jax.tree.map(lambda s, r: s.at[c].set(r), local, row)

    local = jax.lax.fori_loop(0, jnp.sum(train_mask, dtype=jnp.int32), one,
                              broadcast)
    return broadcast, local


def _cohort_round(model: Classifier, fed: FedConfig, strategy: Strategy,
                  params, rnd, hist, cx, cy, sizes, keys,
                  sel_mask, train_mask, k_active, axis_name=None,
                  energy=None, channel=None, client_ids=None,
                  n_total=None):
    """One round over a cohort view of the federation.

    ``hist`` holds the cohort's per-client rows (``deltas`` / ``prev_local``
    / ``trained_ever`` + any strategy extras); every executor wraps this
    one traceable core. With ``axis_name`` set the cohort axis is
    ``shard_map``'ed and aggregation reduces across shards (the
    strategies' ``aggregate`` hooks psum), so the returned global params
    are replicated.

    ``channel`` (an :class:`repro.core.channel.UplinkChannel`, or None
    for the exact noiseless uplink) fades the stacked uploads before
    aggregation — ``client_ids`` are the cohort's absolute ids into the
    ``n_total``-client gain draw — and corrupts the aggregated delta with
    this round's AWGN (post-psum, so the draw is replicated).
    Returns ``(new_params, new_hist)``.
    """
    with scope(LOCAL_SGD):
        broadcast, local = _train_cohort(model, fed, params, keys, cx, cy,
                                         sizes, k_active, train_mask,
                                         prox=strategy.prox_coeff(),
                                         dual=strategy.local_dual(hist),
                                         axis_name=axis_name)
        trained_delta = tree_sub(local, broadcast)

    # ---- estimation for skipped clients --------------------------
    with scope(ESTIMATE):
        stale_delta = tree_sub(hist["prev_local"], broadcast)
        stale_delta = masked_select(hist["trained_ever"], stale_delta,
                                    tree_zeros_like(stale_delta))
        ctx = RoundCtx(sel_mask=sel_mask, train_mask=train_mask,
                       k_active=k_active, round=rnd, tau=fed.tau,
                       stale_delta=stale_delta, trained_delta=trained_delta,
                       axis_name=axis_name, energy=energy)
        est = strategy.estimate(hist, ctx)
        delta_i = masked_select(train_mask, trained_delta, est)

    # ---- uplink + aggregation (Eq. 3 over Δ) ----------------------
    # fading touches only the aggregated copy of the uploads — history
    # keeps each client's true delta, exactly as a receiver cannot
    # corrupt what the client stores locally
    with scope(AGGREGATE):
        up = delta_i
        if channel is not None:
            nt = n_total if n_total is not None else sel_mask.shape[0]
            ids = (client_ids if client_ids is not None
                   else jnp.arange(nt, dtype=jnp.int32))
            up = channel.fade(up, rnd, ids, nt, TAG_UPLINK)
        aggf = strategy.agg_mask(ctx).astype(jnp.float32)
        delta = strategy.aggregate(up, aggf, ctx)
        if channel is not None:
            delta = channel.corrupt(delta, rnd, TAG_UPLINK)
        new_params = tree_add(params, delta)

    # ---- history updates ------------------------------------------
    with scope(HISTORY):
        upd = sel_mask & train_mask
        deltas, prev_local = strategy.update_history(hist, ctx,
                                                     trained_delta, local,
                                                     est)
        new_hist = {
            "deltas": deltas,
            "prev_local": prev_local,
            "trained_ever": hist["trained_ever"] | upd,
        }
        new_hist.update(strategy.update_extra_history(
            hist, ctx, trained_delta, local, est))
    return new_params, new_hist


def _bind(jitted, **inputs):
    """Pass ``inputs`` (the federation's data, profile rows, the model's
    frozen base) to a jitted runner as arguments on every call: arrays an
    executor closed over would be compiled into its program as
    constants."""
    return functools.partial(jitted, **inputs)


def make_round_body(model: Classifier, fed: FedConfig, *,
                    fused: bool = False):
    """The traceable single-round transition ``(state, sel, train, k,
    data, energy=None, base=None) → state`` that every executor (jit,
    scan, fused) wraps; ``base`` is the model's frozen base, traced."""
    strategy = fed.resolve()
    if fused:
        return _make_fused_round_body(model, fed, strategy)
    channel = uplink_channel(fed)

    def round_body(state, sel_mask, train_mask, k_active, data,
                   energy=None, base=None):
        with scope(LOCAL_SGD):
            key, keys = _round_keys(state["key"], data.n_clients)
        new_params, new_hist = _cohort_round(
            model._replace(base=base), fed, strategy, state["params"],
            state["round"], state,
            data.x, data.y, data.sizes, keys, sel_mask, train_mask,
            k_active, energy=energy, channel=channel)
        return {
            "params": new_params,
            **new_hist,
            "round": state["round"] + 1,
            "key": key,
        }

    return round_body


def _make_fused_round_body(model: Classifier, fed: FedConfig,
                           strategy: Strategy):
    """Route the round through the fused Pallas kernel: one HBM pass
    computes Δ_t^i = train ? (x_K^i − x_t) : est_i, the weighted mean and
    the global update over flat (N, P) parameters.

    The strategy specializes the kernel through its
    :meth:`~repro.core.strategies.Strategy.fused_epilogue` coefficients
    (every registry estimate is affine in the stored Δ and the stale-model
    delta), so the whole registry runs fused. With
    ``fed.compress == "int8"`` the Δ history is carried as a flat
    tile-padded int8 payload + per-row scales and the round runs the q8
    kernel; replay-only strategies (``needs_stale=False``) then drop the
    f32 ``prev_local`` carry entirely."""
    from repro.kernels import ops

    if not strategy.fused_capable:
        raise ValueError(
            f"strategy {strategy.name!r} is not fused-capable (its estimate "
            "is not affine in the stored Δ / stale delta); use the "
            "tree-ops path")
    q8 = fed.compress == "int8"
    channel = uplink_channel(fed)

    def round_body(state, sel_mask, train_mask, k_active, data,
                   energy=None, base=None):
        n = data.n_clients
        with scope(LOCAL_SGD):
            key, keys = _round_keys(state["key"], n)
            broadcast, local = _train_cohort(
                model._replace(base=base), fed, state["params"], keys,
                data.x, data.y,
                data.sizes, k_active, train_mask,
                prox=strategy.prox_coeff(), dual=strategy.local_dual(state))
        # the kernel estimates, aggregates and writes the Δ history in
        # one pass, so the whole epilogue is the aggregation's scope
        with scope(AGGREGATE):
            flat_local, unravel_clients = tree_ravel_clients(local)
            flat_global, unravel = tree_ravel(state["params"])
            p = flat_global.shape[0]
            pad = (-p) % _FUSED_PAD
            if pad:                 # zero-pad: padded lanes stay exactly 0
                flat_local = jnp.pad(flat_local, ((0, 0), (0, pad)))
                flat_global = jnp.pad(flat_global, (0, pad))
            # history semantics: stored Δ only advances for sel∧train
            # clients, so that (not bare train_mask) is the kernel's train
            # input
            upd = sel_mask & train_mask
            ctx = RoundCtx(sel_mask=sel_mask, train_mask=train_mask,
                           k_active=k_active, round=state["round"],
                           tau=fed.tau, stale_delta=None,
                           trained_delta=None, energy=energy)
            ep = strategy.fused_epilogue(ctx)
            if channel is not None and channel.fading:
                # fading scales only each client's aggregated
                # contribution — fold the gains into the kernel's
                # aggregation weights; the stored Δ history stays the
                # client's true delta
                gains = channel.gains(state["round"],
                                      jnp.arange(n, dtype=jnp.int32), n,
                                      TAG_UPLINK)
                ep = ep._replace(agg_w=ep.agg_w * gains)
            stale_flat = None
            if strategy.needs_stale:
                stale = masked_select(
                    state["trained_ever"],
                    tree_sub(state["prev_local"], broadcast),
                    tree_zeros_like(broadcast))
                stale_flat, _ = tree_ravel_clients(stale)
                if pad:
                    stale_flat = jnp.pad(stale_flat, ((0, 0), (0, pad)))
            updf = upd.astype(jnp.float32)
            if q8:
                new_payload, new_scales, new_global = \
                    ops.cc_delta_update_q8(
                        flat_local, state["deltas"]["payload"],
                        state["deltas"]["scales"], flat_global, updf, updf,
                        ep.agg_w, ep.e_replay, ep.e_stale, ep.store_scale,
                        ep.denom, ep.post_scale, stale_flat)
                new_deltas = {"payload": new_payload, "scales": new_scales}
            else:
                flat_deltas, _ = tree_ravel_clients(state["deltas"])
                if pad:
                    flat_deltas = jnp.pad(flat_deltas, ((0, 0), (0, pad)))
                new_flat, new_global = ops.cc_epilogue_update(
                    flat_local, flat_deltas, flat_global, updf, updf,
                    ep.agg_w, ep.e_replay, ep.e_stale, ep.store_scale,
                    ep.denom, ep.post_scale, stale_flat)
                new_deltas = unravel_clients(new_flat[:, :p])
            new_params = unravel(new_global[:p])
            if channel is not None:
                # the kernel already applied the (faded) aggregate; AWGN
                # hits the aggregated delta exactly as in the tree-ops path
                d = channel.corrupt(tree_sub(new_params, state["params"]),
                                    state["round"], TAG_UPLINK)
                new_params = tree_add(state["params"], d)
        out = {
            "params": new_params,
            "deltas": new_deltas,
            "trained_ever": state["trained_ever"] | upd,
            "round": state["round"] + 1,
            "key": key,
        }
        with scope(HISTORY):
            if "prev_local" in state:
                out["prev_local"] = masked_select(upd, local,
                                                  state["prev_local"])
            if strategy.extra_history_keys():
                out.update(strategy.update_extra_history(
                    state, ctx, tree_sub(local, broadcast), local, None))
        return out

    return round_body


def make_round_fn(model: Classifier, data: FederatedData, fed: FedConfig,
                  *, fused: bool = False):
    """One jitted round: ``round_fn(state, sel_mask, train_mask, k_active)``."""
    return _bind(jax.jit(make_round_body(model, fed, fused=fused)),
                 data=data, base=model.base)


def make_span_runner(model: Classifier, data: FederatedData, fed: FedConfig,
                     *, fused: bool = False):
    """Scan executor: ``run_span(state, sel_chunk, train_chunk, k_active)``
    advances the federation over a (C, N) chunk of plan masks as one jitted
    ``lax.scan`` — no host sync until the span ends. Recompiles once per
    distinct chunk length C (eval cadence makes C constant in practice)."""
    round_body = make_round_body(model, fed, fused=fused)

    @jax.jit
    def run_span(state, sel_chunk, train_chunk, k_active, data, base):
        def step(st, masks):
            sel, train = masks
            return round_body(st, sel, train, k_active, data,
                              base=base), None

        state, _ = jax.lax.scan(step, state, (sel_chunk, train_chunk))
        return state

    return _bind(run_span, data=data, base=model.base)


# ---------------------------------------------------------------------------
# policy mode: traced in-loop decisions over simulated device state
# ---------------------------------------------------------------------------


def _check_profile(profile, data: FederatedData) -> None:
    if profile.n_clients != data.n_clients:
        raise ValueError(
            f"device profile covers {profile.n_clients} clients, data has "
            f"{data.n_clients}")


def make_policy_round_body(model: Classifier, fed: FedConfig, policy,
                           profile, *, fused: bool = False):
    """The policy-mode round transition ``(state, sel_mask, k_active,
    data, rows, base=None) → state`` (``rows`` = ``profile.rows()``): the
    train/estimate decision happens *inside the trace* —
    ``policy.decide`` reads the carried device state, the device simulator
    advances, and the energy ledger accumulates. Wraps the same mask-mode
    round body every executor uses, so round numerics are identical given
    identical decisions."""
    from repro.core.budget import budget_ctx
    from repro.system.devices import advance_devices, update_ledger

    body = make_round_body(model, fed, fused=fused)
    # strategy extras (e.g. feddyn's dual rows) ride the base round state
    base_keys = _BASE_KEYS + fed.resolve().extra_history_keys()

    def round_body(state, sel_mask, k_active, data, rows, base=None):
        ids = jnp.arange(data.n_clients, dtype=jnp.int32)
        dev = state["device"]
        with scope(POLICY):
            ctx = budget_ctx(rows, dev, state["round"], ids, sel_mask,
                             profile.seed)
            train_mask, new_rows = policy.decide(state["policy"], ctx)
            train_mask = train_mask & sel_mask
        # compress="int8" replay strategies carry no prev_local
        base_state = {k: state[k] for k in base_keys if k in state}
        new_base = body(base_state, sel_mask, train_mask, k_active, data,
                        energy=dev["energy"], base=base)
        with scope(POLICY):
            spent = sel_mask & train_mask
            new_base["policy"] = new_rows
            new_base["device"] = advance_devices(rows, dev, spent,
                                                 state["round"], ids,
                                                 profile.seed)
            new_base["ledger"] = update_ledger(state["ledger"], rows,
                                               sel_mask, train_mask)
        return new_base

    return round_body


def make_policy_round_fn(model: Classifier, data: FederatedData,
                         fed: FedConfig, policy, profile, *,
                         fused: bool = False):
    """One jitted policy-mode round: ``round_fn(state, sel_mask,
    k_active)``."""
    _check_profile(profile, data)
    return _bind(jax.jit(make_policy_round_body(model, fed, policy,
                                                profile, fused=fused)),
                 data=data, rows=profile.rows(), base=model.base)


def make_policy_span_runner(model: Classifier, data: FederatedData,
                            fed: FedConfig, policy, profile, *,
                            fused: bool = False):
    """Policy-mode scan executor: ``run_span(state, sel_chunk, k_active)``
    advances a (C, N) span of *selection* masks as one jitted ``lax.scan``
    — training decisions, device dynamics and the ledger are all traced, so
    an eval-free span is still a single program with no host sync."""
    _check_profile(profile, data)
    round_body = make_policy_round_body(model, fed, policy, profile,
                                        fused=fused)

    @jax.jit
    def run_span(state, sel_chunk, k_active, data, rows, base):
        def step(st, sel):
            return round_body(st, sel, k_active, data, rows, base), None

        state, _ = jax.lax.scan(step, state, sel_chunk)
        return state

    return _bind(run_span, data=data, rows=profile.rows(), base=model.base)


def make_sharded_span_runner(model: Classifier, data: FederatedData,
                             fed: FedConfig, *, mesh=None,
                             cohort_size: int | None = None,
                             policy=None, profile=None):
    """Sharded executor: ``run_span(state, sel_chunk, train_chunk, k_active,
    cohort_idx)`` advances the federation over a (C, N) chunk of plan masks
    with each round's cohort ``shard_map``'ed over the ``clients`` mesh axis.

    ``cohort_idx`` is a (C, M) table of participant ids (see
    :class:`repro.data.federated.CohortSampler`; M = ``cohort_size``,
    defaulting to ``fed.cohort_size`` or the full federation). Per round the
    scan body gathers only the cohort's history rows and data shards
    (``strategy.gather_history``), runs the cohort round split across the
    mesh — aggregation reduces with ``lax.psum``, so the new global params
    come back replicated — and scatters the updated rows into the full-N
    state (``strategy.scatter_history``). Non-members are untouched, exactly
    as if their ``sel``/``train`` masks were False.

    ``mesh`` defaults to a 1-D client mesh over the largest device count
    that divides the cohort (:func:`repro.launch.mesh.make_client_mesh`);
    an explicit mesh must divide it.

    With ``policy`` + ``profile`` set (policy mode) the signature drops the
    train chunk — ``run_span(state, sel_chunk, k_active, cohort_idx)`` —
    and each round *decides* per-shard: the cohort's policy rows, device
    rows and profile rows are gathered alongside the history, and the
    decision runs inside ``shard_map`` (every policy op is per-client
    elementwise, so no cross-shard reduction is needed). The device advance
    and ledger update then run over the FULL federation outside the shard
    — off-cohort devices keep harvesting and their load keeps evolving,
    exactly as in a full round where they simply aren't selected. Together
    with decision randomness keyed on absolute client ids, this makes a
    sampled-cohort policy round EQUAL a full policy round whose selection
    mask is zeroed outside the cohort (pinned bit-for-bit in
    ``tests/test_executor_matrix.py``).
    """
    from jax.sharding import PartitionSpec
    from repro.launch.mesh import best_client_shards, make_client_mesh
    from repro.sharding.api import ShardingContext

    if (policy is None) != (profile is None):
        raise ValueError("policy mode needs BOTH policy and profile "
                         "(got exactly one)")
    strategy = fed.resolve()
    n = data.n_clients
    m = cohort_size if cohort_size is not None else (fed.cohort_size or n)
    if not 1 <= m <= n:
        raise ValueError(f"cohort_size must be in [1, {n}], got {m}")
    if mesh is None:
        mesh = make_client_mesh(best_client_shards(m))
    if CLIENT_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh must carry a {CLIENT_AXIS!r} axis, got "
                         f"{mesh.axis_names}")
    shards = dict(zip(mesh.axis_names, mesh.devices.shape))[CLIENT_AXIS]
    if m % shards:
        raise ValueError(
            f"cohort size {m} must divide evenly over the {shards}-way "
            f"{CLIENT_AXIS!r} mesh axis")

    # the logical-axis rules of sharding/api map the cohort dim to the mesh
    ctx_sh = ShardingContext(mesh=mesh, rules={CLIENT_AXIS: [CLIENT_AXIS]})
    cspec = ctx_sh.spec((CLIENT_AXIS,))       # shard leading (cohort) dim
    rspec = PartitionSpec()                   # replicated

    channel = uplink_channel(fed)

    if policy is None:
        def shard_body(base, params, rnd, hist, keys, cx, cy, sizes, sel,
                       train, ka, ids):
            # ids: this shard's slice of the cohort's ABSOLUTE client ids
            # — fading gains are drawn for the full federation and indexed
            # by them, so a sharded cohort sees exactly the flat gains;
            # the post-aggregate AWGN keys only on (seed, tag, round), so
            # the post-psum draw is replicated across shards
            return _cohort_round(model._replace(base=base), fed, strategy,
                                 params, rnd, hist, cx, cy, sizes, keys,
                                 sel, train, ka, axis_name=CLIENT_AXIS,
                                 channel=channel, client_ids=ids, n_total=n)

        cohort_round = jax.shard_map(
            shard_body, mesh=mesh,
            in_specs=(rspec, rspec, rspec, cspec, cspec, cspec, cspec,
                      cspec, cspec, cspec, cspec, cspec),
            out_specs=(rspec, cspec))

        @jax.jit
        def run_span(state, sel_chunk, train_chunk, k_active, cohort_idx,
                     data, base):
            def step(st, xs):
                sel, train, idx = xs
                # at full participation the cohort IS the federation
                # (CohortSampler degenerates to arange — pinned in tests)
                # and the takes/scatters below are identity updates; a
                # dedicated branch that skipped them benchmarked SLOWER
                # than letting XLA see the uniform gather/scatter round
                # (benchmarks/sharded_clients.py), so there is one path
                take = functools.partial(jnp.take, indices=idx, axis=0)
                with scope(LOCAL_SGD):
                    key, keys = _round_keys(st["key"], n)
                    cohort = (take(keys), take(data.x), take(data.y),
                              take(data.sizes))
                with scope(HISTORY):
                    hist = strategy.gather_history(st, idx)
                new_params, new_hist = cohort_round(
                    base, st["params"], st["round"], hist, *cohort,
                    take(sel), take(train), take(k_active), idx)
                with scope(HISTORY):
                    new_state = strategy.scatter_history(st, idx, new_hist)
                new_state.update(params=new_params, round=st["round"] + 1,
                                 key=key)
                return new_state, None

            state, _ = jax.lax.scan(step, state,
                                    (sel_chunk, train_chunk, cohort_idx))
            return state

        return _bind(run_span, data=data, base=model.base)

    # ---- policy mode: decide per-shard on gathered device rows ----------
    from repro.core.budget import budget_ctx
    from repro.system.devices import advance_devices, update_ledger

    _check_profile(profile, data)

    def shard_body(base, params, rnd, hist, keys, cx, cy, sizes, sel, ka,
                   pol, dev, prof, ids):
        with scope(POLICY):
            ctx = budget_ctx(prof, dev, rnd, ids, sel, profile.seed)
            train, new_pol = policy.decide(pol, ctx)
            train = train & sel
        new_params, new_hist = _cohort_round(
            model._replace(base=base), fed, strategy, params, rnd, hist,
            cx, cy, sizes, keys,
            sel, train, ka, axis_name=CLIENT_AXIS, energy=dev["energy"],
            channel=channel, client_ids=ids, n_total=n)
        return new_params, new_hist, new_pol, train

    cohort_round = jax.shard_map(
        shard_body, mesh=mesh,
        in_specs=(rspec, rspec, rspec, cspec, cspec, cspec, cspec, cspec,
                  cspec, cspec, cspec, cspec, cspec, cspec),
        out_specs=(rspec, cspec, cspec, cspec))

    @jax.jit
    def run_span(state, sel_chunk, k_active, cohort_idx, data, rows, base):
        all_ids = jnp.arange(n, dtype=jnp.int32)

        def step(st, xs):
            sel, idx = xs
            # one path for every cohort size — see the mask-mode note above
            take = functools.partial(jnp.take, indices=idx, axis=0)
            with scope(LOCAL_SGD):
                key, keys = _round_keys(st["key"], n)
                cohort = (take(keys), take(data.x), take(data.y),
                          take(data.sizes))
            with scope(HISTORY):
                hist = strategy.gather_history(st, idx)
            with scope(POLICY):
                decide_rows = (jax.tree.map(take, st["policy"]),
                               jax.tree.map(take, st["device"]),
                               jax.tree.map(take, rows))
            new_params, new_hist, new_pol, train_c = cohort_round(
                base, st["params"], st["round"], hist, *cohort,
                take(sel), take(k_active), *decide_rows, idx)
            with scope(HISTORY):
                new_state = strategy.scatter_history(st, idx, new_hist)
            with scope(POLICY):
                new_state["policy"] = jax.tree.map(
                    lambda full, part: full.at[idx].set(part),
                    st["policy"], new_pol)
                # off-cohort clients behave exactly as unselected clients
                # of a full round: no training spend, no ledger entry —
                # but their devices keep harvesting and their load keeps
                # evolving
                eff_sel = sel & jnp.zeros((n,), bool).at[idx].set(True)
                train_full = jnp.zeros((n,), bool).at[idx].set(train_c)
                new_state["device"] = advance_devices(
                    rows, st["device"], train_full, st["round"], all_ids,
                    profile.seed)
                new_state["ledger"] = update_ledger(st["ledger"], rows,
                                                    eff_sel, train_full)
            new_state.update(params=new_params, round=st["round"] + 1,
                             key=key)
            return new_state, None

        state, _ = jax.lax.scan(step, state, (sel_chunk, cohort_idx))
        return state

    return _bind(run_span, data=data, rows=profile.rows(), base=model.base)


# ---------------------------------------------------------------------------
# hierarchical two-tier executor: client → edge aggregator → server
# ---------------------------------------------------------------------------


def _tree_rows(tree: PyTree, sl) -> PyTree:
    """Slice the leading (client) axis of every leaf."""
    return jax.tree.map(lambda x: x[sl], tree)


def _slice_ctx(ctx: RoundCtx, sl) -> RoundCtx:
    """Restrict a round context to one edge's block of client rows."""
    import dataclasses
    return dataclasses.replace(
        ctx, sel_mask=ctx.sel_mask[sl], train_mask=ctx.train_mask[sl],
        k_active=ctx.k_active[sl],
        stale_delta=_tree_rows(ctx.stale_delta, sl),
        trained_delta=_tree_rows(ctx.trained_delta, sl),
        energy=None if ctx.energy is None else ctx.energy[sl],
        edge_id=None if ctx.edge_id is None else ctx.edge_id[sl])


def make_hierarchical_span_runner(model: Classifier, data: FederatedData,
                                  fed: FedConfig, topo, *, mesh=None,
                                  policy=None, profile=None):
    """Two-tier executor: ``run_span(state, sel_chunk, train_chunk,
    k_active)`` advances a (C, N) span of plan masks through the
    client→edge→server topology ``topo``
    (:class:`repro.core.hierarchy.EdgeTopology`).

    Round semantics (one scan step):

    * every client trains (or estimates) against **its edge aggregator's
      model** — the carry holds an (E,)-stacked ``edge_params`` tree next
      to the server's ``params``;
    * on an intra-edge round (``(t+1) % edge_period != 0``) each edge
      aggregates ONLY its own members — ``strategy.aggregate`` runs on the
      edge's block with the edge-restricted aggregation mask, so
      cc/fednova/s2 estimation semantics hold per edge — and advances its
      edge model; the server sees nothing;
    * on a sync round (every ``edge_period``-th) the final intra-edge
      aggregation is folded into the server merge: client i uploads
      ``y_i = Δ_i + (x_{e(i)} − G)`` (its fresh delta on top of its edge's
      period displacement) and the server takes the flat masked mean of
      the uploads — exactly the aggregation-mass-weighted average of edge
      models (the nested-mean identity of :mod:`repro.core.hierarchy`),
      computed with the SAME primitive the flat executors use. All edges
      then reset to the new global model.

    Collapse guarantees (the oracle for ``tests/test_executor_matrix.py``):
    with ``edge_period == 1`` the edge displacement is exactly zero, so
    the sync round IS a flat round bit-for-bit; with a single edge the
    edge and the server coincide, so every round runs the flat update on
    the edge model and the sync is an identity (the global model stays
    fresh every round).

    ``mesh`` is a 1-D ``("edges",)`` mesh
    (:func:`repro.launch.mesh.make_edge_mesh`; defaults to the largest
    visible device count that divides E). With more than one shard the
    topology must be contiguous-uniform so whole edges land on one device:
    intra-edge rounds then run with ZERO cross-device traffic — each
    edge's block aggregation reads exactly its own rows, making results
    bit-identical across shard counts — and sync rounds ``all_gather`` the
    uploads so every shard computes the identical full-federation merge
    (the gather IS the edge→server uplink).

    With ``policy`` + ``profile`` (policy mode, the Session default) the
    signature drops the train chunk — ``run_span(state, sel_chunk,
    k_active)`` — and the budget policy decides per round from the carried
    device state, exactly as in the flat policy executors; ``BudgetCtx``
    and ``RoundCtx`` carry each client's edge id so policies/strategies
    can condition on the gateway.
    """
    import dataclasses

    from jax.sharding import PartitionSpec
    from repro.launch.mesh import best_edge_shards, make_edge_mesh

    if (policy is None) != (profile is None):
        raise ValueError("policy mode needs BOTH policy and profile "
                         "(got exactly one)")
    strategy = fed.resolve()
    n = data.n_clients
    if topo.n_clients != n:
        raise ValueError(f"topology covers {topo.n_clients} clients, data "
                         f"has {n}")
    n_edges, period = topo.n_edges, topo.edge_period
    if mesh is None:
        # irregular layouts cannot place whole edges per device — they run
        # single-shard; uniform ones spread edges over the visible devices
        mesh = make_edge_mesh(best_edge_shards(n_edges)
                              if topo.is_contiguous_uniform else 1)
    if EDGE_AXIS not in mesh.axis_names:
        raise ValueError(f"mesh must carry an {EDGE_AXIS!r} axis, got "
                         f"{mesh.axis_names}")
    shards = dict(zip(mesh.axis_names, mesh.devices.shape))[EDGE_AXIS]
    if n_edges % shards:
        raise ValueError(
            f"{n_edges} edges must divide evenly over the {shards}-way "
            f"{EDGE_AXIS!r} mesh axis")
    uniform = topo.is_contiguous_uniform
    if shards > 1 and not uniform:
        raise ValueError(
            "a multi-shard edge mesh needs a contiguous-uniform topology "
            "(N % E == 0, consecutive equal blocks) so whole edges land "
            "on one device; run irregular topologies on a 1-shard mesh")
    e_local = n_edges // shards
    n_local = n // shards           # uniform guaranteed when shards > 1
    block = n // n_edges if uniform else None
    if uniform:
        # identical on every shard: local client row r belongs to the
        # shard's local edge r // block
        local_assign = jnp.asarray(np.arange(n_local) // block, jnp.int32)
    else:
        local_assign = jnp.asarray(topo.assignment, jnp.int32)

    if profile is not None:
        _check_profile(profile, data)

    if shards > 1:
        def local_rows(x):
            """This shard's client rows of a replicated (N, ...) array."""
            i = jax.lax.axis_index(EDGE_AXIS)
            return jax.lax.dynamic_slice_in_dim(x, i * n_local, n_local)

        def gather(x):
            return jax.lax.all_gather(x, EDGE_AXIS, axis=0, tiled=True)

        def edge_ids_of():
            return (local_assign
                    + jax.lax.axis_index(EDGE_AXIS) * e_local)
    else:
        def local_rows(x):
            return x

        def gather(x):
            return x

        def edge_ids_of():
            return jnp.asarray(topo.assignment, jnp.int32)

    hist_keys = strategy.history_keys
    channel = uplink_channel(fed)

    if shards > 1:
        def client_ids_of():
            """Absolute client ids of this shard's rows (uniform layout:
            shard s holds the contiguous block s·n_local ...)."""
            return (jax.lax.axis_index(EDGE_AXIS) * n_local
                    + jnp.arange(n_local, dtype=jnp.int32))
    else:
        def client_ids_of():
            return jnp.arange(n, dtype=jnp.int32)

    def hier_round(base, G, rnd, edge_params, hist, keys, cx, cy, sizes,
                   sel, train, k_active, energy=None):
        """One two-tier round over this shard's clients and edges; returns
        (new_G replicated, new_edge_params, new_hist)."""
        edge_ids = edge_ids_of()
        with scope(LOCAL_SGD):
            client_start = jax.tree.map(lambda x: x[local_assign],
                                        edge_params)
            local = _train_clients(model._replace(base=base), fed,
                                   client_start, keys, cx, cy,
                                   sizes, k_active,
                                   prox=strategy.prox_coeff(),
                                   dual=strategy.local_dual(hist))
            trained_delta = tree_sub(local, client_start)
        with scope(ESTIMATE):
            stale_delta = tree_sub(hist["prev_local"], client_start)
            stale_delta = masked_select(hist["trained_ever"], stale_delta,
                                        tree_zeros_like(stale_delta))
            ctx = RoundCtx(sel_mask=sel, train_mask=train, k_active=k_active,
                           round=rnd, tau=fed.tau, stale_delta=stale_delta,
                           trained_delta=trained_delta, axis_name=None,
                           energy=energy, edge_id=edge_ids)
            est = strategy.estimate(hist, ctx)
            delta_i = masked_select(train, trained_delta, est)
        with scope(AGGREGATE):
            aggf = strategy.agg_mask(ctx).astype(jnp.float32)
            # client→edge uplink fading: one gain draw per client per
            # round, shared by whichever tier consumes the upload this
            # round (the history still stores the true deltas — see
            # _cohort_round)
            up_i = (delta_i if channel is None else
                    channel.fade(delta_i, rnd, client_ids_of(), n, TAG_C2E))

        # ---- intra-edge tier: each edge aggregates only its members ---
        # Uniform layouts slice each edge's own block, so total work stays
        # O(N) and nothing crosses shards; irregular layouts (1-shard
        # only) pay E full-width masked aggregations — the cost of
        # arbitrary assignments at small scale.
        def intra_update(edge_params):
            parts = []
            for e in range(e_local):
                if uniform:
                    sl = slice(e * block, (e + 1) * block)
                    d_e = strategy.aggregate(_tree_rows(up_i, sl),
                                             aggf[sl], _slice_ctx(ctx, sl))
                else:
                    member = (local_assign == e).astype(jnp.float32)
                    d_e = strategy.aggregate(up_i, aggf * member, ctx)
                if channel is not None:
                    # independent AWGN per edge receiver, keyed on the
                    # GLOBAL edge id so results are shard-layout-invariant
                    ge = (e if shards == 1 else
                          e + jax.lax.axis_index(EDGE_AXIS) * e_local)
                    d_e = channel.corrupt(d_e, rnd, TAG_C2E, sub=ge)
                parts.append(tree_add(tree_index(edge_params, e), d_e))
            return tree_stack(parts)

        if n_edges == 1:
            # the edge IS the server: the sync is an identity, performed
            # every round so the global model never goes stale — this is
            # exactly the flat executor's update, bit-for-bit
            with scope(AGGREGATE):
                ep_intra = intra_update(edge_params)
            return tree_index(ep_intra, 0), ep_intra, _roll_hist(
                hist, ctx, trained_delta, local, est, sel, train)

        # ---- sync tier: fold the last edge aggregation into the merge -
        def sync_update(edge_params):
            if period == 1:
                y = delta_i    # edge displacement is exactly zero
            else:
                y = tree_add(delta_i,
                             tree_sub(client_start,
                                      tree_broadcast_clients(G, n_local)))
            if channel is not None:
                # the client transmits the WHOLE upload y_i (fresh delta +
                # edge displacement) over the air — same gain draw as the
                # intra tier, applied to the full signal
                y = channel.fade(y, rnd, client_ids_of(), n, TAG_C2E)
            ctx_full = dataclasses.replace(
                ctx, sel_mask=gather(sel), train_mask=gather(train),
                k_active=gather(k_active),
                stale_delta=jax.tree.map(gather, stale_delta),
                trained_delta=jax.tree.map(gather, trained_delta),
                energy=None if energy is None else gather(energy),
                edge_id=gather(edge_ids))
            d_global = strategy.aggregate(jax.tree.map(gather, y),
                                          gather(aggf), ctx_full)
            if channel is not None:
                # two independent hops — client→edge, then edge→server —
                # both keyed only on (seed, tag, round), so every shard
                # computes the identical replicated draws
                d_global = channel.corrupt(d_global, rnd, TAG_C2E)
                d_global = channel.corrupt(d_global, rnd, TAG_E2S)
            G_sync = tree_add(G, d_global)
            return G_sync, tree_broadcast_clients(G_sync, e_local)

        with scope(AGGREGATE):
            if period == 1:
                new_G, new_ep = sync_update(edge_params)
            else:
                # lax.cond, NOT a where-select: the all_gather + full
                # merge of the sync branch must only execute on period
                # boundaries — intra-edge rounds stay collective-free (the
                # predicate is replicated, so no shard can diverge)
                is_sync = ((rnd + 1) % period) == 0
                new_G, new_ep = jax.lax.cond(
                    is_sync, sync_update,
                    lambda ep: (G, intra_update(ep)), edge_params)
        return new_G, new_ep, _roll_hist(hist, ctx, trained_delta, local,
                                         est, sel, train)

    def _roll_hist(hist, ctx, trained_delta, local, est, sel, train):
        with scope(HISTORY):
            deltas, prev_local = strategy.update_history(
                hist, ctx, trained_delta, local, est)
            out = {"deltas": deltas, "prev_local": prev_local,
                   "trained_ever": hist["trained_ever"] | (sel & train)}
            out.update(strategy.update_extra_history(
                hist, ctx, trained_delta, local, est))
        return out

    rspec, sspec = PartitionSpec(), PartitionSpec(EDGE_AXIS)
    state_spec = {"params": rspec, "round": rspec, "key": rspec,
                  "edge_params": sspec}
    state_spec.update({k: sspec for k in hist_keys})
    if policy is not None:
        state_spec.update(policy=sspec, device=sspec, ledger=sspec)
    chunk_spec = PartitionSpec(None, EDGE_AXIS)

    if policy is None:
        def span_body(state, sel_chunk, train_chunk, k_active, cx, cy,
                      sizes, base):
            def step(st, xs):
                sel, train = xs
                with scope(LOCAL_SGD):
                    key, keys = _round_keys(st["key"], n)
                new_G, new_ep, new_hist = hier_round(
                    base, st["params"], st["round"], st["edge_params"],
                    {k: st[k] for k in hist_keys}, local_rows(keys),
                    cx, cy, sizes, sel, train, k_active)
                return {"params": new_G, "edge_params": new_ep,
                        **new_hist, "round": st["round"] + 1,
                        "key": key}, None

            state, _ = jax.lax.scan(step, state, (sel_chunk, train_chunk))
            return state

        if shards > 1:
            # check_vma=False: params/round/key stay replicated by
            # construction (the merge runs on all_gather'ed values
            # identically on every shard), but all_gather's result is typed
            # varying, so the sync/intra-edge lax.cond branches and the
            # scan carry would not type-check
            span_body = jax.shard_map(
                span_body, mesh=mesh,
                in_specs=(state_spec, chunk_spec, chunk_spec, sspec,
                          sspec, sspec, sspec, rspec),
                out_specs=state_spec, check_vma=False)

        @jax.jit
        def run_span(state, sel_chunk, train_chunk, k_active, data, base):
            return span_body(state, sel_chunk, train_chunk, k_active,
                             data.x, data.y, data.sizes, base)

        return _bind(run_span, data=data, base=model.base)

    # ---- policy mode: in-loop decisions over per-edge device state ----
    from repro.core.budget import budget_ctx
    from repro.system.devices import advance_devices, update_ledger

    def span_body(state, sel_chunk, k_active, cx, cy, sizes, rows, base):
        prof_l = jax.tree.map(local_rows, rows)
        ids_l = local_rows(jnp.arange(n, dtype=jnp.int32))

        def step(st, sel):
            with scope(LOCAL_SGD):
                key, keys = _round_keys(st["key"], n)
            dev = st["device"]
            with scope(POLICY):
                bctx = budget_ctx(prof_l, dev, st["round"], ids_l, sel,
                                  profile.seed, edge_ids=edge_ids_of())
                train, new_pol = policy.decide(st["policy"], bctx)
                train = train & sel
            new_G, new_ep, new_hist = hier_round(
                base, st["params"], st["round"], st["edge_params"],
                {k: st[k] for k in hist_keys}, local_rows(keys),
                cx, cy, sizes, sel, train, k_active,
                energy=dev["energy"])
            with scope(POLICY):
                spent = sel & train
                new_dev = advance_devices(prof_l, dev, spent, st["round"],
                                          ids_l, profile.seed)
                new_ledger = update_ledger(st["ledger"], prof_l, sel, train)
            return {"params": new_G, "edge_params": new_ep, **new_hist,
                    "policy": new_pol, "device": new_dev,
                    "ledger": new_ledger,
                    "round": st["round"] + 1, "key": key}, None

        state, _ = jax.lax.scan(step, state, sel_chunk)
        return state

    if shards > 1:
        # check_vma=False for the same all_gather typing as above; the
        # profile rows enter replicated and each shard slices its own
        span_body = jax.shard_map(
            span_body, mesh=mesh,
            in_specs=(state_spec, chunk_spec, sspec, sspec, sspec, sspec,
                      rspec, rspec),
            out_specs=state_spec, check_vma=False)

    @jax.jit
    def run_span(state, sel_chunk, k_active, data, rows, base):
        return span_body(state, sel_chunk, k_active, data.x, data.y,
                         data.sizes, rows, base)

    return _bind(run_span, data=data, rows=profile.rows(), base=model.base)


def span_boundaries(rounds: int, eval_every: int) -> list[int]:
    """Eval checkpoints of the classic loop: every ``eval_every`` rounds
    plus the final round — spans run scan-fused between them.

    ``eval_every > rounds`` means a single span ending at the final round;
    non-positive values are rejected (they used to silently produce a
    bogus round-0 boundary / negative stops).
    """
    if rounds < 1:
        raise ValueError(f"rounds must be >= 1, got {rounds}")
    if eval_every < 1:
        raise ValueError(f"eval_every must be >= 1, got {eval_every}")
    stops = list(range(eval_every, rounds + 1, eval_every))
    if not stops or stops[-1] != rounds:
        stops.append(rounds)
    return stops
