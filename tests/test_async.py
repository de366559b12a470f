"""Async executor + sharded int8 history store.

Covers what the executor matrix doesn't: input validation of the async
knobs (spec, config, arrival simulator), the arrival process's structural
invariants (one in-flight update per client, delivery ⊆ dispatch, K-merge
cadence), the int8 history store's layout/round-trip/memory math, the
int8-vs-dense numerical budget under real staleness, mid-run checkpoint
resume bit-identity (including the in-flight buffer), and the
ledger-driven arrival accounting behind ``Session.cost_report`` /
``Session.staleness_summary``.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec, Session
from repro.checkpoint.store import save_fed_state
from repro.core.async_rounds import AsyncConfig, staleness_weights
from repro.core.compress import dequantize_rows, quantize_rows
from repro.core.history_store import TILE, HistoryStore, padded_width
from repro.system.devices import make_profile, simulate_arrivals

N = 4


def _spec(**kw) -> ExperimentSpec:
    base = dict(dataset="gaussian", n_samples=256, dim=8, n_classes=4,
                n_clients=N, budget="power", beta=2, model="mlp", width=4,
                local_steps=2, batch_size=16, lr=0.1, schedule="adhoc",
                rounds=6, eval_every=2, seed=0, executor="async")
    base.update(kw)
    return ExperimentSpec(**base)


# ---------------------------------------------------------------------------
# satellite: input validation + regression errors
# ---------------------------------------------------------------------------


def test_async_config_rejects_bad_values():
    with pytest.raises(ValueError, match="buffer size"):
        AsyncConfig(buffer_size=0)
    with pytest.raises(ValueError, match="buffer size"):
        AsyncConfig(buffer_size=1.5)
    with pytest.raises(ValueError, match="staleness_decay"):
        AsyncConfig(staleness_decay=0.0)
    with pytest.raises(ValueError, match="staleness_decay"):
        AsyncConfig(staleness_decay=1.2)
    with pytest.raises(ValueError, match="schedule"):
        AsyncConfig(schedule="exponential")
    with pytest.raises(ValueError, match="latency"):
        AsyncConfig(latency=-1.0)
    with pytest.raises(ValueError, match="jitter"):
        AsyncConfig(jitter=-0.1)
    with pytest.raises(ValueError, match="history_store"):
        AsyncConfig(history_store="f16")
    # the boundary values are legal
    AsyncConfig(buffer_size=1, staleness_decay=1.0, latency=0.0, jitter=0.0)


def test_spec_validates_async_fields():
    with pytest.raises(ValueError, match="buffer size"):
        _spec(async_buffer=0)
    with pytest.raises(ValueError, match="staleness_decay"):
        _spec(staleness_decay=2.0)
    with pytest.raises(ValueError, match="latency"):
        _spec(async_latency=-1.0)
    with pytest.raises(ValueError, match="use_fused"):
        _spec(use_fused=True)
    with pytest.raises(ValueError, match="history_store"):
        _spec(history_store="f16")
    # async knobs on a synchronous executor are a config error, not a
    # silent no-op
    with pytest.raises(ValueError, match="executor='async'"):
        _spec(executor="scan", async_buffer=4)
    with pytest.raises(ValueError, match="executor='async'"):
        _spec(executor="python", history_store="int8")


def test_spec_round_trips_async_fields():
    spec = _spec(async_buffer=3, staleness_decay=0.7,
                 staleness_schedule="polynomial", async_latency=2.0,
                 async_jitter=0.5, history_store="int8")
    again = ExperimentSpec.from_json(spec.to_json())
    assert again == spec
    cfg = again.async_config()
    assert cfg == AsyncConfig(buffer_size=3, staleness_decay=0.7,
                              schedule="polynomial", latency=2.0,
                              jitter=0.5, history_store="int8")
    assert _spec().replace(executor="scan").async_config() is None


def test_simulate_arrivals_rejects_bad_values():
    prof = make_profile("budget", np.full(N, 0.5))
    sel = np.ones((3, N), bool)
    with pytest.raises(ValueError, match="buffer size"):
        simulate_arrivals(prof, sel, buffer_size=0)
    with pytest.raises(ValueError, match="latency"):
        simulate_arrivals(prof, sel, latency=-1.0)
    with pytest.raises(ValueError, match="jitter"):
        simulate_arrivals(prof, sel, jitter=-0.5)
    with pytest.raises(ValueError, match="bool table"):
        simulate_arrivals(prof, np.ones(N, bool))
    with pytest.raises(ValueError, match="clients"):
        simulate_arrivals(prof, np.ones((3, N + 1), bool))


def test_session_rejects_async_cfg_on_sync_executor():
    from repro.core.rounds import FedConfig
    from repro.core.schedules import make_plan
    from repro.data.federated import build_federated
    from repro.data.partition import partition_gamma
    from repro.data.synthetic import make_dataset, train_test_split
    from repro.models.simple import make_classifier
    ds = make_dataset("gaussian", n=64, dim=8, n_classes=4, seed=0)
    tr, _ = train_test_split(ds)
    fd = build_federated(tr, partition_gamma(tr, N, gamma=0.5, seed=0))
    model = make_classifier("mlp", input_shape=(8,), n_classes=4, width=4)
    with pytest.raises(ValueError, match="executor='async'"):
        Session(model, fd, FedConfig(strategy="cc"),
                make_plan("full", np.ones(N), 2), executor="scan",
                async_cfg=AsyncConfig())


# ---------------------------------------------------------------------------
# arrival-process simulator invariants
# ---------------------------------------------------------------------------


def test_zero_lag_collapses_to_selection():
    prof = make_profile("budget", np.full(N, 0.5), seed=3)
    rng = np.random.default_rng(0)
    sel = rng.random((12, N)) < 0.6
    sched = simulate_arrivals(prof, sel, buffer_size=1)
    np.testing.assert_array_equal(sched.dispatch, sel)
    np.testing.assert_array_equal(sched.deliver, sel)
    np.testing.assert_array_equal(sched.merge, sel.any(axis=1))


def test_one_in_flight_update_per_client():
    """Between a dispatch and its delivery the client never re-dispatches,
    and every delivery has a matching earlier (or same-round) dispatch."""
    prof = make_profile("budget", np.full(N, 0.5), load_mean=0.3,
                        load_jitter=0.2, seed=3)
    sel = np.ones((30, N), bool)
    sched = simulate_arrivals(prof, sel, buffer_size=2, latency=2.0,
                              jitter=1.0)
    in_flight = np.zeros(N, bool)
    pending = np.zeros(N, bool)
    for t in range(30):
        assert not (sched.dispatch[t] & (in_flight | pending)).any()
        in_flight |= sched.dispatch[t]
        assert (sched.deliver[t] <= in_flight).all()
        in_flight &= ~sched.deliver[t]
        pending |= sched.deliver[t]
        if sched.merge[t]:
            assert pending.sum() >= 2          # the K-arrival trigger
            pending[:] = False
    # cumulative conservation: every delivery was dispatched
    assert sched.deliver.sum() <= sched.dispatch.sum()


def test_latency_scales_with_device_speed():
    """Slow devices (small flops_rate) deliver later than fast ones under
    the same nominal latency — the arrival process is profile-driven."""
    p = np.array([1.0, 1.0, 0.25, 0.25])
    prof = make_profile("budget", p, seed=0)
    sel = np.ones((40, N), bool)
    sched = simulate_arrivals(prof, sel, buffer_size=1, latency=2.0)
    arrivals = sched.deliver.sum(axis=0)
    assert arrivals[0] > arrivals[2], (
        f"fast client delivered {arrivals[0]}x vs slow {arrivals[2]}x")


def test_merge_cadence_respects_buffer_size():
    prof = make_profile("budget", np.full(N, 0.5), seed=1)
    full = np.ones((20, N), bool)
    for k in (1, 3, N):
        # zero-lag full participation: N arrivals land every round, ≥ any
        # legal K, so the buffer flushes every round
        assert simulate_arrivals(prof, full, buffer_size=k).merge.all()
    # one arrival per round (round-robin singletons): merges every K-th
    sel = np.zeros((20, N), bool)
    sel[np.arange(20), np.arange(20) % N] = True
    sched = simulate_arrivals(prof, sel, buffer_size=3)
    np.testing.assert_array_equal(sched.merge,
                                  np.arange(1, 21) % 3 == 0)
    # a buffer larger than the federation could never fill — rejected
    with pytest.raises(ValueError, match="n_clients"):
        simulate_arrivals(prof, full, buffer_size=N + 1)
    with pytest.raises(ValueError, match="n_clients"):
        _spec(async_buffer=N + 1)


# ---------------------------------------------------------------------------
# staleness-decay schedules
# ---------------------------------------------------------------------------


def test_staleness_weights_shapes_and_monotonicity():
    s = jnp.arange(6, dtype=jnp.int32)
    for schedule in ("geometric", "polynomial"):
        w = np.asarray(staleness_weights(schedule, 0.8, s))
        assert w[0] == 1.0                     # exact — the collapse pin
        assert (np.diff(w) < 0).all()          # strictly decaying
        assert (w > 0).all()
    # decay=1.0 means no decay at all, any staleness
    w = np.asarray(staleness_weights("geometric", 1.0, s))
    np.testing.assert_array_equal(w, 1.0)
    with pytest.raises(ValueError, match="schedule"):
        staleness_weights("exponential", 0.9, s)


# ---------------------------------------------------------------------------
# history store: layout, round-trip, memory math
# ---------------------------------------------------------------------------


def test_history_store_validation():
    with pytest.raises(ValueError, match="kind"):
        HistoryStore(4, 512, kind="f16")
    with pytest.raises(ValueError, match="n_clients"):
        HistoryStore(0, 512)
    with pytest.raises(ValueError, match="width"):
        HistoryStore(4, 0)
    store = HistoryStore(4, 512, kind="int8")
    with pytest.raises(ValueError, match="carry"):
        store.like({"rows": None})
    HistoryStore(4, 512, kind="dense").like({"rows": None})


def test_padded_width_tiles():
    assert padded_width(1) == TILE
    assert padded_width(TILE) == TILE
    assert padded_width(TILE + 1) == 2 * TILE


@pytest.mark.parametrize("kind", ["dense", "int8"])
def test_history_store_read_write_round_trip(kind):
    store = HistoryStore(6, TILE, kind=kind)
    carry = store.init()
    store.like(carry)
    np.testing.assert_array_equal(np.asarray(store.read(carry)), 0.0)
    rng = np.random.default_rng(0)
    rows = jnp.asarray(rng.standard_normal((6, TILE)), jnp.float32)
    mask = jnp.asarray([True, False, True, True, False, False])
    new = store.write(carry, mask, rows)
    got = np.asarray(store.read(new))
    atol = 0.0 if kind == "dense" else np.abs(rows).max() / 127 + 1e-6
    np.testing.assert_allclose(got[np.asarray(mask)],
                               np.asarray(rows)[np.asarray(mask)],
                               atol=atol)
    np.testing.assert_array_equal(got[~np.asarray(mask)], 0.0)
    # cohort gather matches the full read
    idx = jnp.asarray([0, 3])
    np.testing.assert_array_equal(np.asarray(store.read(new, idx)),
                                  got[np.asarray(idx)])
    # cohort scatter lands only at idx
    upd = jnp.ones((2, TILE), jnp.float32)
    scattered = store.scatter(new, idx, upd)
    got2 = np.asarray(store.read(scattered))
    np.testing.assert_allclose(got2[np.asarray(idx)], 1.0,
                               atol=atol if kind == "int8" else 0.0)
    np.testing.assert_array_equal(got2[1], got[1])


def test_int8_masked_write_keeps_unmasked_bits_verbatim():
    """The bit-identity contract behind checkpoint resume: rows OUTSIDE
    the write mask keep their stored payload/scale bits exactly — no
    requantization drift for clients that didn't deliver."""
    store = HistoryStore(4, TILE, kind="int8")
    rng = np.random.default_rng(1)
    carry = store.write(store.init(), jnp.ones(4, bool),
                        jnp.asarray(rng.standard_normal((4, TILE)),
                                    jnp.float32))
    mask = jnp.asarray([True, False, False, True])
    new = store.write(carry, mask,
                      jnp.asarray(rng.standard_normal((4, TILE)),
                                  jnp.float32))
    keep = ~np.asarray(mask)
    np.testing.assert_array_equal(np.asarray(new["payload"])[keep],
                                  np.asarray(carry["payload"])[keep])
    np.testing.assert_array_equal(np.asarray(new["scales"])[keep],
                                  np.asarray(carry["scales"])[keep])


def test_history_store_memory_math():
    """The acceptance bound: at P = 1024 the int8 store holds ≤ 30% of the
    dense f32 bytes — N·P + 4·N vs 4·N·P."""
    for n in (100, 10_000, 100_000):
        dense = HistoryStore(n, 1024, kind="dense")
        q8 = HistoryStore(n, 1024, kind="int8")
        assert dense.nbytes() == 4 * n * 1024
        assert q8.nbytes() == n * 1024 + 4 * n
        assert q8.nbytes() / dense.nbytes() <= 0.30
    # carry_bytes agrees with the layout math on materialized carries
    store = HistoryStore(8, TILE, kind="int8")
    assert HistoryStore.carry_bytes(store.init()) == store.nbytes()
    dense = HistoryStore(8, TILE, kind="dense")
    assert HistoryStore.carry_bytes(dense.init()) == dense.nbytes()


def test_q8_gather_scatter_ops_match_reference():
    from repro.kernels.ops import q8_gather_rows, q8_scatter_rows
    rng = np.random.default_rng(2)
    rows = jnp.asarray(rng.standard_normal((8, 64)), jnp.float32)
    payload, scales = quantize_rows(rows)
    idx = jnp.asarray([1, 5, 2])
    got = q8_gather_rows(payload, scales, idx)
    want = dequantize_rows(payload, scales)[idx]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    upd = jnp.asarray(rng.standard_normal((3, 64)), jnp.float32)
    new_p, new_s = q8_scatter_rows(payload, scales, idx, upd)
    ref_p, ref_s = quantize_rows(upd)
    np.testing.assert_array_equal(np.asarray(new_p[idx]), np.asarray(ref_p))
    np.testing.assert_array_equal(np.asarray(new_s[idx]), np.asarray(ref_s))
    keep = np.setdiff1d(np.arange(8), np.asarray(idx))
    np.testing.assert_array_equal(np.asarray(new_p)[keep],
                                  np.asarray(payload)[keep])


def test_history_store_shard_requires_divisibility():
    from repro.launch.mesh import make_client_mesh
    store = HistoryStore(len(jax.devices()) * 2 + 1, TILE, kind="int8")
    if len(jax.devices()) > 1:
        # an explicit all-device mesh: the default picks the largest
        # device count that divides N, which always fits
        with pytest.raises(ValueError, match="divide"):
            store.shard(store.init(), mesh=make_client_mesh())
    even = HistoryStore(len(jax.devices()) * 2, TILE, kind="int8")
    sharded = even.shard(even.init())
    assert set(sharded) == {"payload", "scales"}


# ---------------------------------------------------------------------------
# int8 store vs dense under real staleness (the non-collapse regime)
# ---------------------------------------------------------------------------


def test_int8_store_matches_dense_within_q8_bounds():
    spec = dict(async_buffer=2, async_latency=1.0, async_jitter=0.5,
                staleness_decay=0.8)
    dense = Session.from_spec(_spec(**spec)).run()
    q8 = Session.from_spec(_spec(**spec, history_store="int8")).run()
    # identical arrival process, near-identical numerics (q8 error only)
    assert dense.staleness_summary() == q8.staleness_summary()
    for a, b in zip(jax.tree.leaves(dense.state["params"]),
                    jax.tree.leaves(q8.state["params"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-2)
    np.testing.assert_allclose(dense.metrics.series("test_acc"),
                               q8.metrics.series("test_acc"), atol=2.5e-2)
    assert set(q8.state["deltas"]) == {"payload", "scales"}


# ---------------------------------------------------------------------------
# checkpoint: mid-run resume bit-identity (async carry + int8 store)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("store", ["dense", "int8"])
def test_mid_run_resume_is_bit_identical(store):
    """Kill the run mid-span — with updates in flight AND buffered — and
    the restored session must finish with bit-identical state + metrics."""
    spec = _spec(async_buffer=3, async_latency=2.0, async_jitter=1.0,
                 staleness_decay=0.7, history_store=store, rounds=8)
    with tempfile.TemporaryDirectory() as d:
        s1 = Session.from_spec(spec, ckpt_dir=d)
        s1.run(3)
        carry = s1.state["async"]
        s1.save()
        s1.run()
        s2 = Session.restore_from(d)
        # the in-flight/buffer machinery really was mid-work at the save
        np.testing.assert_array_equal(
            np.asarray(carry["pending_mask"]) |
            np.asarray(carry["pull_round"]) >= 0, True)
        s2.run()
        assert s1.metrics.series("test_acc") == s2.metrics.series("test_acc")
        for key in s1.state:
            for a, b in zip(jax.tree.leaves(s1.state[key]),
                            jax.tree.leaves(s2.state[key])):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                              err_msg=f"{store}/{key}")


def test_save_refuses_partial_async_carry():
    spec = _spec()
    s = Session.from_spec(spec)
    s.run(2)
    crippled = dict(s.state)
    crippled["async"] = {k: v for k, v in s.state["async"].items()
                         if k != "pending"}
    with tempfile.TemporaryDirectory() as d:
        with pytest.raises(ValueError, match="async carry is missing"):
            save_fed_state(f"{d}/x.npz", crippled)


# ---------------------------------------------------------------------------
# satellite: per-arrival cost accounting + staleness summary
# ---------------------------------------------------------------------------


def test_cost_report_accounts_uploads_per_arrival():
    """The ledger books one upload per REALIZED arrival — a stale update
    counts exactly once, at its delivery round; in-flight work isn't an
    upload yet."""
    sess = Session.from_spec(_spec(async_buffer=2, async_latency=2.0,
                                   async_jitter=1.0, rounds=10)).run()
    led = sess.ledger()
    decided = int(led["train_rounds"].sum() + led["est_rounds"].sum())
    summ = sess.staleness_summary()
    assert decided == summ["arrivals"], (
        "ledger rows must equal realized arrivals, not dispatches")
    dispatches = int(sess._sched.dispatch.sum())
    in_flight_or_buffered = dispatches - summ["arrivals"]
    assert in_flight_or_buffered >= 0
    rep = sess.cost_report()
    assert rep["arrivals"] == summ["arrivals"]
    assert rep["merges"] == summ["merges"]
    assert rep["upload_bytes"] >= 0


def test_staleness_summary_reports_realized_staleness():
    sess = Session.from_spec(_spec(async_buffer=2, async_latency=2.0,
                                   async_jitter=1.0, rounds=10)).run()
    summ = sess.staleness_summary()
    assert summ["arrivals"] > 0 and summ["merges"] > 0
    assert summ["max_staleness"] >= 1          # latency 2.0 ⇒ real lag
    assert 0.0 < summ["mean_staleness"] <= summ["max_staleness"]
    assert summ["mean_buffer_occupancy"] >= 2  # K=2 merges wait for 2
    assert summ["pending_now"] >= 0
    # synchronous sessions have no arrival process to summarize
    sync = Session.from_spec(_spec(executor="scan"))
    with pytest.raises(ValueError, match="async"):
        sync.staleness_summary()
