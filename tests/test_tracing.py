"""The program's tracing (:mod:`repro.utils.trace`): the ``fed.*`` device
scopes in every executor's compiled round, the host spans of a profiled
``Session`` run, and the local-SGD counter."""
import contextlib
import glob
import os
import re

import jax
import numpy as np
import pytest

from repro.api import ExperimentSpec, Session
from repro.core.evaluation import jitted_apply
from repro.utils import trace

ROUND_SCOPES = {trace.LOCAL_SGD, trace.ESTIMATE, trace.AGGREGATE,
                trace.HISTORY, trace.POLICY}
# the fused kernel estimates, aggregates and writes the history in one
# pass under fed.aggregate
FUSED_SCOPES = ROUND_SCOPES - {trace.ESTIMATE}

EXECUTORS = [
    ("python", {}, ROUND_SCOPES),
    ("scan", {}, ROUND_SCOPES),
    ("scan", {"use_fused": True}, FUSED_SCOPES),
    ("sharded", {}, ROUND_SCOPES),
    ("hierarchical", {"topology": "contiguous", "n_edges": 2,
                      "edge_period": 2}, ROUND_SCOPES),
    ("async", {}, ROUND_SCOPES),
]
N = 4


def _spec(executor, **extra):
    return ExperimentSpec(dataset="gaussian", n_samples=256, dim=8,
                          n_classes=4, n_clients=N, width=2, local_steps=2,
                          batch_size=8, rounds=4, eval_every=2,
                          executor=executor, **extra)


def _one_round(sess):
    """The executor's jitted round program and its arguments for one
    round."""
    sel = sess._sel[:1]
    if sess.executor == "python":
        run = sess._get_round_fn()
        return run, (sess.state, sel[0], sess.k_active)
    run = sess._get_span_runner()
    if sess.executor == "sharded":
        return run, (sess.state, sel, sess.k_active, sess._cohort[:1])
    if sess.executor == "async":
        return run, (sess.state, sess.k_active,
                     tuple(np.asarray(x[:1]) for x in sess._sched))
    return run, (sess.state, sel, sess.k_active)


def _compiled_text(sess) -> str:
    run, args = _one_round(sess)
    return run.func.lower(*args, **run.keywords).compile().as_text()


def _op_names(hlo: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("executor,extra,scopes", EXECUTORS,
                         ids=[e + ("_fused" if x.get("use_fused") else "")
                              for e, x, _ in EXECUTORS])
def test_every_executor_scopes_its_round(executor, extra, scopes):
    """The compiled round's op_name metadata names each layer the
    executor runs, as ``fed.<layer>``: scope names survive vmap, scan,
    shard_map, cond and the fused kernel's lowering."""
    names = _op_names(_compiled_text(Session.from_spec(
        _spec(executor, **extra))))
    found = {s for s in trace.SCOPES
             if any(trace.PREFIX + s in n for n in names)}
    assert scopes <= found, f"missing {scopes - found}"
    # local SGD's loop carries the scope, the while and its body alike
    assert any(trace.PREFIX + trace.LOCAL_SGD in n and "while" in n
               for n in names)


def test_scopes_change_no_arithmetic(monkeypatch):
    """With every named_scope made a no-op the compiled round is the same
    program once its metadata (and the source-location tables the
    metadata points into) is stripped."""
    def stripped(text):
        text = re.sub(r", metadata=\{[^}]*\}", "", text)
        return [ln for ln in text.splitlines()
                if not re.match(r"^\d+ |^[A-Z][A-Za-z]+$", ln)]

    scoped = _compiled_text(Session.from_spec(_spec("scan")))
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled_text(Session.from_spec(_spec("scan")))
    assert "fed." in scoped and "fed." not in bare
    assert stripped(scoped) == stripped(bare)


def test_evaluation_apply_is_scoped():
    sess = Session.from_spec(_spec("scan"))
    hlo = jitted_apply(sess.model.apply).lower(
        sess.state["params"], sess.x_test[:8]).compile().as_text()
    assert any(trace.PREFIX + trace.EVAL in n for n in _op_names(hlo))


def test_unknown_names_are_refused():
    with pytest.raises(ValueError, match="unknown scope"):
        trace.scope("local-sgd")
    with pytest.raises(ValueError, match="unknown span"):
        trace.span("round")


def _host_span_names(trace_dir: str) -> set[str]:
    from jax.profiler import ProfileData
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names.update(ev.name for ev in line.events
                             if ev.name.startswith(trace.PREFIX))
    return names


def test_profiled_session_holds_the_host_spans(tmp_path):
    """A Session run under ``jax.profiler`` leaves its ``fed.*`` spans on
    the trace's host plane."""
    sess = Session.from_spec(_spec("scan"))
    sess.run(n_rounds=1)                      # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        sess.run(n_rounds=1)                  # ends in an evaluation
    names = _host_span_names(str(tmp_path))
    assert {trace.PREFIX + s for s in (trace.RUN, trace.DISPATCH,
                                       trace.EVAL, trace.EVAL_BATCH,
                                       trace.CALLBACKS)} <= names


@pytest.mark.parametrize("executor,extra,lanes", [
    ("scan", {}, "trainers"),
    ("python", {}, "trainers"),
    ("sharded", {"cohort_size": 2}, "trainers"),
    ("scan", {"schedule": "full"}, "all"),
    ("hierarchical", {"topology": "contiguous", "n_edges": 2,
                      "edge_period": 2}, "all"),
    ("async", {}, "all"),
])
def test_local_sgd_counter(executor, extra, lanes):
    """``local_sgd_client_rounds`` counts the client-rounds the executor
    ran through local SGD. The flat executors run only each round's
    trainers, one per trip, so what ran is what was trained (everyone,
    where the plan trains everyone); the hierarchical and async executors
    train every client. The summary's useful share is the ledger's trained
    client-rounds over the counter."""
    sess = Session.from_spec(_spec(executor, **extra))
    assert sess.counters == {trace.LOCAL_SGD_CLIENT_ROUNDS: 0}
    assert "local_sgd_useful_share" not in sess.summary()
    sess.run(n_rounds=3)
    ran = sess.counters[trace.LOCAL_SGD_CLIENT_ROUNDS]
    trained = int(sess.ledger()["train_rounds"].sum())
    share = sess.summary()["local_sgd_useful_share"]
    if lanes == "trainers":
        # the plans leave clients out, and they run no local SGD
        assert ran == trained < 3 * N
        assert share == 1.0
    else:
        assert ran == 3 * N
        assert share == pytest.approx(trained / ran)
        assert 0.0 < share <= 1.0


def test_useful_share_counts_from_the_last_restore(tmp_path):
    """A restored session counts the local SGD it runs itself, against the
    client-rounds trained since the restore, not the checkpoint's: with
    the trainers compacted, the two are the same."""
    sess = Session.from_spec(_spec("scan"), ckpt_dir=str(tmp_path))
    sess.run(n_rounds=2)
    sess.save()
    back = Session.restore_from(str(tmp_path))
    assert back.counters == {trace.LOCAL_SGD_CLIENT_ROUNDS: 0}
    assert "local_sgd_useful_share" not in back.summary()
    before = int(back.ledger()["train_rounds"].sum())
    back.run(n_rounds=2)
    trained = int(back.ledger()["train_rounds"].sum()) - before
    assert back.counters[trace.LOCAL_SGD_CLIENT_ROUNDS] == trained
    assert trained < 2 * N
    assert back.summary()["local_sgd_useful_share"] == 1.0
