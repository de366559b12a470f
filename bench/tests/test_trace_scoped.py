"""The reduction of the program's ``fed.*`` scopes and spans
(``bench/trace_scopes.py``) on a small trace
(``bench/data/trace_scoped.json.gz``) whose sums are worked out by hand.

The trace is one chip's, in ms. The span program (1–7) holds a
``vmap(fed.local_sgd)/while`` (1–4) whose body ops (1.2–2.2 and 2.5–3.5,
one under ``transpose(jvp(…))``) nest in it; then ``fed.estimate``
(4.5–5), ``fed.aggregate`` (5–5.5), ``fed.history`` (5.5–6),
``fed.policy`` (6.2–6.4), an unscoped ``add`` (6.4–6.5) and a copy with
no op_name (6.8–7). Two evaluation programs run ``fed.eval`` ops (8–9,
9.5–10.5) around an unscoped eager argmax (9.2–9.3). The benchmark's host
spans are ``span`` (0.5–7.5), ``eval`` (7.5–11) and ``sync`` (11–12), so
the window is 0.5–12. The program's host spans: ``fed.run`` (0.5–11.8),
``fed.dispatch`` (0.6–0.9), ``fed.callbacks`` (7.4–7.6 and 10.8–10.9),
one ``fed.eval`` (7.6–10.8) and its two ``fed.eval_batch`` (7.7–9.4,
9.4–10.7). The trace holds 2 rounds.

By hand:

* busy: [1,4] [4.5,6] [6.2,6.5] [6.8,7] [8,9] [9.2,9.3] [9.5,10.5] =
  3 + 1.5 + 0.3 + 0.2 + 1 + 0.1 + 1 = 7.1 ms; idle 11.5 − 7.1 = 4.4 ms;
* ``fed.local_sgd``: the while's 3 ms, its body counted once → 3 / 2 =
  1.5 ms a round;
* estimate ∪ aggregate ∪ history ∪ policy: [4.5,6] + [6.2,6.4] = 1.7 ms
  → 0.85 ms a round;
* ``fed.eval``: 2 ms over one ``fed.eval`` span → 2 ms an evaluation;
* idle inside ``fed.eval`` (7.6–10.8): 7.6–8, 9–9.2, 9.3–9.5 and
  10.5–10.8 = 1.1 ms → 1.1 ms an evaluation;
* unscoped busy: 7.1 − (3 + 1.7 + 2) = 0.4 ms (the add, the copy, the
  argmax) → 100 · 0.4 / 7.1 % of busy time; the four classes add up to
  the busy time;
* idle by innermost program span, each gap named at its middle:
  ``fed.run`` 0.5 (4–4.5) + 0.2 + 0.3 + 1.5 (10.5–12) = 2.5,
  ``fed.callbacks`` 1.0 (7–8), ``fed.dispatch`` 0.5 (0.5–1),
  ``fed.eval_batch`` 0.2 + 0.2 = 0.4.
"""
import json
from pathlib import Path

import pytest

from bench import harness, trace_reduce, trace_scopes
from bench.cells import reader
from bench.trace_scopes import ScopedOp, ScopedTrace

DATA = Path(__file__).resolve().parents[1] / "data"
SCOPED = DATA / "trace_scoped.json.gz"
SMALL = DATA / "trace_small.json.gz"
MS = 1e-3


@pytest.fixture(scope="module")
def tr():
    return ScopedTrace.load(str(SCOPED))


def test_scopes_match_as_components(tr):
    names = {o.name: o for o in tr.ops}
    assert trace_scopes.in_scope(names["while.5"], "fed.local_sgd")
    assert trace_scopes.in_scope(names["fusion.32"], "fed.local_sgd")
    assert not trace_scopes.in_scope(names["reduce.8"], "fed.local_sgd")
    assert not trace_scopes.is_scoped(names["copy.12"])
    assert not trace_scopes.is_scoped(names["add.11"])


def test_scoped_and_unscoped_time(tr):
    busy = trace_reduce.busy_s(tr, 0)
    assert busy == pytest.approx(7.1 * MS)
    local = trace_scopes.scoped_s(tr, 0, trace_scopes.LOCAL_SGD)
    server = trace_scopes.scoped_s(tr, 0, trace_scopes.SERVER)
    ev = trace_scopes.scoped_s(tr, 0, trace_scopes.EVAL)
    un = trace_scopes.unscoped_s(tr, 0)
    assert (local, server, ev, un) == pytest.approx(
        (3.0 * MS, 1.7 * MS, 2.0 * MS, 0.4 * MS))
    assert local + server + ev + un == pytest.approx(busy)


def test_program_spans_and_idle(tr):
    assert len(trace_scopes.program_spans(tr, "fed.eval")) == 1
    assert len(trace_scopes.program_spans(tr, "fed.eval_batch")) == 2
    assert trace_scopes.idle_in_spans_s(tr, 0, "fed.eval") == \
        pytest.approx(1.1 * MS)
    idle = dict(trace_scopes.idle_by_program_span(tr, 0))
    assert idle == pytest.approx({"fed.run": 2.5 * MS,
                                  "fed.callbacks": 1.0 * MS,
                                  "fed.dispatch": 0.5 * MS,
                                  "fed.eval_batch": 0.4 * MS})
    assert sum(idle.values()) == pytest.approx(
        sum(s for _, s in trace_reduce.idle_gaps(tr, 0)))


def test_scoped_readers(tr):
    got = trace_scopes.split(tr, 0, rounds=2)
    idle = got.pop("program_idle_ms")
    assert got == pytest.approx({
        "window_ms": 11.5, "busy_ms": 7.1, "local_sgd_ms": 3.0,
        "server_ms": 1.7, "eval_ms": 2.0, "unscoped_ms": 0.4,
        "unscoped_device_share": 100 * 0.4 / 7.1, "evals": 1,
        "eval_ms_per_eval": 2.0, "eval_idle_ms_per_eval": 1.1,
        "local_sgd_ms_per_round": 1.5, "server_ms_per_round": 0.85})
    assert idle == pytest.approx({"fed.run": 2.5, "fed.callbacks": 1.0,
                                  "fed.dispatch": 0.5,
                                  "fed.eval_batch": 0.4})


def _record(trace=None, rounds=0, **kw):
    rec = harness.RunRecord(config={}, traffic={}, chips=1, peaks=None, **kw)
    rec.trace, rec.traced_rounds, rec.devices = trace, rounds, [0]
    return rec


def test_local_sgd_ms_per_round_reads_the_scope(tr):
    """The union of ``fed.local_sgd`` intervals (the while's 3 ms, its
    body once) over the 2 traced rounds."""
    got = reader("local_sgd_ms_per_round")(_record(tr, rounds=2))
    assert got == pytest.approx(1.5)
    assert reader("local_sgd_ms_per_round")(_record(tr, rounds=0)) is None
    assert reader("local_sgd_ms_per_round")(_record(None, rounds=2)) is None


def test_readers_find_nothing_in_an_unscoped_trace():
    """A program without scopes or spans (the trace before them) leaves
    every scoped number empty; the benchmark's own reduction reads the
    trace as a plain one."""
    tr = ScopedTrace.load(str(SMALL))
    assert tr.program_spans == []
    assert all(o.scope == "" for o in tr.ops)
    got = trace_scopes.split(tr, 0, rounds=2)
    assert (got["local_sgd_ms"], got["server_ms"], got["eval_ms"]) == \
        (0.0, 0.0, 0.0)
    assert got["unscoped_ms"] is got["unscoped_device_share"] is None
    assert got["eval_ms_per_eval"] is got["eval_idle_ms_per_eval"] is None
    assert got["program_idle_ms"] == {"none": pytest.approx(4.5)}
    plain = trace_reduce.Trace.load(str(SMALL))
    assert trace_reduce.busy_s(tr, 0) == trace_reduce.busy_s(plain, 0)
    assert trace_reduce.idle_gaps(tr, 0) == trace_reduce.idle_gaps(plain, 0)


def test_a_trace_round_trips_with_its_scopes(tr, tmp_path):
    path = tmp_path / "t.json.gz"
    tr.save(str(path))
    back = ScopedTrace.load(str(path))
    assert back.program_spans == tr.program_spans
    assert [o.scope for o in back.ops] == [o.scope for o in tr.ops]


def test_scopes_come_from_each_program_s_hlo():
    """An op's scope is its instruction's op_name in its program run's
    HLO; an instruction with no op_name, or a program the trace holds no
    HLO for, leaves it unscoped."""
    tr = ScopedTrace(ops=[
        ScopedOp(0, "%while.5 = (s32[]) while((s32[]) %t)", 0, 1,
                 program="jit_run_span(7)"),
        ScopedOp(0, "%copy.3 = f32[8] copy(f32[8] %p)", 1, 1,
                 program="jit_run_span(7)"),
        ScopedOp(0, "fusion.1", 2, 1, program="jit_apply(9)")])
    trace_scopes.attribute_scopes(tr, {
        "jit_run_span(7)": {"while.5": "jit(run_span)/fed.local_sgd/while",
                            "copy.3": ""},
        "jit_apply(8)": {"fusion.1": "jit(apply)/fed.eval/conv"}})
    assert [o.scope for o in tr.ops] == [
        "jit(run_span)/fed.local_sgd/while", "", ""]


def test_hlo_op_names_read_a_recorded_trace(tmp_path, capsys):
    """The compiled HLO that a profiled program leaves on the trace's host
    metadata plane names its instructions' scopes, and the host plane
    holds its ``fed.*`` spans, which are the window of a trace without the
    benchmark's (here on the CPU, which has no TPU plane of operations)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("fed.aggregate"):
            return jnp.tanh(x).sum(0)

    x = jnp.ones((8, 128))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("fed.run"):
            f(x).block_until_ready()
    path = trace_reduce.find_xplane(str(tmp_path))
    with open(path, "rb") as fh:
        names = trace_scopes.hlo_op_names(fh.read())
    runs = [r for r in names if r.startswith("jit_f(")]
    assert len(runs) == 1
    assert any("/fed.aggregate/" in n for n in names[runs[0]].values())

    tr = trace_scopes.read_xplane(path)
    assert [s.name for s in tr.program_spans] == ["fed.run"]
    (run,) = tr.program_spans
    assert tr.window() == (run.start_ns, run.end_ns)
    assert trace_scopes.main([str(tmp_path)]) == 1
    assert "no device operations" in capsys.readouterr().err


def test_the_command_prints_the_split(tr, monkeypatch, capsys):
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_scopes, "read_xplane", lambda p: tr)
    assert trace_scopes.main(["trace_dir", "--rounds", "2"]) == 0
    got = json.loads(capsys.readouterr().out)
    assert got["local_sgd_ms_per_round"] == pytest.approx(1.5)
    assert got["eval_idle_ms_per_eval"] == pytest.approx(1.1)
