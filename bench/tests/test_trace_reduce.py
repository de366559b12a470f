"""The trace reduction, and the readers that use it, on a small trace
(``bench/data/trace_small.json.gz``) whose sums are worked out by hand.

The trace is one chip's: a span program (``jit_run_span``) from 1 to
7 ms holding two convolutions (2 ms and 1.5 ms, overlapping by 0.5 ms),
an all-reduce (0.5 ms) and the q8 kernel (1.5 ms); two evaluation
programs with 1 ms each; the benchmark's host spans ``span`` (0.5–7.5
ms), ``eval`` (7.5–11 ms) and ``sync`` (11–12 ms).
"""
from pathlib import Path

import pytest

from bench import harness, trace_reduce
from bench.cells import reader, resolve
from bench.trace_reduce import Trace

SMALL = Path(__file__).resolve().parents[1] / "data" / "trace_small.json.gz"
MS = 1e-3


@pytest.fixture(scope="module")
def tr():
    return Trace.load(str(SMALL))


def test_window_busy_and_idle(tr):
    assert trace_reduce.window_s(tr) == pytest.approx(11.5 * MS)
    # merged: [1,4] [4.5,5] [5.5,7] [8,9] [9.5,10.5] ms
    assert trace_reduce.busy_s(tr, 0) == pytest.approx(7.0 * MS)
    gaps = trace_reduce.idle_gaps(tr, 0)
    assert gaps[0] == ("sync", pytest.approx(1.5 * MS))
    assert gaps[1] == ("eval", pytest.approx(1.0 * MS))
    assert sorted(n for n, _ in gaps[2:]) == ["eval", "span", "span", "span"]
    assert sum(s for _, s in gaps) == pytest.approx(4.5 * MS)


def test_programs_and_op_sums(tr):
    assert [o.program for o in tr.ops[:4]] == ["jit_run_span(1)"] * 4
    conv = trace_reduce.op_seconds(tr, 0, lambda o: "convolution" in o.text())
    assert conv == pytest.approx(4.5 * MS)
    top = trace_reduce.top_ops(tr, 0)
    assert top[0] == ("jit_run_span(1)/convolution.1", pytest.approx(2 * MS))
    assert len(top) == 6


def _record(cell, rounds=1):
    c = resolve(cell)
    rec = harness.RunRecord(config=c.config, traffic=c.traffic, chips=1,
                            peaks={"flops_bf16": 197e12,
                                   "hbm_bytes_per_s": 819e9})
    rec.trace, rec.traced_rounds, rec.devices = \
        Trace.load(str(SMALL)), rounds, [0]
    return rec


def test_device_readers():
    rec = _record("silo8.cc_power", rounds=2)
    assert reader("device_idle_share")(rec) == pytest.approx(
        100 * (1 - 7.0 / 11.5))
    # a plain trace carries no scopes: the local-SGD reader finds nothing
    assert reader("local_sgd_ms_per_round")(rec) is None
