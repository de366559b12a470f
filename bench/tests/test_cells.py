"""Every cell of ``BENCHMARK.json`` resolves its files by name, and the
file keeps to its rules for names, units and bounds."""
import json
import re

import pytest

from bench import harness
from bench.cells import ROOT, load_benchmark, reader, resolve

BENCH = load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = resolve(cell)
    assert c.chips in (1, 4)
    assert c.config["name"] in {x["name"] for x in BENCH["configs"]}
    assert set(c.limits) >= {"param_change_gap", "history_gap",
                             "train_count_gap"}
    assert c.limits["train_count_gap"] == 0
    assert {m["name"] for m in c.end_to_end} >= {"client_rounds_per_s",
                                                 "setup_s"}
    assert c.per_layer, "every cell reports a per-layer metric"
    for m in c.per_layer:
        assert callable(reader(m["name"]))


@pytest.mark.parametrize("metric", [m["name"] for m in BENCH["per_layer"]])
def test_reader_finds_nothing_in_an_empty_run(metric):
    """A reader with nothing to read returns None, never 0 for a share."""
    run = harness.RunRecord(config=resolve(CELLS[0]).config,
                            traffic=resolve(CELLS[0]).traffic, chips=1,
                            peaks=None)
    got = reader(metric)(run)
    assert got is None or metric == "compiles_in_window"


@pytest.mark.parametrize("cell", CELLS)
def test_an_entry_without_workloads_applies_to_every_cell(cell):
    every = {m["name"] for m in BENCH["per_layer"] if "workloads" not in m}
    assert every >= {"mfu", "device_idle_share", "eval_share",
                     "compiles_in_window"}
    assert every <= {m["name"] for m in resolve(cell).per_layer}


def test_names_units_and_bounds():
    names = [m["name"] for m in METRICS] + CELLS + [
        c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for m in METRICS:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        assert set(m.get("workloads", CELLS)) <= set(CELLS)
    assert 1 <= BENCH["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    for w in BENCH["workloads"]:
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_config_files_lie_under_paths():
    for c in BENCH["configs"]:
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        assert (ROOT / c["file"]).is_file()
