"""A configuration brings its own model with files alone.

A copy of the benchmark (``BENCHMARK.json`` and ``bench/``) gains a second
model family, a LoRA-wrapped zoo decoder (``bench/tests/fixture/``): the
family module, its configuration, a traffic mix, the cell's limits, and
entries appended to ``BENCHMARK.json``; and a per-layer metric of its own
(``sgd_client_rounds``, a reader of the program's counter), listed for
the new cell and the existing ones. The cell then runs through
:func:`bench.harness.run` at its CPU size, comes out correct, reports
every metric it lists, and leaves every file the copy held as it was.
"""
import hashlib
import json
import shutil
from pathlib import Path

import pytest

from bench import check, generate
from bench.cells import ROOT, family, resolve
from bench.tests.tiny import SEED, run_tiny, tiny

FIXTURE = Path(__file__).resolve().parent / "fixture"
CELL = "lora8.lora_power"
ADDED = {"lora_decoder.py": "bench/families/lora_decoder.py",
         "lora8-silo8.json": "bench/configs/lora8-silo8.json",
         "lora_power.json": "bench/traffic/lora_power.json",
         "lora8.lora_power.json": "bench/limits/lora8.lora_power.json",
         "sgd_client_rounds.py": "bench/metrics/sgd_client_rounds.py"}


def _files(root: Path) -> dict:
    return {str(p.relative_to(root)): hashlib.sha256(p.read_bytes()).digest()
            for p in sorted(root.rglob("*"))
            if p.is_file() and "__pycache__" not in p.parts}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = tmp_path_factory.mktemp("checkout")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    held = _files(root)
    before = json.loads((root / "BENCHMARK.json").read_text())
    for src, dst in ADDED.items():
        assert not (root / dst).exists()
        shutil.copy(FIXTURE / src, root / dst)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    for key, entries in json.loads(
            (FIXTURE / "entries.json").read_text()).items():
        bench[key] = bench[key] + entries
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    yield root
    # every file the copy held is unchanged, and BENCHMARK.json only
    # gained entries
    now = _files(root)
    assert {k: now[k] for k in held if k != "BENCHMARK.json"} == {
        k: v for k, v in held.items() if k != "BENCHMARK.json"}
    after = json.loads((root / "BENCHMARK.json").read_text())
    for key, value in before.items():
        if isinstance(value, list):
            assert after[key][:len(value)] == value
        else:
            assert after[key] == value


def _trained_in_window(c, out) -> int:
    """The client-rounds the schedule trains in the measured window, which
    starts after the first call's ``eval_every`` rounds."""
    inputs = generate.make_inputs(family(c), c.config, c.traffic, SEED)
    start = int(c.traffic["eval_every"])
    n = int(c.config["federation"]["n_clients"])
    stop = start + out["attempted"] // n
    return int((inputs.selection[start:stop]
                & inputs.training[start:stop]).sum())


def test_the_family_loads_from_the_checkout(checkout):
    c = resolve(CELL, checkout)
    assert Path(family(c).__file__) == (
        checkout / "bench" / "families" / "lora_decoder.py")
    # the cell's own family is found under the same root
    assert Path(family(resolve("silo8.cc_power", checkout)).__file__
                ).parent == checkout / "bench" / "families"


@pytest.mark.parametrize("trace", [False, True])
def test_a_second_family_runs_with_files_alone(checkout, trace):
    out = run_tiny(CELL, trace=trace, root=checkout)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    c = resolve(CELL, checkout)
    listed = c.per_layer if trace else c.end_to_end
    # the CPU's trace has no device plane: the device-trace metrics find
    # nothing to read there
    assert set(out["metrics"]) == {m["name"] for m in listed
                                   if m["source"] != "device_trace"}
    for name, m in out["metrics"].items():
        assert m["value"] >= 0, name
    if trace:
        assert out["metrics"]["sgd_client_rounds"]["value"] == \
            _trained_in_window(tiny(CELL, checkout), out) > 0
        assert 0 < out["metrics"]["mfu"]["value"] < 100


@pytest.mark.parametrize("cell", ["silo8.cc_power", "silo8.full_train"])
def test_an_added_reader_reads_the_window_s_counters(checkout, cell):
    """The counter's change across the measured window reaches a reader
    that files alone added, in the cells the benchmark already had."""
    out = run_tiny(cell, trace=True, root=checkout)
    assert out["metrics"]["sgd_client_rounds"]["value"] == \
        _trained_in_window(tiny(cell, checkout), out) > 0


def test_the_second_family_s_control_is_not_correct(checkout):
    c = tiny(CELL, checkout)
    inputs = generate.make_inputs(family(c), c.config, c.traffic, SEED)
    ref = check.reference_outputs(c, inputs)
    ctl = check.control_outputs(c, inputs)
    correct, compared = check.verdict(
        check.numbers(inputs.params, ctl, ref), c.limits)
    assert not correct, compared
