"""Find a cell's configuration, traffic mix, limits and metric readers by name.

``BENCHMARK.json`` lists the cells. Everything that belongs to one
configuration, one mix or one metric is a file of its own, found from
the names there:

* a configuration: the ``file`` its entry names (``bench/configs/``);
* a model family: ``bench/families/<family>.py``, named by the
  configuration's ``model.family``. It defines ``make_data(config,
  words)``, ``init_params(config, key)``, ``build_model(config)`` (the
  program's ``Classifier``), ``loss(params, xb, yb, config)`` (the plain
  reference's), ``train_flops(config)`` and ``forward_flops(config)``
  (of one example) and ``shrink(config)`` (the CPU test size);
* a traffic mix: ``bench/traffic/<traffic>.json``;
* the limits of a cell's correctness check: ``bench/limits/<cell>.json``;
* a per-layer metric's reader: ``bench/metrics/<metric>.py``, whose
  ``read(run)`` returns a number, or None where it finds nothing to read.
  A metric entry without ``workloads`` applies to every cell, those
  added later too.

Every file is found under the root the cell was resolved from, so a
checkout anywhere reads its own. Adding a configuration, a family, a
cell or a metric is adding files and entries; nothing here changes.
"""
from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)   # metric entries
    per_layer: list = field(default_factory=list)    # metric entries
    root: Path = ROOT                                # where it was found


def load_benchmark(root: Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def resolve(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload``, with every file it names read."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: "
                       f"{sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=_json(root / configs[w["config"]]["file"]),
        traffic=_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
        limits=_json(root / "bench" / "limits" / f"{workload}.json"),
        end_to_end=[m for m in bench["end_to_end"]
                    if _applies(m, workload)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, workload)],
        root=root)


def _load(path: Path, prefix: str):
    name = path.stem.replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(f"{prefix}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str, root: Path = ROOT):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    return _load(root / "bench" / "metrics" / f"{metric}.py",
                 "bench_metric").read


@functools.lru_cache(maxsize=16)
def _family(root: Path, name: str):
    return _load(root / "bench" / "families" / f"{name}.py", "bench_family")


def family(cell: Cell):
    """The module of the cell's model family,
    ``bench/families/<model.family>.py`` under the cell's root."""
    return _family(cell.root, cell.config["model"]["family"])
