"""A cell cut to a size the CPU test run can hold, and a runner for it.

The cell keeps its configuration's structure (its model family, 8
clients, the cell's strategy, executor, history, schedule, span length
and compared rounds) at the size its family's ``shrink`` gives, and
evaluates after the compared rounds. ``run_tiny`` drives the rest of a
run through :func:`bench.harness.run`, skipping only the look for a
chip; ``root`` is the checkout the cell is found in.

    python -m bench.tests.tiny <cell> [fault ...]

prints one JSON line per run (no fault first), for runs that need a
process of their own (four virtual CPU devices for the sharded cell).
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

from bench import harness
from bench.cells import ROOT, family, resolve

PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 31 + 99


def tiny(name: str, root: Path = ROOT):
    c = resolve(name, root)
    c.config = family(c).shrink(c.config)
    c.traffic = dict(c.traffic, plan_rounds=64,
                     eval_every=int(c.limits["rounds"]))
    return c


def run_tiny(name: str, fault: str | None = None, trace: bool = False,
             root: Path = ROOT):
    return harness.run(tiny(name, root), SEED, 0.5, trace,
                       t_start=time.perf_counter(), require_chip=False,
                       use_cache=False, peaks=PEAKS, fault=fault)


if __name__ == "__main__":
    name, faults = sys.argv[1], [None] + sys.argv[2:]
    for f in faults:
        print(json.dumps({"fault": f, **run_tiny(name, f)}), flush=True)
