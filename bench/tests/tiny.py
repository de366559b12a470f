"""A cell cut to a size the CPU test run can hold, and a runner for it.

The cell keeps its configuration's structure (ResNet-18-GN, 8 clients,
the cell's strategy, executor, history, schedule, span length and
compared rounds) at width 16 on 16×16 images with 64 images per client,
and evaluates after the compared rounds. ``run_tiny`` drives the rest of a run through
:func:`bench.harness.run`, skipping only the look for a chip.

    python -m bench.tests.tiny <cell> [fault ...]

prints one JSON line per run (no fault first), for runs that need a
process of their own (four virtual CPU devices for the sharded cell).
"""
from __future__ import annotations

import copy
import json
import sys
import time

from bench import harness
from bench.cells import resolve

PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2 ** 31 + 99


def tiny(name: str):
    c = resolve(name)
    c.config = copy.deepcopy(c.config)
    c.config["model"].update(width=16, image_size=16, n_classes=10)
    c.config["federation"].update(samples_per_client=64, test_samples=64)
    c.config["training"].update(batch_size=8)
    c.traffic = dict(c.traffic, plan_rounds=64,
                     eval_every=int(c.limits["rounds"]))
    return c


def run_tiny(name: str, fault: str | None = None, trace: bool = False):
    return harness.run(tiny(name), SEED, 0.5, trace,
                       t_start=time.perf_counter(), require_chip=False,
                       use_cache=False, peaks=PEAKS, fault=fault)


if __name__ == "__main__":
    name, faults = sys.argv[1], [None] + sys.argv[2:]
    for f in faults:
        print(json.dumps({"fault": f, **run_tiny(name, f)}), flush=True)
