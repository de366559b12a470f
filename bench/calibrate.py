"""Readings that a cell's correctness limits are set from.

    python3 bench/calibrate.py --workload <name> --seeds <n> [<n> ...]
        [--rounds R ...] [--control K] [--faults half_batch altered ...]
        [--fault-seeds K] [--out FILE]

For each seed, in one process: the program's state after each count of
rounds in ``--rounds`` (by default the count the cell's check compares),
driven as a run drives it, against the plain reference (the sound
readings, whose largest is the lower reading of each number); for the
first ``--control`` seeds the
reference computed in bfloat16 in the program's place (the control,
``check.control_outputs``);
and for the first ``--fault-seeds`` seeds each planted fault
(``bench/faults.py``). Each reading is one JSON line on standard output
and in ``--out``, with the numbers compared and whether
``check.verdict`` finds them correct under the cell's limits. The
benchmark's own runs never call this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def leaf_gaps(params0, got, ref) -> dict:
    """Per-leaf relative norm gaps of the span's change (for the look
    behind a worst-leaf reading)."""
    import numpy as np
    from bench.check import leaves64
    p0 = leaves64(params0)
    a = np.array([np.linalg.norm(g - x)
                  for g, x in zip(leaves64(got["params"]), p0)])
    r = np.array([np.linalg.norm(g - x)
                  for g, x in zip(leaves64(ref["params"]), p0)])
    med = float(np.median(r))
    gaps = np.abs(a - r) / np.maximum(r, med)
    order = np.argsort(-gaps)[:5]
    return {"median_leaf_gap": float(np.median(gaps)),
            "worst": [[int(i), float(gaps[i]), float(r[i] / med)]
                      for i in order]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rounds", type=int, nargs="*", default=[])
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

    import gc

    from bench import check, harness
    from bench.cells import resolve
    from bench.faults import planted

    cell = resolve(args.workload, ROOT)
    harness.check_devices(cell.chips)
    harness.enable_cache()
    rounds = sorted(set(args.rounds or [int(cell.limits["rounds"])]))
    out = open(args.out, "w") if args.out else None

    def emit(rec, values):
        correct, _ = check.verdict(values, cell.limits)
        line = json.dumps({**rec, **values, "correct": correct})
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def program(seed, fault=None):
        """The program's state after each count in ``rounds``."""
        clock = harness.make_clock(cell)
        got = {}
        with planted(fault), harness.precision(cell.config):
            inputs, sess = harness.prepare(cell, seed, clock)
            for r in rounds:
                harness.drive(sess, clock, r - sess.t, 0.0)
                got[r] = harness.capture(sess)
        del sess
        gc.collect()
        return inputs, got

    for i, seed in enumerate(args.seeds):
        t0 = time.perf_counter()
        inputs, got = program(seed)
        t1 = time.perf_counter()
        ref = check.reference_snapshots(cell, inputs, rounds)
        t2 = time.perf_counter()
        for r in rounds:
            emit({"seed": seed, "kind": "sound", "rounds": r,
                  **leaf_gaps(inputs.params, got[r], ref[r]),
                  "program_s": t1 - t0, "reference_s": t2 - t1},
                 check.numbers(inputs.params, got[r], ref[r]))
        if i < args.control:
            ctl = check.reference_snapshots(cell, inputs, rounds,
                                            dtype="bfloat16")
            for r in rounds:
                emit({"seed": seed, "kind": "control_bf16", "rounds": r,
                      **leaf_gaps(inputs.params, ctl[r], ref[r])},
                     check.numbers(inputs.params, ctl[r], ref[r]))
        if i < args.fault_seeds:
            for fault in args.faults:
                _, bad = program(seed, fault)
                for r in rounds:
                    emit({"seed": seed, "kind": f"fault_{fault}",
                          "rounds": r,
                          **leaf_gaps(inputs.params, bad[r], ref[r])},
                         check.numbers(inputs.params, bad[r], ref[r]))
        del inputs, got, ref
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
