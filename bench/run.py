"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, traffic
mix, limits and metric readers are found by name from ``BENCHMARK.json``
(``bench/cells.py``). The last line of standard output is one JSON object
with ``correct``, ``attempted``, ``failed``, ``metrics`` and ``device``
(and with ``--trace 1`` the per-layer metrics and ``breakdown``), and
last of all ``checks``: each number compared, beside its limit. The same
numbers are the last lines of standard error.

The run exits non-zero, and prints no result, where JAX finds no TPU,
fewer chips than the cell asks for, or a device kind missing from
``bench/peaks.py``, and where the checkout holds no program (``src/``).
``--keep-trace DIR`` keeps the traced window's trace in ``DIR``.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench.cells import resolve
    from bench.harness import NoChip, run

    cell = resolve(args.workload, ROOT)
    try:
        out = run(cell, args.seed, args.seconds, bool(args.trace),
                  t_start=T_START, keep_trace=args.keep_trace)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
