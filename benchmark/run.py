"""Benchmark entry point:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine and
prints one JSON object as the last line of standard output. Exits 3,
printing no result, where JAX finds no TPU or fewer chips than the cell
asks for, and 2 where the cell or its files cannot be found.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    # the controls' readings (never asked for by the driver's runs)
    ap.add_argument("--controls", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from benchmark import harness

    try:
        harness.find_cell(ROOT, args.workload)
    except (OSError, KeyError, ValueError) as e:
        harness.log(f"benchmark: {e!r}")
        return 2
    try:
        res = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                               bool(args.trace), controls=bool(args.controls))
    except harness.NoChip as e:
        harness.log(f"benchmark: {e}")
        return 3
    for name, c in res["checks"].items():
        harness.log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
