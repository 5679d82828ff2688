#!/usr/bin/env python3
"""Run one benchmark cell once on the chips this process holds.

    python3 bench/run.py --workload cr1_ticks.caiso_w100k --seed 7 \
        --seconds 51 --trace 0

Prints set-up parts, a stall record and the correctness checks on
earlier lines, and one JSON result object as the last line of standard
output. Exits nonzero, with no result, when JAX finds no TPU or fewer
chips than the cell asks for. Cells, configurations, traffic mixes and
metrics are named in `BENCHMARK.json` at the checkout's root.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    from harness import runner, spec
    cell = spec.find_cell(args.workload)
    try:
        result = runner.run(cell, args.seed, args.seconds, bool(args.trace))
    except runner.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
