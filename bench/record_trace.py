#!/usr/bin/env python3
"""Record a short device trace of one cell for the reduction's tests.

  python3 bench/record_trace.py --workload mh153m.chat --seed 5 \
      --seconds 10 --out bench/tests/data/v5e_trace.json.gz

One traced run of the cell as `run.py --trace 1` makes it; the events that
`trace_reduce.extract` reads, cut to the first half second of the traced
span, are written to `--out`, and beside it (`<out>.lines.json`) the
trace's planes and lines with their most frequent event names. Prints the
run's result line.
"""
from __future__ import annotations

import argparse
import json
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--out", required=True)
    a = ap.parse_args(argv)
    cell = run.Cell(run.ROOT, a.workload)
    try:
        res = run.run(cell, a.seed, a.seconds, True, save_trace=a.out)
    except run.NoChip as e:
        print(f"record_trace: {e}", file=sys.stderr)
        return run.NO_CHIP_EXIT
    for line in res["info"]:
        print(f"record_trace: {line}", file=sys.stderr)
    print(json.dumps(res["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
