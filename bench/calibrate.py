#!/usr/bin/env python3
"""Readings that set a cell's correctness limits, many seeds in one process.

  python3 bench/calibrate.py --workload mh153m.chat --seeds 101-112 \
      --seconds 20 [--control 101-103] [--fault top_p_skipped:113-115 ...]

For each seed: weights from the seed, the cell's engine (its executables
compiled once for all seeds), the cell's mix for `--seconds`, and the
check `run.py` makes of what was served: the greedy gap, the sampled
tokens' nucleus excess and surprise. For the `--control` seeds also the
control in the program's place (the greedy gap of the tokens the float8
reference puts first) and whether the cell's limits find it not correct.
Each `--fault name:seeds` then plants that fault (`bench/faults.py`, or
the architecture's `FAULTS`) and reads the same numbers on its seeds.
Prints one JSON line per seed; the limits lie between the largest sound
reading and the smallest control or fault reading (PERF.md gives both).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys

import faults
import run
import traffic


def seed_list(spec: str) -> list:
    out = []
    for part in spec.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def reading(cell, seed: int, seconds: float, control: bool) -> dict:
    planned = traffic.generate(cell.mix, seed, cell.vocab, seconds)
    _, w, eng, _ = run.setup(cell, seed, planned)
    rec = run.drive_cell(eng, cell, planned, seconds)
    del eng
    gc.collect()
    chk = run.check_served(cell.arch, w, cell.cfg, cell.mix,
                           *run.pick_checked(rec, cell.mix, seed),
                           control=control)
    out = dict(chk, seed=seed, compiles=rec.compiles,
               correct=run.verdict(run.compare(chk, cell.limits, 0)))
    if control:
        out["control_correct"] = run.verdict(
            run.control_compared(chk, cell.limits))
    del w
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", default="")
    ap.add_argument("--fault", action="append", default=[],
                    help="name:seeds, a fault of bench/faults.py or of "
                    "the cell's architecture")
    ap.add_argument("--seconds", type=float, default=20.0)
    a = ap.parse_args(argv)
    cell = run.Cell(run.ROOT, a.workload)
    control = set(seed_list(a.control)) if a.control else set()
    try:
        run.check_devices(cell.chips)
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return run.NO_CHIP_EXIT
    for seed in seed_list(a.seeds):
        print(json.dumps(reading(cell, seed, a.seconds, seed in control)),
              flush=True)
    for spec in a.fault:
        name, _, seeds = spec.partition(":")
        remove = faults.plant(name, cell.arch)
        for seed in seed_list(seeds):
            print(json.dumps(dict(reading(cell, seed, a.seconds, False),
                                  fault=name)), flush=True)
        remove()
    return 0


if __name__ == "__main__":
    sys.exit(main())
