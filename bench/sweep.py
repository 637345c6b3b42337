#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest rate it sustains.

  python3 bench/sweep.py --workload mh153m.chat --rates 10,20,30 --seconds 10

One process, one engine and one warmup; then for each rate, lowest first,
the cell's mix at that rate for `--seconds` (after its ramp), and a full
drain before the next rate. A rate is sustained when the backlog does not
grow through the window: the requests due in its last third wait no more
than twice as long for their first token as those due in its first third,
and no more requests than one admission batch are still queued when the
window closes. The knee is the highest rate below the first one that is
not sustained; the sweep stops at that one. Prints one JSON line per rate
and last the knee; the rate a cell runs at (0.8 of the knee) goes into its
traffic file.
"""
from __future__ import annotations

import argparse
import json
import sys

import run
import traffic


def window_stats(rec, seconds: float) -> dict:
    due = [s for s in rec.seen if rec.w0 <= s.due < rec.w0 + seconds]
    ttft = lambda ss: [(s.first if s.first == s.first else rec.end) - s.due
                       for s in ss]
    third = seconds / 3.0
    early = ttft([s for s in due if s.due < rec.w0 + third])
    late = ttft([s for s in due if s.due >= rec.w0 + 2 * third])
    queued = sum(1 for s in due if s.req.t_admitted != s.req.t_admitted
                 or s.req.t_admitted > rec.w1)
    p = run.percentile
    return {"requests": len(due),
            "ttft_p50_early_ms": 1e3 * p(early, 50) if early else None,
            "ttft_p50_late_ms": 1e3 * p(late, 50) if late else None,
            "ttft_p95_ms": 1e3 * p(ttft(due), 95) if due else None,
            "itl_p95_ms": 1e3 * p(rec.gaps, 95) if rec.gaps else None,
            "queued_at_close": queued,
            "tokens_per_s": rec.tokens_in_window / (rec.w1 - rec.w0)}


def sustained(st: dict, batch: int) -> bool:
    e, l_ = st["ttft_p50_early_ms"], st["ttft_p50_late_ms"]
    return (e is not None and l_ is not None and l_ <= 2.0 * e
            and st["queued_at_close"] <= batch)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True, help="comma list, req/s")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    a = ap.parse_args(argv)
    cell = run.Cell(run.ROOT, a.workload)
    rates = sorted(float(r) for r in a.rates.split(","))
    vocab = cell.vocab
    plans = {r: traffic.generate(cell.mix, a.seed + i, vocab, a.seconds,
                                 rate_rps=r) for i, r in enumerate(rates)}
    allp = [p for ps in plans.values() for p in ps]
    try:
        _, _, eng, warmup_s = run.setup(cell, a.seed, allp)
    except run.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return run.NO_CHIP_EXIT
    print(json.dumps({"warmup_s": warmup_s}), flush=True)
    knee = None
    for r in rates:
        rec = run.drive_cell(eng, cell, plans[r], a.seconds)
        eng.run()                               # drain before the next rate
        st = dict(window_stats(rec, a.seconds), rate_rps=r,
                  compiles=rec.compiles)
        st["sustained"] = sustained(st, cell.mix["prefills_per_step"])
        print(json.dumps(st), flush=True)
        if not st["sustained"]:
            break
        knee = r
    print(json.dumps({"knee_rps": knee,
                      "rate_rps": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
