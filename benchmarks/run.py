"""Benchmark driver: one module per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--only fig1.1] [--json out.json]

Prints ``name,us_per_call,derived`` CSV rows. Suites may additionally return
a structured metrics dict; --json collects those into one file (used by
`make bench-serve` to track the serving perf trajectory across PRs). All
models are width-reduced (CPU container); the comparison *structure* matches
the paper's figures.
"""
import argparse
import json
import sys
import traceback

sys.path.insert(0, "src")

from benchmarks import (bench_distill, bench_kernels, bench_memory,
                        bench_prefill_strategies, bench_prompt_scaling,
                        bench_state_dim, bench_throughput)
from repro.launch.compile_cache import enable_compile_cache

SUITES = {
    "fig1.1_throughput": bench_throughput.main,
    "serve_stream": bench_throughput.stream_main,
    "serve_chaos": bench_throughput.chaos_main,
    "fig5.3_prompt_scaling": bench_prompt_scaling.main,
    "fig5.4_memory": bench_memory.main,
    "sec5.4_state_dim": bench_state_dim.main,
    "sec3.4_prefill": bench_prefill_strategies.main,
    "fig5.2_distill": bench_distill.main,
    "kernels": bench_kernels.main,
}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", type=str, default=None)
    ap.add_argument("--json", type=str, default=None,
                    help="write structured suite metrics to this file")
    args = ap.parse_args()
    enable_compile_cache()
    print("name,us_per_call,derived")
    rows = []
    data = {}

    def out(r):
        print(r, flush=True)
        rows.append(r)

    failures = 0
    for name, fn in SUITES.items():
        if args.only and args.only not in name:
            continue
        try:
            ret = fn(out)
            if isinstance(ret, dict):
                data.update(ret)
        except Exception:
            failures += 1
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(data, f, indent=2, sort_keys=True)
        print(f"[bench] wrote {args.json}", file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
