# Tiered test entry points (see pytest.ini: `slow` tests are deselected by
# default, so `test-fast` is the tier-1 suite the driver runs).
PY := PYTHONPATH=src python

.PHONY: test-fast test-all test-slow bench bench-serve bench-check bench-chaos

test-fast:
	$(PY) -m pytest -x -q

test-all:
	$(PY) -m pytest -q -m "slow or not slow"

test-slow:
	$(PY) -m pytest -q -m slow

bench:
	$(PY) -m benchmarks.run

# serving perf trajectory: tok/s (+ decode tok/s and speculative acceptance),
# latency/TTFT percentiles, and prefill compile counts per mode, written to
# BENCH_serve.json for cross-PR tracking. Also measures the telemetry layer
# (tracer + metrics) on vs off in the same run — the `observability` row —
# the distilled-vs-exact drift at growing horizons (`error_vs_length`), the
# drift sentinel's saturated-decode overhead (`sentinel`; gated <=2% with
# zero steady-state compiles by check_regression --drift), and writes the
# telemetry-on request trace to BENCH_serve_trace.json (Chrome-trace JSON;
# load in https://ui.perfetto.dev).
# On the CPU the scaling sweep runs over 4 forced host devices (the flag
# does nothing on an accelerator, where the sweep takes the chips it has).
bench-serve:
	XLA_FLAGS=--xla_force_host_platform_device_count=4 \
	    $(PY) -m benchmarks.run --only serve_stream --json BENCH_serve.json

# regression gate: re-run the serving bench and compare against the
# committed baseline (fails on a >15% tok/s drop, a speculative-decode
# floor violation, or >2% telemetry overhead on saturated decode).
# CI uses this with the pre-bench copy as baseline.
bench-check:
	cp BENCH_serve.json /tmp/BENCH_baseline.json
	$(MAKE) bench-serve
	$(PY) -m benchmarks.check_regression \
	    --baseline /tmp/BENCH_baseline.json --new BENCH_serve.json

# chaos gate: the request stream under the standard seeded fault schedule
# (benchmarks/bench_throughput.CHAOS_SCHEDULE) per cache kind, plus the
# distilled_drift row (silent sign-flip of a slot's modal state; the drift
# sentinel must alarm and demote the engine to the exact epoch path). Fails
# if any request never reached a terminal status; recovered-fault counters
# (quarantines, re-prefills, watchdog trips, ...) are report-only. Runs
# nightly in CI.
bench-chaos:
	$(PY) -m benchmarks.run --only serve_chaos --json BENCH_chaos.json
	$(PY) -m benchmarks.check_regression --chaos BENCH_chaos.json
