"""Observability layer: metrics registry math and exposition, the span
tracer and its Chrome-trace export, and the scheduler integration — an
exported request trace must reconstruct the measured TTFT / end-to-end
latency exactly, recovery events must land on the affected request's
timeline, a profiler capture must carry every engine phase span, the
admission stamps must be ordered, and telemetry-on serving must stay at
zero steady-state compiles."""
import glob
import json
import math
import os
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import HYENA, HyenaConfig, ModelConfig
from repro.distributed.sharding import unzip
from repro.models.model import init_params
from repro.serve.faults import FaultInjector
from repro.serve.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                                 RESILIENCE_KEYS, ResilienceCounters,
                                 count_compiles, jit_cache_size,
                                 speculative_summary, start_metrics_server)
from repro.serve.scheduler import ContinuousBatchingEngine, Request
from repro.serve.trace import (ANNOTATION_PREFIX, HOST_PID, NULL_TRACER,
                               REQUEST_PID, SPANS, Tracer, stat_key)

MAX_LEN = 48
PROMPT_LENS = (4, 7, 12, 20, 9)
GEN_LENS = (8, 5, 11, 6, 9)


def _hyena_cfg(name="obs-hyena"):
    return ModelConfig(name=name, family="lcsm", n_layers=2,
                       d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                       d_ff=64, vocab=64, act="gelu", norm="layernorm",
                       pattern=(HYENA,),
                       hyena=HyenaConfig(n_filter_heads=2, filter_order=16,
                                         filter_emb=9, distill_order=8),
                       max_seq=512, dtype="float32")


@pytest.fixture(scope="module")
def hyena_model():
    cfg = _hyena_cfg()
    params, _ = unzip(init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _prompts(vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


# ---------------------------------------------------------------------------
# histogram / percentile math
# ---------------------------------------------------------------------------
def test_histogram_buckets_and_counts():
    h = Histogram("h", buckets=(1.0, 2.0, 5.0))
    for v in (0.5, 1.0, 1.5, 3.0, 10.0):
        h.observe(v)
    assert h.count == 5
    assert h.sum == pytest.approx(16.0)
    snap = h.snapshot()
    # cumulative: <=1 holds {0.5, 1.0}, <=2 adds 1.5, <=5 adds 3.0, +Inf all
    assert snap["buckets"] == {"1": 2, "2": 3, "5": 4, "+Inf": 5}
    assert snap["min"] == 0.5 and snap["max"] == 10.0


def test_histogram_percentile_properties():
    h = Histogram("h", buckets=(0.001, 0.01, 0.1, 1.0))
    rng = np.random.default_rng(0)
    vals = rng.uniform(0.0005, 0.5, size=500)
    for v in vals:
        h.observe(float(v))
    qs = [0, 10, 25, 50, 75, 90, 99, 100]
    est = [h.percentile(q) for q in qs]
    # monotone in q, clamped to the observed range
    assert all(a <= b + 1e-12 for a, b in zip(est, est[1:]))
    assert est[0] >= vals.min() and est[-1] <= vals.max()
    # bucketed estimate lands near the true quantile (bucket-width bound)
    true_p50 = float(np.percentile(vals, 50))
    assert abs(est[3] - true_p50) < 0.1


def test_histogram_empty_and_single():
    h = Histogram("h", buckets=(1.0,))
    assert math.isnan(h.percentile(50))
    assert h.snapshot()["p50"] is None
    h.observe(0.25)
    # one observation: every percentile is that value (min==max clamp)
    assert h.percentile(1) == pytest.approx(0.25)
    assert h.percentile(99) == pytest.approx(0.25)


def test_histogram_rejects_bad_buckets():
    with pytest.raises(ValueError):
        Histogram("h", buckets=(2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("h", buckets=(1.0, 1.0))
    with pytest.raises(ValueError):
        Histogram("h", buckets=())


# ---------------------------------------------------------------------------
# registry: get-or-create, kind safety, disabled mode, exposition
# ---------------------------------------------------------------------------
def test_registry_get_or_create_and_kind_clash():
    reg = MetricsRegistry()
    c = reg.counter("serve_x", help="things")
    assert reg.counter("serve_x") is c          # same instrument back
    c.inc()
    c.inc(2)
    assert c.value == 3
    g = reg.gauge("serve_depth")
    g.set(7)
    g.inc(-2)
    assert g.value == 5
    with pytest.raises(ValueError):
        reg.gauge("serve_x")                    # kind clash
    assert reg.get("serve_x") is c
    assert reg.get("nope") is None              # get() never creates
    assert "nope" not in reg.names()


def test_registry_disabled_is_nullop():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("serve_x")
    h = reg.histogram("serve_h")
    assert c is reg.gauge("anything")           # one shared null instrument
    c.inc()
    h.observe(1.0)
    assert h.count == 0 and math.isnan(h.percentile(50))
    assert reg.names() == []
    assert reg.snapshot() == {}
    assert reg.to_prometheus().strip() == ""


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("serve_reqs", help="finished requests").inc(3)
    reg.gauge("serve_depth").set(2)
    h = reg.histogram("serve_lat", buckets=(0.1, 1.0), help="latency")
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.to_prometheus()
    assert "# HELP serve_reqs finished requests" in text
    assert "# TYPE serve_reqs counter" in text
    assert "serve_reqs 3" in text
    assert "# TYPE serve_depth gauge" in text
    assert "serve_depth 2" in text
    assert "# TYPE serve_lat histogram" in text
    assert 'serve_lat_bucket{le="0.1"} 1' in text
    assert 'serve_lat_bucket{le="1"} 2' in text
    assert 'serve_lat_bucket{le="+Inf"} 3' in text
    assert "serve_lat_sum 5.55" in text
    assert "serve_lat_count 3" in text
    assert text.endswith("\n")


def test_resilience_counters_feed_registry():
    reg = MetricsRegistry()
    res = ResilienceCounters(registry=reg)
    res.bump("health_failures")
    res.bump("health_failures", 2)
    assert res.get("health_failures") == 3
    assert reg.get("serve_resilience_health_failures").value == 3
    res.reset()                                 # snapshot resets ...
    assert res.get("health_failures") == 0
    assert sorted(res.snapshot()) == sorted(RESILIENCE_KEYS)
    # ... but the registry counter stays monotonic (Prometheus semantics)
    assert reg.get("serve_resilience_health_failures").value == 3


# ---------------------------------------------------------------------------
# jit_cache_size: cross-version probing, loud degradation
# ---------------------------------------------------------------------------
def test_jit_cache_size_probes_known_spellings():
    class Method:
        def _cache_size(self):
            return 4

    class Attr:
        cache_size = 7

    class NewSpelling:                          # method under the new name
        def cache_size(self):
            return 2

    assert jit_cache_size(Method()) == 4
    assert jit_cache_size(Attr()) == 7
    assert jit_cache_size(NewSpelling()) == 2


def test_jit_cache_size_on_real_jitted_fn(hyena_model):
    """The probe must resolve on this jax version for at least a freshly
    jitted callable — if it returns None here, compile accounting silently
    degraded and the probe list needs a new spelling."""
    fn = jax.jit(lambda x: x + 1)
    fn(jnp.zeros((2,)))
    n = jit_cache_size(fn)
    assert n is not None and n >= 1


def test_jit_cache_size_degrades_loudly(monkeypatch):
    import repro.serve.metrics as M

    class Opaque:
        pass

    monkeypatch.setattr(M, "_jit_cache_warned", False)
    with pytest.warns(RuntimeWarning, match="compile"):
        assert jit_cache_size(Opaque()) is None
    # one-time warning: second call is silent
    import warnings as W
    with W.catch_warnings():
        W.simplefilter("error")
        assert jit_cache_size(Opaque()) is None


# ---------------------------------------------------------------------------
# speculative_summary: explicit fallback chain
# ---------------------------------------------------------------------------
def test_speculative_summary_bases():
    real = speculative_summary({"spec_drafted": 40, "spec_accepted": 30,
                                "spec_slot_rounds": 10})
    assert real["tokens_per_slot_round"] == pytest.approx(4.0)
    assert real["tokens_per_slot_round_basis"] == "spec_slot_rounds"
    legacy = speculative_summary({"spec_drafted": 40, "spec_accepted": 30},
                                 spec_k=4)
    assert legacy["tokens_per_slot_round"] == pytest.approx(4.0)
    assert legacy["tokens_per_slot_round_basis"] == "spec_k"
    assert legacy["acceptance_rate"] == pytest.approx(0.75)


def test_speculative_summary_unknown_basis_warns():
    with pytest.warns(RuntimeWarning, match="spec_slot_rounds"):
        out = speculative_summary({"spec_drafted": 40, "spec_accepted": 30})
    # explicit unknown — not zero, not a fabricated rate
    assert out["tokens_per_slot_round"] is None
    assert out["tokens_per_slot_round_basis"] is None
    assert out["spec_drafted"] == 40            # the drafts stay visible


def test_speculative_summary_no_speculation_is_silent():
    import warnings as W
    with W.catch_warnings():
        W.simplefilter("error")
        out = speculative_summary({})
    assert out["acceptance_rate"] is None
    assert out["tokens_per_slot_round"] is None


# ---------------------------------------------------------------------------
# tracer: spans, ring bounds, Chrome-trace schema
# ---------------------------------------------------------------------------
def test_tracer_spans_and_instants():
    t = [0.0]
    tr = Tracer(clock=lambda: t[0])
    seconds = {}

    def stat(key, dur):
        seconds[key] = seconds.get(key, 0.0) + dur

    with tr.span("tick", n=1, stat=stat, step=1):
        t[0] = 1.0
        with tr.span("decode_step", stat=stat):
            t[0] = 3.0
        t[0] = 4.0
    tr.instant("quarantine", rid=7, detail="nan")
    tr.complete("queue_wait", 0.5, 2.5, rid=7)
    evs = tr.events()
    # inner span closes first
    inner, outer, inst, comp = evs
    assert (inner["name"], inner["ts"], inner["dur"]) == ("decode_step", 1.0, 2.0)
    assert (outer["name"], outer["ts"], outer["dur"]) == ("tick", 0.0, 4.0)
    assert outer["pid"] == HOST_PID and outer["args"] == {"n": 1}
    assert inst["ph"] == "i" and inst["pid"] == REQUEST_PID and inst["tid"] == 7
    assert comp["ph"] == "X" and comp["dur"] == pytest.approx(2.0)
    assert tr.request_timeline(7) == [comp, inst]   # sorted by timestamp
    # each span's duration lands in its seconds counter, on the same clock
    assert seconds == {"phase_s_tick": 4.0, "phase_s_decode_step": 2.0}


def test_tracer_ring_bounds():
    tr = Tracer(capacity=4)
    for i in range(10):
        tr.instant(f"e{i}")
    assert len(tr) == 4
    assert tr.total == 10 and tr.dropped == 6
    assert [e["name"] for e in tr.events()] == ["e6", "e7", "e8", "e9"]
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_chrome_trace_schema(tmp_path):
    t = [100.0]
    tr = Tracer(clock=lambda: t[0])
    with tr.span("tick"):
        t[0] = 100.001
    tr.instant("retire", rid=3, reason="max_tokens")
    doc = tr.to_chrome_trace()
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    names = {(e["name"], e["pid"]) for e in meta}
    assert ("process_name", HOST_PID) in names
    assert ("process_name", REQUEST_PID) in names
    assert ("thread_name", REQUEST_PID) in names    # request 3's track
    span = next(e for e in evs if e["ph"] == "X")
    assert span["ts"] == pytest.approx(0.0, abs=1e-6)      # µs from epoch
    assert span["dur"] == pytest.approx(1000.0, rel=1e-6)  # 1 ms -> 1000 µs
    inst = next(e for e in evs if e["ph"] == "i")
    assert inst["s"] == "t" and inst["tid"] == 3
    assert doc["otherData"]["total_events"] == 2
    # save() round-trips through json
    p = tr.save(str(tmp_path / "trace.json"))
    assert json.load(open(p))["traceEvents"]


def test_null_tracer_is_inert(tmp_path):
    assert not NULL_TRACER.enabled
    seconds = {}
    with NULL_TRACER.span("x"):
        with NULL_TRACER.span("retire.wait",
                              stat=lambda k, d: seconds.update({k: d})):
            pass
    # nothing recorded, but the seconds counter still counts
    assert list(seconds) == ["phase_s_retire_wait"] and seconds[
        "phase_s_retire_wait"] >= 0.0
    NULL_TRACER.instant("z", rid=1)
    NULL_TRACER.complete("w", 0.0, 1.0, rid=1)
    assert len(NULL_TRACER) == 0 and NULL_TRACER.events() == []
    assert NULL_TRACER.to_chrome_trace()["traceEvents"] == []


# ---------------------------------------------------------------------------
# scheduler integration: the trace reconstructs the measured numbers
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def traced_run(hyena_model):
    """One instrumented serving run shared by the reconstruction tests:
    5 requests through 2 slots with tracing + metrics on."""
    cfg, params = hyena_model
    tracer = Tracer()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   tracer=tracer, events_limit=8)
    reqs = [eng.submit(p, max_new_tokens=g)
            for p, g in zip(_prompts(cfg.vocab), GEN_LENS)]
    eng.run()
    return eng, tracer, reqs


def test_trace_reconstructs_ttft_and_latency(traced_run):
    """queue_wait + prefill spans sum to the measured TTFT; the full span
    chain sums to the measured end-to-end latency — exactly, because the
    spans are emitted from the Request's own timestamps. queue_wait ends
    when the request leaves the queue; prefill runs from there to the
    first token (host glue, device work and the wait for the token)."""
    eng, tracer, reqs = traced_run
    for req in reqs:
        assert req.status == "finished"
        tl = tracer.request_timeline(req.rid)
        spans = {e["name"]: e for e in tl if e["ph"] == "X"}
        assert set(spans) == {"queue_wait", "prefill", "decode"}
        assert spans["queue_wait"]["dur"] == pytest.approx(
            req.t_dequeued - req.t_submit, abs=1e-9)
        assert spans["prefill"]["dur"] == pytest.approx(
            req.t_first_token - req.t_dequeued, abs=1e-9)
        ttft = spans["queue_wait"]["dur"] + spans["prefill"]["dur"]
        assert ttft == pytest.approx(req.ttft, abs=1e-9)
        total = ttft + spans["decode"]["dur"]
        assert total == pytest.approx(req.latency, abs=1e-9)
        # contiguous: each stage starts where the previous ended
        assert spans["prefill"]["ts"] == pytest.approx(
            spans["queue_wait"]["ts"] + spans["queue_wait"]["dur"])
        retire = [e for e in tl if e["name"] == "retire"]
        assert len(retire) == 1
        assert retire[0]["args"]["reason"] == "max_tokens"


def test_host_loop_phase_spans_present(traced_run):
    eng, tracer, _ = traced_run
    host = {e["name"] for e in tracer.events() if e["pid"] == HOST_PID}
    assert {"tick", "dispatch", "retire", "retire.wait", "admit",
            "decode_step", "prefill", "admit.wait"} <= host
    # no per-slot decode instants: a request track holds its lifecycle
    # spans and a retire marker only
    assert not [e for e in tracer.events() if e["ph"] == "i"
                and e["name"] in ("decode_tick", "spec_round")]


def test_admission_stamps_ordered(traced_run):
    _, _, reqs = traced_run
    for r in reqs:
        assert (r.t_submit <= r.t_dequeued <= r.t_prefill_enqueued
                <= r.t_admitted <= r.t_first_token), r


@pytest.mark.parametrize("lens,rows,bucket", [((5, 7), 2, 8), ((12,), 1, 16)],
                         ids=["two_rows", "one_row"])
def test_prefill_bucket_and_rows(hyena_model, lens, rows, bucket):
    """A request records the padded length and the real rows of the
    prefill call that admitted it: two same-bucket prompts share one
    two-row call, a lone prompt fills one row of it."""
    cfg, params = hyena_model
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   max_prefills_per_step=2, min_bucket=8)
    rng = np.random.default_rng(1)
    reqs = [eng.submit(rng.integers(0, cfg.vocab, n).astype(np.int32),
                       max_new_tokens=2) for n in lens]
    eng.step()
    assert eng.stats["prefill_calls"] == 1
    for r in reqs:
        assert (r.prefill_bucket, r.prefill_rows) == (bucket, rows)
    eng.run()
    assert [(r.prefill_bucket, r.prefill_rows) for r in reqs] \
        == [(bucket, rows)] * len(lens)


def test_phase_counters_bounded_by_tick(traced_run):
    """Every span has its seconds counter in `stats` and on /metrics; the
    phases inside a tick add up to no more than the tick total."""
    eng, _, _ = traced_run
    st = eng.stats
    assert {stat_key(n) for n in SPANS} <= set(st)
    tick = st["phase_s_tick"]
    assert tick > 0.0
    inside = [n for n in SPANS if n not in ("tick", "warmup")]
    for n in inside:
        assert 0.0 <= st[stat_key(n)] <= tick, n
    top = ("faults", "drift_check", "dispatch", "retire", "deadline_sweep",
           "admit")
    assert sum(st[stat_key(n)] for n in top) <= tick
    assert st["phase_s_retire_wait"] <= st["phase_s_retire"]
    assert (st["phase_s_prefill"] + st["phase_s_admit_wait"]
            <= st["phase_s_admit"])
    assert eng.t_admit == st["phase_s_admit"]
    text = eng.metrics.to_prometheus()
    assert "serve_phase_s_tick " in text and "serve_phase_s_admit_wait " in text


def _profiled_names(logdir):
    """Every event name in the profiler capture under `logdir`."""
    from jax.profiler import ProfileData
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    assert found, "the profiler wrote no trace"
    pd = ProfileData.from_file(sorted(found)[-1])
    return {e.name for plane in pd.planes for line in plane.lines
            for e in line.events}


def test_profiler_capture_holds_every_span(hyena_model, tmp_path):
    """With no tracer bound, one engine run under a profiler capture
    writes every `serve.*` phase span: a capture is the switch. The run
    takes every path: warmup, scripted faults, the drift sentinel,
    speculation, chunked and bucketed prefill, and deadlines."""
    cfg, params = hyena_model
    inj = FaultInjector([{"tick": 2, "kind": "stall", "duration_s": 0.0}],
                        seed=0)
    eng = ContinuousBatchingEngine(
        params, cfg, n_slots=2, max_len=MAX_LEN, spec_k=2, prefill_chunk=16,
        drift_check_every=2, deadline_s=600.0, fault_injector=inj)
    assert not eng.tracer.enabled
    prompts = _prompts(cfg.vocab)
    with jax.profiler.trace(str(tmp_path)):
        eng.warmup(PROMPT_LENS)
        # a lone request that opts out of speculation decodes plainly
        eng.submit_request(Request(rid=99, prompt=prompts[0],
                                   max_new_tokens=3, spec=False))
        eng.run()
        for p, g in zip(prompts, GEN_LENS):
            eng.submit(p, max_new_tokens=g)
        eng.run()
    names = _profiled_names(str(tmp_path))
    want = {ANNOTATION_PREFIX + n for n in SPANS}
    assert want <= names, sorted(want - names)
    assert all(r.status == "finished" for r in eng.finished)


def test_metrics_populated_by_run(traced_run):
    eng, _, reqs = traced_run
    m = eng.metrics
    assert m.get("serve_requests_finished").value == len(reqs)
    assert m.get("serve_ttft_s").count == len(reqs)
    assert m.get("serve_request_latency_s").count == len(reqs)
    assert m.get("serve_tick_latency_s").count >= len(reqs)
    assert m.get("serve_decode_steps").value == eng.stats["decode_steps"]
    fill = m.get("serve_batch_fill_ratio")
    assert fill.count > 0 and 0.0 <= fill.percentile(50) <= 1.0
    # percentiles agree with the engine's own recorded latencies
    lats = sorted(r.latency for r in reqs)
    h = m.get("serve_request_latency_s")
    assert lats[0] - 1e-9 <= h.percentile(50) <= lats[-1] + 1e-9
    # the whole thing expounds without error
    assert "serve_ttft_s_count" in m.to_prometheus()
    json.dumps(m.snapshot())


def test_events_ring_is_bounded(hyena_model):
    """With events_limit=n the recovery log keeps the n newest events while
    the monotonic total and the serve_events_total counter keep counting."""
    cfg, params = hyena_model
    inj = FaultInjector([{"tick": t, "kind": "corrupt", "where": "state",
                          "value": float("nan")} for t in (3, 5, 7, 9)],
                        seed=0)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   fault_injector=inj, events_limit=2)
    for p, g in zip(_prompts(cfg.vocab), GEN_LENS):
        eng.submit(p, max_new_tokens=g)
    eng.run()
    assert eng._events_total >= 3               # one quarantine per corrupt
    assert len(eng.events) == 2                 # ring kept only the newest
    assert eng._events_total > len(eng.events)
    assert eng.metrics.get("serve_events_total").value == eng._events_total


def test_fault_recovery_lands_on_request_timeline(hyena_model):
    """A quarantined request's timeline shows the recovery instants — the
    trace answers 'why was this request slow'."""
    cfg, params = hyena_model
    tracer = Tracer()
    inj = FaultInjector([{"tick": 4, "kind": "corrupt", "where": "state",
                          "value": float("nan")}], seed=0)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   fault_injector=inj, tracer=tracer)
    reqs = [eng.submit(p, max_new_tokens=g)
            for p, g in zip(_prompts(cfg.vocab), GEN_LENS)]
    eng.run()
    assert eng.resilience.get("slot_reprefills") >= 1
    hit = [ev["rid"] for ev in eng.events
           if ev["kind"] == "quarantine" and "rid" in ev]
    assert hit
    tl = tracer.request_timeline(hit[0])
    kinds = {e["name"] for e in tl if e["ph"] == "i"}
    assert "quarantine" in kinds
    # the faulted request still has a complete lifecycle
    assert {e["name"] for e in tl if e["ph"] == "X"} \
        == {"queue_wait", "prefill", "decode"}
    for r in reqs:
        assert r.status in ("finished", "error")


def test_zero_steady_state_compiles_with_telemetry_on():
    """Tracing + metrics must not introduce tracing-unstable values into
    jitted code: after warmup, a fully instrumented serving run triggers no
    XLA compilation (the observability acceptance gate, unit-sized)."""
    cfg = _hyena_cfg("obs-compile-count")
    params, _ = unzip(init_params(jax.random.PRNGKey(0), cfg))
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   tracer=Tracer(), overlap=True)
    eng.warmup(PROMPT_LENS)
    with count_compiles() as scope:
        for p, g in zip(_prompts(cfg.vocab), GEN_LENS):
            eng.submit(p, max_new_tokens=g)
        eng.run()
    assert scope.compiles == 0, "telemetry must stay off the device path"
    assert len(eng.finished) == len(GEN_LENS)
    assert len(eng.tracer) > 0                  # ... while actually tracing


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------
def test_metrics_http_endpoint():
    reg = MetricsRegistry()
    reg.counter("serve_reqs").inc(5)
    tr = Tracer()
    tr.instant("tick")
    server = start_metrics_server(reg, 0, tracer=tr,
                                  extra=lambda: {"stats": {"ticks": 9}})
    try:
        port = server.server_address[1]
        base = f"http://127.0.0.1:{port}"
        text = urllib.request.urlopen(f"{base}/metrics").read().decode()
        assert "serve_reqs 5" in text
        doc = json.load(urllib.request.urlopen(f"{base}/metrics.json"))
        assert doc["metrics"]["serve_reqs"] == 5
        assert doc["stats"] == {"ticks": 9}
        trace = json.load(urllib.request.urlopen(f"{base}/trace.json"))
        assert any(e.get("name") == "tick" for e in trace["traceEvents"])
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(f"{base}/nope")
    finally:
        server.shutdown()
