"""Resilience layer: seeded fault injection, state-integrity guards,
quarantine + exact re-prefill recovery, graceful degradation, and engine
checkpoint/restore.

The load-bearing contract: under a scripted fault schedule the engine
completes EVERY submitted request with a terminal status (zero crashes),
poisoned requests finish with ERROR after bounded retries, and requests
whose slots were never faulted produce greedy outputs token-identical to a
fault-free run (the recovered request itself may diverge by one float-path:
re-prefill vs step-by-step decode are equal only to numerical tolerance).
"""
import json
import pickle

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ATTN, HYENA, HyenaConfig, ModelConfig
from repro.distributed.sharding import unzip
from repro.models.model import init_cache, modal_state_bound, slot_health
from repro.serve.checkpoint import restore_engine, save_engine
from repro.serve.engine import GenerationEngine
from repro.serve.faults import (FaultEvent, FaultInjector, corrupt_cache_slot)
from repro.serve.metrics import ResilienceCounters, count_compiles
from repro.serve.sampling import sample_token_slots
from repro.serve.scheduler import (ContinuousBatchingEngine, Request,
                                   SamplingParams)

MAX_LEN = 48
PROMPT_LENS = (4, 7, 12, 20, 9)
GEN_LENS = (8, 5, 11, 6, 9)


def _hyena_cfg():
    return ModelConfig(name="res-hyena", family="lcsm", n_layers=2,
                       d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                       d_ff=64, vocab=64, act="gelu", norm="layernorm",
                       pattern=(HYENA,),
                       hyena=HyenaConfig(n_filter_heads=2, filter_order=16,
                                         filter_emb=9, distill_order=8),
                       max_seq=512, dtype="float32")


def _attn_cfg():
    return ModelConfig(name="res-attn", family="dense", n_layers=2,
                       d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                       d_ff=64, vocab=64, act="gelu", norm="layernorm",
                       pattern=(ATTN,), max_seq=512, dtype="float32")


@pytest.fixture(scope="module")
def hyena_model():
    cfg = _hyena_cfg()
    params, _ = unzip(init_params_seeded(cfg))
    return cfg, params


@pytest.fixture(scope="module")
def attn_model():
    cfg = _attn_cfg()
    params, _ = unzip(init_params_seeded(cfg))
    return cfg, params


def init_params_seeded(cfg):
    from repro.models.model import init_params
    return init_params(jax.random.PRNGKey(0), cfg)


def _prompts(vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


_SEQ_CACHE = {}


def _sequential_greedy(cfg, params, mode):
    """Fault-free per-request baseline (cached per module run)."""
    key = (cfg.name, mode)
    if key not in _SEQ_CACHE:
        eng = GenerationEngine(params, cfg, max_len=MAX_LEN, mode=mode)
        prompts = _prompts(cfg.vocab)
        _SEQ_CACHE[key] = [
            np.asarray(eng.generate(jax.random.PRNGKey(1),
                                    jnp.asarray(p)[None], g)[0][0])
            for p, g in zip(prompts, GEN_LENS)]
    return _SEQ_CACHE[key]


def _affected_rids(eng):
    """Requests a fault actually touched (quarantined, expired, rejected,
    poisoned, or recovered through a pool rebuild / engine demotion — the
    latter two requeue every resident, so treat every request seen at the
    event's tick as affected)."""
    rids = {ev["rid"] for ev in eng.events if "rid" in ev}
    if any(ev["kind"] in ("pool_rebuild", "engine_demotion")
           for ev in eng.events):
        rids |= {r.rid for r in eng.finished}
    return rids


def _check_unaffected_exact(eng, want):
    """Every request reached a terminal status; fault-untouched requests are
    token-identical to the fault-free baseline."""
    by_rid = {r.rid: r for r in eng.finished}
    assert sorted(by_rid) == list(range(len(want)))
    affected = _affected_rids(eng)
    assert len(affected) < len(want), "schedule faulted every request"
    for rid, w in enumerate(want):
        r = by_rid[rid]
        assert r.status in ("finished", "error")
        if rid not in affected:
            assert r.status == "finished"
            np.testing.assert_array_equal(np.asarray(r.tokens), w)


def _run_with_faults(cfg, params, mode, events, *, n_slots=2, spec_k=0,
                     **kw):
    inj = FaultInjector(events, seed=0)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=n_slots,
                                   max_len=MAX_LEN, mode=mode,
                                   spec_k=spec_k, fault_injector=inj, **kw)
    for p, g in zip(_prompts(cfg.vocab), GEN_LENS):
        eng.submit(p, max_new_tokens=g)
    eng.run()
    return eng, inj


# ---------------------------------------------------------------------------
# fault injection + quarantine recovery, per cache kind
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode,where", [("distilled", "state"),
                                        ("cached_conv", "conv"),
                                        ("cached_conv", "any")])
def test_corruption_recovers_lcsm(hyena_model, mode, where):
    """NaN/Inf injected into a resident slot's cache row mid-decode trips
    the health guard; the slot is quarantined and its request re-prefilled
    from committed tokens. Untouched requests stay bit-identical, all
    requests complete, zero crashes."""
    cfg, params = hyena_model
    want = _sequential_greedy(cfg, params, mode)
    value = float("inf") if where == "conv" else float("nan")
    eng, inj = _run_with_faults(
        cfg, params, mode,
        [{"tick": 4, "kind": "corrupt", "where": where, "value": value}])
    assert [e for e in inj.log if e["kind"] == "corrupt"]
    assert eng.resilience.get("health_failures") >= 1
    assert eng.resilience.get("slot_reprefills") >= 1
    _check_unaffected_exact(eng, want)


def test_corruption_recovers_attention(attn_model):
    """Attention-KV pool: "state" has no modal leaves so the injector falls
    back to poisoning any float leaf (the kv ring). The NaN propagates into
    the logits, the fused logits-finiteness check catches it."""
    cfg, params = attn_model
    want = _sequential_greedy(cfg, params, "distilled")
    eng, inj = _run_with_faults(
        cfg, params, "distilled",
        [{"tick": 4, "kind": "corrupt", "where": "state", "value": "nan"}])
    assert [e for e in inj.log if e["kind"] == "corrupt"]
    assert eng.resilience.get("health_failures") >= 1
    _check_unaffected_exact(eng, want)


def test_fault_mid_speculation(hyena_model):
    """Corruption + an injected dispatch fault while the engine is running
    speculative rounds: the state-only guard quarantines the slot, the
    FaultError tick is skipped without invalidating the pool, and untouched
    requests remain identical to the fault-free spec run (which is itself
    greedy-identical to sequential decode)."""
    cfg, params = hyena_model
    want = _sequential_greedy(cfg, params, "distilled")
    eng, inj = _run_with_faults(
        cfg, params, "distilled",
        [{"tick": 4, "kind": "corrupt", "where": "state", "value": "nan"},
         {"tick": 8, "kind": "raise"}],
        spec_k=2)
    assert eng.resilience.get("health_failures") >= 1
    assert eng.resilience.get("dispatch_faults") == 1
    _check_unaffected_exact(eng, want)


def test_poisoned_after_bounded_retries(hyena_model):
    """A slot corrupted on every tick exhausts max_retries and its request
    completes with ERROR status ("poisoned") — it never wedges the engine —
    while other requests finish normally."""
    cfg, params = hyena_model
    want = _sequential_greedy(cfg, params, "distilled")
    events = [{"tick": t, "kind": "corrupt", "where": "state", "slot": 0}
              for t in range(3, 60)]
    eng, _ = _run_with_faults(cfg, params, "distilled", events,
                              max_retries=1, retry_backoff_ticks=0)
    poisoned = [r for r in eng.finished if r.finish_reason == "poisoned"]
    assert poisoned and all(r.status == "error" for r in poisoned)
    assert eng.resilience.get("poisoned") == len(poisoned)
    ok = [r for r in eng.finished if r.status == "finished"]
    assert len(ok) + len(poisoned) == len(want)
    for r in ok:
        if r.rid not in _affected_rids(eng):
            np.testing.assert_array_equal(np.asarray(r.tokens), want[r.rid])


def test_spec_demotion_after_repeated_quarantine(hyena_model):
    """Two quarantines of the same request demote it from speculation to
    plain decode (demote_spec_after default 2); it still completes. A
    single long request in a 1-slot pool pins both corruptions to it."""
    cfg, params = hyena_model
    inj = FaultInjector(
        [{"tick": 4, "kind": "corrupt", "where": "state", "slot": 0},
         {"tick": 10, "kind": "corrupt", "where": "state", "slot": 0}],
        seed=0)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=1, max_len=MAX_LEN,
                                   mode="distilled", spec_k=2,
                                   fault_injector=inj, max_retries=5)
    req = eng.submit(_prompts(cfg.vocab)[0], max_new_tokens=30)
    eng.run()
    assert req.retries == 2 and req.spec is False
    assert eng.resilience.get("spec_demotions") == 1
    assert req.status == "finished" and len(req.tokens) == 30


def test_engine_demotion_to_cached_conv(hyena_model):
    """Repeated distilled-path corruption (opt-in demote_engine_after)
    demotes the whole engine to the exact cached-conv kind; every request
    still reaches a terminal status and new decode runs conv-exact."""
    cfg, params = hyena_model
    eng, _ = _run_with_faults(
        cfg, params, "distilled",
        [{"tick": 4, "kind": "corrupt", "where": "state", "slot": 0},
         {"tick": 10, "kind": "corrupt", "where": "state", "slot": 0}],
        max_retries=5, demote_engine_after=2)
    assert eng.mode == "cached_conv" and eng._cache_kind == "conv"
    assert eng.resilience.get("engine_demotions") == 1
    assert len(eng.finished) == len(GEN_LENS)
    assert all(r.status in ("finished", "error") for r in eng.finished)


# ---------------------------------------------------------------------------
# deadlines, bounded queue, watchdog
# ---------------------------------------------------------------------------
def test_deadline_expiry_during_chunked_prefill(hyena_model):
    """A request whose deadline expires while its prompt is mid-chunked-
    prefill is cancelled (ERROR "deadline"), its reserved slot is freed, and
    the remaining requests complete bit-exactly."""
    cfg, params = hyena_model
    want = _sequential_greedy(cfg, params, "distilled")
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   mode="distilled", prefill_chunk=8)
    doomed = Request(rid=100, prompt=_prompts(cfg.vocab, seed=3)[3],
                     max_new_tokens=6, sampling=SamplingParams(),
                     deadline_s=0.0)
    eng.submit_request(doomed)
    for p, g in zip(_prompts(cfg.vocab), GEN_LENS):
        eng.submit(p, max_new_tokens=g)
    eng.run()
    assert doomed.status == "error" and doomed.finish_reason == "deadline"
    assert eng.resilience.get("deadline_expiries") >= 1
    by_rid = {r.rid: r for r in eng.finished}
    for rid, w in enumerate(want):
        assert by_rid[rid].status == "finished"
        np.testing.assert_array_equal(np.asarray(by_rid[rid].tokens), w)


def test_bounded_queue_rejection(hyena_model):
    """Admission control: submissions past max_queue complete immediately
    with ERROR "rejected" instead of growing the queue; accepted requests
    are unaffected and bit-exact."""
    cfg, params = hyena_model
    want = _sequential_greedy(cfg, params, "distilled")
    eng = ContinuousBatchingEngine(params, cfg, n_slots=1, max_len=MAX_LEN,
                                   mode="distilled", max_queue=2)
    reqs = [eng.submit(p, max_new_tokens=g)
            for p, g in zip(_prompts(cfg.vocab), GEN_LENS)]
    rejected = [r for r in reqs if r.finish_reason == "rejected"]
    accepted = [r for r in reqs if r.finish_reason != "rejected"]
    assert len(rejected) == 3 and len(accepted) == 2
    assert all(r.status == "error" for r in rejected)
    assert eng.resilience.get("rejected") == 3
    eng.run()
    for r in accepted:
        assert r.status == "finished"
        np.testing.assert_array_equal(np.asarray(r.tokens), want[r.rid])
    assert len(eng.finished) == len(reqs)  # rejections count as completions


def test_stall_trips_watchdog(hyena_model):
    """An injected host-loop stall exceeds the tick watchdog; the trip is
    counted and decode output is unaffected (determinism is positional, not
    timing-dependent)."""
    cfg, params = hyena_model
    want = _sequential_greedy(cfg, params, "distilled")
    eng, inj = _run_with_faults(
        cfg, params, "distilled",
        [{"tick": 3, "kind": "stall", "duration_s": 0.03}],
        watchdog_s=0.01)
    assert eng.resilience.get("watchdog_trips") >= 1
    assert [e for e in inj.log if e["kind"] == "stall"]
    by_rid = {r.rid: r for r in eng.finished}
    for rid, w in enumerate(want):
        np.testing.assert_array_equal(np.asarray(by_rid[rid].tokens), w)


def test_forced_expiry_event(hyena_model):
    """The "expire" fault kind force-expires one resident request; it
    finishes with ERROR "deadline" and the rest are untouched."""
    cfg, params = hyena_model
    want = _sequential_greedy(cfg, params, "distilled")
    eng, _ = _run_with_faults(cfg, params, "distilled",
                              [{"tick": 5, "kind": "expire"}])
    expired = [r for r in eng.finished if r.finish_reason == "deadline"]
    assert len(expired) == 1 and expired[0].status == "error"
    _check_unaffected_exact(eng, want)


# ---------------------------------------------------------------------------
# checkpoint / restore
# ---------------------------------------------------------------------------
def test_checkpoint_kill_restore_bit_exact(hyena_model, tmp_path):
    """Snapshot a mid-stream engine, "kill" it, restore into a fresh engine
    and drain: every request's greedy tokens are identical to an
    uninterrupted run."""
    cfg, params = hyena_model
    want = _sequential_greedy(cfg, params, "distilled")
    path = str(tmp_path / "engine.ckpt")

    eng_a = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                     mode="distilled")
    for p, g in zip(_prompts(cfg.vocab), GEN_LENS):
        eng_a.submit(p, max_new_tokens=g)
    for _ in range(8):
        if eng_a.has_work:
            eng_a.step()
    save_engine(eng_a, path)
    assert eng_a.resilience.get("checkpoint_saves") == 1
    del eng_a  # the "kill"

    eng_b = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                     mode="distilled")
    restore_engine(eng_b, path)
    assert eng_b.resilience.get("checkpoint_restores") == 1
    eng_b.run()
    by_rid = {r.rid: r for r in eng_b.finished}
    assert sorted(by_rid) == list(range(len(want)))
    for rid, w in enumerate(want):
        assert by_rid[rid].status == "finished"
        np.testing.assert_array_equal(np.asarray(by_rid[rid].tokens), w)


def test_checkpoint_shape_mismatch_rejected(hyena_model, tmp_path):
    cfg, params = hyena_model
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN)
    state = save_engine(eng)
    other = ContinuousBatchingEngine(params, cfg, n_slots=3, max_len=MAX_LEN)
    with pytest.raises(ValueError, match="n_slots"):
        restore_engine(other, state)
    bad = dict(state, format=99)
    with pytest.raises(ValueError, match="format"):
        restore_engine(ContinuousBatchingEngine(params, cfg, n_slots=2,
                                                max_len=MAX_LEN), bad)
    # a snapshot from a HIGHER ladder rung cannot restore into a lower one
    # (the reverse direction — saved lower, engine higher — replays the
    # demotion instead; covered in test_epoch.py)
    up = dict(state, mode="distilled")
    with pytest.raises(ValueError, match="mode"):
        restore_engine(ContinuousBatchingEngine(params, cfg, n_slots=2,
                                                max_len=MAX_LEN,
                                                mode="epoch"), up)


# ---------------------------------------------------------------------------
# guards + compile budget
# ---------------------------------------------------------------------------
def test_zero_steady_state_compiles_with_guards(hyena_model):
    """The fused health checks (and the host-side deadline/watchdog paths)
    add ZERO steady-state XLA compiles after warmup — the acceptance
    criterion that keeps the guards on by default."""
    cfg, params = hyena_model
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   mode="distilled", health_every=1,
                                   deadline_s=100.0, watchdog_s=100.0)
    eng.warmup(PROMPT_LENS)
    for p, g in zip(_prompts(cfg.vocab), GEN_LENS):
        eng.submit(p, max_new_tokens=g)
    with count_compiles() as scope:
        eng.run()
    assert scope.compiles == 0
    assert all(r.status == "finished" for r in eng.finished)


def test_slot_health_flags_only_poisoned_rows(hyena_model):
    """Unit check of the fused guard: a clean pool is all-healthy; poisoning
    one slot's modal state flags exactly that slot; a modal-norm blowup past
    the pole-derived bound is flagged without any non-finite values."""
    cfg, params = hyena_model
    cache, _ = unzip(init_cache(cfg, 4, MAX_LEN, cache_kind="native",
                                per_slot=True))
    logits = jnp.zeros((4, cfg.vocab), jnp.float32)
    bound = modal_state_bound(params, cfg)
    assert np.isfinite(bound) and bound > 0
    assert np.asarray(slot_health(cache, logits, bound)).all()
    bad = corrupt_cache_slot(cache, 2, "state", float("nan"))
    h = np.asarray(slot_health(bad, logits, bound))
    assert not h[2] and h[[0, 1, 3]].all()
    blown = corrupt_cache_slot(cache, 1, "state", bound * 10.0)
    h2 = np.asarray(slot_health(blown, logits, bound))
    assert not h2[1] and h2[[0, 2, 3]].all()


def test_corrupt_cache_slot_is_surgical(hyena_model):
    """The injector only touches the targeted slot's rows; positions and
    every other slot are bit-identical."""
    cfg, params = hyena_model
    cache, _ = unzip(init_cache(cfg, 3, MAX_LEN, cache_kind="native",
                                per_slot=True))
    bad = corrupt_cache_slot(cache, 1, "state", float("nan"))
    np.testing.assert_array_equal(np.asarray(bad["pos"]),
                                  np.asarray(cache["pos"]))
    for (lk, lv) in cache["groups"].items():
        for k, v in lv.items():
            nv = np.asarray(bad["groups"][lk][k])
            ov = np.asarray(v)
            np.testing.assert_array_equal(nv[:, 0], ov[:, 0])
            np.testing.assert_array_equal(nv[:, 2], ov[:, 2])
            if k in ("x_re", "x_im"):
                assert np.isnan(nv[:, 1]).all()


# ---------------------------------------------------------------------------
# degenerate sampling + plumbing units
# ---------------------------------------------------------------------------
def test_degenerate_sampling_rows():
    """Poisoned or over-filtered logits rows sample a deterministic argmax
    fallback instead of NaN-dependent junk: an all-NaN row yields token 0,
    a top_p=0 row yields its argmax, and healthy rows are untouched."""
    V = 16
    rng = np.random.default_rng(0)
    healthy = rng.normal(size=(V,)).astype(np.float32)
    logits = jnp.stack([jnp.asarray(healthy),
                        jnp.full((V,), jnp.nan),
                        jnp.asarray(healthy)])
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    toks = np.asarray(sample_token_slots(
        keys, logits,
        temperature=jnp.array([0.7, 0.7, 0.7]),
        top_k=jnp.zeros((3,), jnp.int32),
        top_p=jnp.array([1.0, 1.0, 0.0])))
    assert toks[1] == 0                       # all-NaN: masked argmax
    assert toks[2] == int(np.argmax(healthy))  # empty nucleus: argmax
    assert 0 <= toks[0] < V
    # greedy rows ignore NaNs entirely
    g = np.asarray(sample_token_slots(
        keys, logits, temperature=jnp.zeros((3,)),
        top_k=jnp.zeros((3,), jnp.int32), top_p=jnp.ones((3,))))
    assert g[1] == 0 and g[0] == int(np.argmax(healthy))


def test_fault_schedule_json_roundtrip(tmp_path):
    inj = FaultInjector(
        [FaultEvent(tick=3, kind="corrupt", where="conv",
                    value=float("inf")),
         FaultEvent(tick=5, kind="stall", duration_s=0.5),
         {"tick": 9, "kind": "corrupt", "value": "nan", "slot": 1}],
        seed=7)
    back = FaultInjector.from_json(inj.to_json())
    assert back.seed == 7
    assert [e.to_dict() for e in back.events] == \
        [e.to_dict() for e in inj.events]
    p = tmp_path / "sched.json"
    p.write_text(inj.to_json())
    assert len(FaultInjector.from_json(str(p)).events) == 3
    with pytest.raises(ValueError, match="kind"):
        FaultEvent(tick=0, kind="meteor")


def test_resilience_counters_snapshot_stable():
    c = ResilienceCounters()
    snap = c.snapshot()
    assert snap["health_failures"] == 0 and "poisoned" in snap
    c.bump("health_failures")
    c.bump("custom_key", 3)
    assert c.get("health_failures") == 1 and c.get("custom_key") == 3
    assert c.total_faults == 1
    c.reset()
    assert c.total_faults == 0 and c.get("custom_key") == 0


def test_checkpoint_pickles_cleanly(hyena_model, tmp_path):
    """The on-disk snapshot is plain pickle of host data — no jax arrays or
    device handles leak into it."""
    cfg, params = hyena_model
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN)
    eng.submit(_prompts(cfg.vocab)[0], max_new_tokens=4)
    eng.step()
    path = str(tmp_path / "e.ckpt")
    save_engine(eng, path)
    with open(path, "rb") as f:
        state = pickle.load(f)
    leaves = jax.tree.leaves(state["cache"])
    assert all(isinstance(x, np.ndarray) for x in leaves)
    assert state["format"] == 2
    assert "mesh" in state     # format-2 slot-pool layout metadata
    if eng.mesh is None:
        assert state["mesh"] is None
    else:
        assert state["mesh"]["n_shards"] == eng._n_shards
    assert json.dumps(state["resilience"])  # JSON-serializable counters


# ---------------------------------------------------------------------------
# the serving CLI fails loudly on a genuine (non-injected) fault
# ---------------------------------------------------------------------------
def test_serve_stream_reports_genuine_dispatch_fault(hyena_model,
                                                     monkeypatch):
    """A real exception inside a decode dispatch is absorbed by the engine
    (pool rebuilt, residents recovered) but the launcher must still count
    the stream as failed; an injected fault schedule exempts it."""
    from repro.launch.serve import build_parser, serve_stream, stream_problems
    cfg, params = hyena_model
    args = build_parser().parse_args([
        "--arch", cfg.name, "--stream", "--slots", "2", "--n-requests", "3",
        "--prompt-lens", "4,7", "--gen", "4", "--rate", "1000"])
    clean = serve_stream(params, cfg, args)[1]
    assert stream_problems(clean, args) == []
    real = ContinuousBatchingEngine._dispatch_decode
    calls = {"n": 0}

    def flaky(self):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("device lost")
        return real(self)

    monkeypatch.setattr(ContinuousBatchingEngine, "_dispatch_decode", flaky)
    eng, m = serve_stream(params, cfg, args)
    assert m["resilience"]["dispatch_faults"] == 1
    assert any("dispatch fault" in p for p in stream_problems(m, args))
    injected = build_parser().parse_args(
        ["--arch", cfg.name, "--fault-schedule", '{"events": []}'])
    assert stream_problems(m, injected) == []
