"""Continuous-batching scheduler: interleaved slot-pool serving must be
token-for-token identical to sequential single-request generation (greedy),
and the slot bookkeeping (admission, eviction, per-slot sampling params)
must be exact."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ATTN, HYENA, HyenaConfig, ModelConfig
from repro.distributed.sharding import unzip
from repro.models.model import (init_cache, init_params, prefill,
                                reset_cache_slot, write_cache_slot)
from repro.serve.engine import GenerationEngine
from repro.serve.sampling import sample_token_slots
from repro.serve.scheduler import (ContinuousBatchingEngine, SamplingParams,
                                   run_request_stream,
                                   synthesize_request_stream)

MAX_LEN = 48
PROMPT_LENS = (4, 7, 12, 20, 9)
GEN_LENS = (8, 5, 11, 6, 9)


def _hyena_cfg():
    return ModelConfig(name="sched-hyena", family="lcsm", n_layers=2,
                       d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                       d_ff=64, vocab=64, act="gelu", norm="layernorm",
                       pattern=(HYENA,),
                       hyena=HyenaConfig(n_filter_heads=2, filter_order=16,
                                         filter_emb=9, distill_order=8),
                       max_seq=512, dtype="float32")


def _attn_cfg():
    return ModelConfig(name="sched-attn", family="dense", n_layers=2,
                       d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
                       d_ff=64, vocab=64, act="gelu", norm="layernorm",
                       pattern=(ATTN,), max_seq=512, dtype="float32")


@pytest.fixture(scope="module")
def hyena_model():
    cfg = _hyena_cfg()
    params, _ = unzip(init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


@pytest.fixture(scope="module")
def attn_model():
    cfg = _attn_cfg()
    params, _ = unzip(init_params(jax.random.PRNGKey(0), cfg))
    return cfg, params


def _prompts(vocab, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=n).astype(np.int32)
            for n in PROMPT_LENS]


def _sequential_greedy(cfg, params, prompts, gens, mode):
    eng = GenerationEngine(params, cfg, max_len=MAX_LEN, mode=mode)
    return [np.asarray(eng.generate(jax.random.PRNGKey(1),
                                    jnp.asarray(p)[None], g)[0][0])
            for p, g in zip(prompts, gens)]


# ---------------------------------------------------------------------------
# Consistency: interleaved == sequential, token for token
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("mode", ["distilled", "cached_conv", "epoch"])
def test_interleaved_matches_sequential_lcsm(hyena_model, mode, overlap):
    """5 concurrent requests with different prompt lengths through 2 slots
    (forces queueing + eviction + slot reuse) produce exactly the tokens of
    5 sequential single-request runs — in all three LCSM deployment modes,
    with both the overlapped (async) and synchronous host loops."""
    cfg, params = hyena_model
    prompts = _prompts(cfg.vocab)
    want = _sequential_greedy(cfg, params, prompts, GEN_LENS, mode)
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   mode=mode, overlap=overlap)
    reqs = [eng.submit(p, max_new_tokens=g)
            for p, g in zip(prompts, GEN_LENS)]
    eng.run()
    for r, w in zip(reqs, want):
        assert r.status == "finished" and r.finish_reason == "max_tokens"
        np.testing.assert_array_equal(np.asarray(r.tokens), w)


def test_interleaved_matches_sequential_attention(attn_model):
    """Same property for the attention-KV slot pool (per-slot positions in
    the kv cache writes, rope, and causal masks)."""
    cfg, params = attn_model
    prompts = _prompts(cfg.vocab)
    want = _sequential_greedy(cfg, params, prompts, GEN_LENS, "distilled")
    eng = ContinuousBatchingEngine(params, cfg, n_slots=3, max_len=MAX_LEN)
    reqs = [eng.submit(p, max_new_tokens=g)
            for p, g in zip(prompts, GEN_LENS)]
    eng.run()
    for r, w in zip(reqs, want):
        np.testing.assert_array_equal(np.asarray(r.tokens), w)


def test_reset_on_evict_is_equivalent(hyena_model):
    """Slot reuse must not leak state: explicit zeroing on eviction changes
    nothing (admission overwrites the slot)."""
    cfg, params = hyena_model
    prompts = _prompts(cfg.vocab)
    outs = []
    for reset in (False, True):
        eng = ContinuousBatchingEngine(params, cfg, n_slots=2,
                                       max_len=MAX_LEN,
                                       reset_on_evict=reset)
        reqs = [eng.submit(p, max_new_tokens=g)
                for p, g in zip(prompts, GEN_LENS)]
        eng.run()
        outs.append([list(r.tokens) for r in reqs])
    assert outs[0] == outs[1]


# ---------------------------------------------------------------------------
# Slot bookkeeping
# ---------------------------------------------------------------------------
def test_admission_eviction_bookkeeping(hyena_model):
    # overlap=False: this test asserts host-visible state between individual
    # ticks, which the synchronous loop defines (the overlapped loop retires
    # each tick's tokens one step later by design)
    cfg, params = hyena_model
    prompts = _prompts(cfg.vocab)[:3]
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN,
                                   max_prefills_per_step=2, overlap=False)
    # the first request samples: the sampled-decode counter covers exactly
    # the three decode ticks it is resident for
    reqs = [eng.submit(p, max_new_tokens=4,
                       sampling=SamplingParams(temperature=float(i == 0)))
            for i, p in enumerate(prompts)]
    assert [r.status for r in reqs] == ["queued"] * 3
    eng.step()
    # two slots filled, third request still queued; FIFO admission order
    assert reqs[0].status == "running" and reqs[1].status == "running"
    assert reqs[2].status == "queued"
    assert eng.n_active == 2 and eng.n_free == 0 and len(eng.queue) == 1
    assert {reqs[0].slot, reqs[1].slot} == {0, 1}
    # first token was emitted at admission, then one decode token
    assert len(reqs[0].tokens) == 2
    eng.run()
    assert all(r.status == "finished" for r in reqs)
    assert all(len(r.tokens) == 4 for r in reqs)
    assert all(r.finish_reason == "max_tokens" for r in reqs)
    assert eng.n_active == 0 and eng.n_free == 2 and not eng.queue
    assert eng.stats["admitted"] == 3 and eng.stats["evicted"] == 3
    assert eng.stats["sampled_decode_steps"] == 3
    assert eng.stats["decode_steps"] == 6
    # request 3 reused a slot freed by an earlier eviction
    assert reqs[2].t_admitted >= min(reqs[0].t_finished, reqs[1].t_finished)


def test_eos_evicts_early(hyena_model):
    cfg, params = hyena_model
    prompts = _prompts(cfg.vocab)
    base = ContinuousBatchingEngine(params, cfg, n_slots=1, max_len=MAX_LEN)
    ref = base.submit(prompts[0], max_new_tokens=8)
    base.run()
    eos = ref.tokens[2]
    eng = ContinuousBatchingEngine(params, cfg, n_slots=1, max_len=MAX_LEN)
    req = eng.submit(prompts[0], max_new_tokens=8, eos_id=eos)
    eng.run()
    assert req.finish_reason == "eos"
    assert req.tokens == ref.tokens[:3]        # stops at (and includes) EOS


def test_submit_validation(hyena_model):
    cfg, params = hyena_model
    eng = ContinuousBatchingEngine(params, cfg, n_slots=1, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(np.zeros(12, np.int32), max_new_tokens=8)   # 20 > max_len
    with pytest.raises(ValueError):
        eng.submit(np.zeros(4, np.int32), max_new_tokens=0)


def test_request_stream_driver(hyena_model):
    cfg, params = hyena_model
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN)
    stream = synthesize_request_stream(
        np.random.default_rng(3), 5, rate=200.0, prompt_lens=(4, 8),
        gen_tokens=(2, 5), vocab=cfg.vocab)
    m = run_request_stream(eng, stream)
    assert m["n_requests"] == 5
    assert m["n_tokens"] == sum(len(r.tokens) for r in eng.finished)
    assert m["p99_latency_s"] >= m["p50_latency_s"] >= 0.0
    assert all(r.ttft <= r.latency for r in eng.finished)


# ---------------------------------------------------------------------------
# Per-slot sampling params
# ---------------------------------------------------------------------------
def test_sample_token_slots_per_row_params():
    """Each row honors its own temperature/top-k/top-p."""
    key = jax.random.PRNGKey(0)
    # the top-3 row's three largest (indices 4, 7, 6) lie close, so its 64
    # draws spread over them
    logits = jnp.asarray([
        [0.0, 1.0, 2.0, 3.0, 10.0, 4.0, 5.0, 6.0],
        [0.0, 1.0, 2.0, 3.0, 10.0, 4.0, 5.0, 6.0],
        [0.0, 1.0, 2.0, 3.0, 7.0, 4.0, 6.0, 6.5],
        [0.0, 1.0, 2.0, 3.0, 10.0, 4.0, 5.0, 6.0],
    ], jnp.float32)
    temperature = jnp.asarray([0.0, 1.0, 1.0, 1.0])
    top_k = jnp.asarray([0, 1, 3, 0])
    top_p = jnp.asarray([1.0, 1.0, 1.0, 0.01])
    hits = set()
    for s in range(64):
        toks = np.asarray(sample_token_slots(
            jax.random.fold_in(key, s), logits, temperature=temperature,
            top_k=top_k, top_p=top_p))
        assert toks[0] == 4                    # greedy row
        assert toks[1] == 4                    # top-k = 1 -> argmax
        assert toks[2] in (4, 6, 7)            # top-3 support only
        assert toks[3] == 4                    # tiny nucleus -> argmax
        hits.add(int(toks[2]))
    assert len(hits) > 1                       # actually samples, not greedy


def test_engine_honors_per_slot_sampling(hyena_model):
    """top_k=1 sampling at high temperature equals greedy — co-resident with
    a genuinely stochastic request (different per-slot params in one pool)."""
    cfg, params = hyena_model
    prompts = _prompts(cfg.vocab)
    want = _sequential_greedy(cfg, params, prompts[:1], [8], "distilled")[0]
    eng = ContinuousBatchingEngine(params, cfg, n_slots=2, max_len=MAX_LEN)
    r_det = eng.submit(prompts[0], max_new_tokens=8,
                       sampling=SamplingParams(temperature=2.0, top_k=1))
    eng.submit(prompts[1], max_new_tokens=8,
               sampling=SamplingParams(temperature=1.5, top_p=0.9))
    eng.run()
    np.testing.assert_array_equal(np.asarray(r_det.tokens), want)


# ---------------------------------------------------------------------------
# Slot-indexed cache helpers
# ---------------------------------------------------------------------------
def test_write_and_reset_cache_slot(hyena_model):
    cfg, params = hyena_model
    pool, _ = unzip(init_cache(cfg, 3, MAX_LEN, per_slot=True))
    toks = jnp.asarray(_prompts(cfg.vocab)[0])[None]
    single, _ = prefill(params, toks, cfg, max_len=MAX_LEN)
    pool = write_cache_slot(pool, single, 1)
    assert list(np.asarray(pool["pos"])) == [0, toks.shape[1], 0]
    slot_rows = jax.tree.map(lambda p: p[:, 1], pool["groups"])
    src_rows = jax.tree.map(lambda s: s[:, 0], single["groups"])
    for a, b in zip(jax.tree.leaves(slot_rows), jax.tree.leaves(src_rows)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    # untouched slots stay zero
    for leaf in jax.tree.leaves(jax.tree.map(lambda p: p[:, 0],
                                             pool["groups"])):
        assert float(jnp.max(jnp.abs(leaf))) == 0.0
    pool = reset_cache_slot(pool, 1)
    assert int(pool["pos"][1]) == 0
    for leaf in jax.tree.leaves(jax.tree.map(lambda p: p[:, 1],
                                             pool["groups"])):
        assert float(jnp.max(jnp.abs(leaf))) == 0.0
