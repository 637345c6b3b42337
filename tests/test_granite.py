"""Granite-4.0-H on the serving path, at a tiny size on the CPU.

A tiny configuration keeps every feature of the published model: a period
with Mamba-2 and GQA attention layers, NoPE, the four multipliers, the
Mamba-2 conv bias and the norm eps. The program (the functions the engine
jits: bucketed prefill, the slot pool's write, the pooled decode; and the
chunked prefill) is compared on logits with the benchmark's plain float32
reference (`bench/archs/granite.py` `logits_at`) on seeded random weights;
the engine itself runs through `bench/program.make_engine`
(`test_granite_bench.py` runs the whole benchmark run on tiny cells). The
float8 control fails the tolerances. A MultiHyena
configuration gives the program's logits bit for bit as before the
ModelConfig gained these settings.
"""
import hashlib
import json
import shutil
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

import program  # noqa: E402
import run  # noqa: E402

from repro.distributed.sharding import unzip  # noqa: E402
from repro.models.model import (decode_step, finalize_prefill_cache,  # noqa: E402
                                init_cache, init_prefill_cache, prefill,
                                prefill_from_cache, state_bytes,
                                write_cache_slot, write_cache_slots)

GRANITE = json.loads((BENCH / "configs" / "granite-4.0-h-micro.json")
                     .read_text())
# the published keys, at a tiny width: two periods of (M, A, M), two
# Mamba-2 groups, and the published multipliers, NoPE, conv bias and eps
TINY = dict(GRANITE, name="tiny-granite", hidden_size=64,
            num_attention_heads=4, num_key_value_heads=2,
            intermediate_size=128, shared_intermediate_size=128,
            vocab_size=257, layer_types=["mamba", "attention", "mamba"] * 2,
            num_hidden_layers=6, mamba_n_heads=8, mamba_d_head=16,
            mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=32,
            dtype="float32", max_position_embeddings=512)
MAX_LEN = 128
# float32 program against the float32 reference: they differ by the KV
# cache, which the program keeps in bfloat16, and by the order of sums of
# the chunked SSD against the per-token recurrence (3.7e-5 measured, of
# logits that spread 0.0105); the float8 control misses by 0.0078
LOGIT_TOL = 2e-4

TINY_DECODE = {"why": "test", "loop": "closed", "clients": 4,
               "requests_per_client": 6, "slots": 4, "max_len": MAX_LEN,
               "prefills_per_step": 2,
               "prompt": {"dist": "lognormal", "median": 16, "sigma": 0.6,
                          "min": 4, "max": 64},
               "output": {"dist": "uniform", "min": 16, "max": 48},
               "sampling": {"temperature": 0.0}, "check": {"requests": 4},
               "trace_s": 0.3}
# the tiny model's logits are nearly flat (spread ~0.01), so its sampled
# requests run cold enough for a wrong temperature to show
TINY_CHAT = dict(TINY_DECODE, loop="open", rate_rps=40.0, ramp_s=0.3,
                 sampling={"temperature": 0.01, "top_p": 0.9},
                 greedy_every=2, check={"requests": 3, "sampled": 16})
# a served greedy token lies below the reference's best by at most twice
# the logit error (7.4e-5 measured); nucleus excess and surprise as the
# tiny MultiHyena chat cell's
TINY_LIMITS = {"max_gap": 2.5e-4, "min_checked_tokens": 1,
               "nucleus_excess": 1e-3, "surprise_z": 5.0,
               "min_sampled_tokens": 1}


@pytest.fixture(scope="module")
def arch():
    return run.load_arch(ROOT, "granite")


@pytest.fixture(scope="module")
def model(arch):
    w = arch.make_weights(TINY, 3)
    return w, arch.to_program(w), arch.model_config(TINY)


def _sequence(T: int, n: int, seed: int = 0):
    toks = np.random.default_rng(seed).integers(0, 257, size=T + n)
    seq = np.zeros(MAX_LEN, np.int32)
    seq[:T + n] = toks
    return seq


def _reference(arch, w, seq, T, n, precision="f32"):
    return np.asarray(arch.logits_at(w, jnp.asarray(seq), T, cfg=TINY,
                                     max_len=MAX_LEN, n_out=n,
                                     precision=precision))


def _decode_teacher_forced(params, mcfg, pool, slot, seq, T, n):
    """Logits of the pooled decode at slot `slot`, fed the sequence."""
    step = jax.jit(lambda p, c, t: decode_step(p, c, t, mcfg))
    out = []
    for j in range(n - 1):
        tok = np.zeros((pool["pos"].shape[0], 1), np.int32)
        tok[slot, 0] = seq[T + j]
        pool, lg = step(params, pool, jnp.asarray(tok))
        out.append(np.asarray(lg[slot, 0], np.float32))
    return out


def test_config_carries_every_granite_feature(arch):
    c = arch.model_config(GRANITE)
    assert c.n_layers == 40 and c.pattern.count("attn") == 1
    assert len(c.pattern) == 10 and c.pattern[5] == "attn"
    assert c.nope and not c.learned_pos
    assert (c.embedding_multiplier, c.residual_multiplier,
            c.attention_multiplier, c.logits_scaling) == (12.0, 0.22,
                                                          0.015625, 8.0)
    assert c.norm_eps == 1e-5 and c.ssm.conv_bias and c.hd == 64
    n = sum(int(np.prod(s)) for s in arch.shapes(GRANITE).values())
    assert abs(n - 3.19e9) < 0.01e9


def test_bucketed_prefill_and_pooled_decode_match_reference(arch, model):
    """Two prompts bucketed into one prefill call, written into slots of a
    pool, then the pooled decode, on logits."""
    w, params, mcfg = model
    T, n, bucket = 37, 12, 64
    seq = _sequence(T, n)
    toks = np.zeros((2, bucket), np.int32)
    toks[0, :T] = seq[:T]
    toks[1, :10] = seq[:10]
    pre = jax.jit(lambda p, t, l: prefill(p, t, mcfg, MAX_LEN, lengths=l))
    cache, last = pre(params, jnp.asarray(toks),
                      jnp.asarray([T, 10], jnp.int32))
    pool = unzip(init_cache(mcfg, 4, MAX_LEN, per_slot=True))[0]
    pool = write_cache_slots(pool, cache, jnp.asarray([1, 4], jnp.int32))
    got = np.stack([np.asarray(last[0], np.float32)]
                   + _decode_teacher_forced(params, mcfg, pool, 1, seq, T, n))
    ref = _reference(arch, w, seq, T, n)
    assert np.abs(got - ref).max() < LOGIT_TOL
    assert np.abs(_reference(arch, w, seq, T, n, "fp8") - ref).max() \
        > 10 * LOGIT_TOL


def test_chunked_prefill_and_pooled_decode_match_reference(arch, model):
    """The resumable prefill (chunks of 16, the last one padded), its
    finalised cache in a slot, then the pooled decode, on logits."""
    w, params, mcfg = model
    T, n, C = 45, 8, 16
    seq = _sequence(T, n, seed=1)
    pc = unzip(init_prefill_cache(mcfg, 1, MAX_LEN, chunk=C))[0]
    step = jax.jit(lambda p, c, t, s, k: prefill_from_cache(
        p, c, t, s, mcfg, MAX_LEN, chunk_len=k))
    for start in range(0, T, C):
        k = min(C, T - start)
        chunk = np.zeros((1, C), np.int32)
        chunk[0, :k] = seq[start:start + k]
        pc, last = step(params, pc, jnp.asarray(chunk), start, k)
    single = finalize_prefill_cache(pc, T, mcfg, MAX_LEN)
    pool = unzip(init_cache(mcfg, 2, MAX_LEN, per_slot=True))[0]
    pool = write_cache_slot(pool, single, 1)
    got = np.stack([np.asarray(last[0], np.float32)]
                   + _decode_teacher_forced(params, mcfg, pool, 1, seq, T, n))
    assert np.abs(got - _reference(arch, w, seq, T, n)).max() < LOGIT_TOL


@pytest.mark.parametrize("chunk", [None, 16])
def test_engine_serves_within_tolerance(arch, model, chunk):
    """The engine `bench/program.make_engine` builds (continuous batching,
    `distilled` mode, bucketed prefill; and with `prefill_chunk`) serves
    greedy tokens whose reference logit lies within the tolerance of the
    reference's best."""
    w, params, mcfg = model
    if chunk:
        from repro.serve.scheduler import ContinuousBatchingEngine
        eng = ContinuousBatchingEngine(params, mcfg, n_slots=4,
                                       max_len=MAX_LEN, mode="distilled",
                                       seed=7, max_prefills_per_step=2,
                                       prefill_chunk=chunk)
    else:
        eng = program.make_engine(params, mcfg, TINY_DECODE, 7)
    rng = np.random.default_rng(2)
    reqs = [eng.submit(rng.integers(0, 257, size=L), max_new_tokens=10)
            for L in (5, 23, 40, 61, 12)]
    eng.run()
    for r in reqs:
        assert r.status == "finished" and len(r.tokens) == 10
        T = r.prompt_len
        seq = np.zeros(MAX_LEN, np.int32)
        seq[:T] = r.prompt
        seq[T:T + 9] = r.tokens[:-1]
        ref = _reference(arch, w, seq, T, 10)
        gap = ref.max(-1) - ref[np.arange(10), np.asarray(r.tokens)]
        assert gap.max() < 2 * LOGIT_TOL


def test_engine_counts_state_bytes_by_kind(model):
    _, params, mcfg = model
    eng = program.make_engine(params, mcfg, TINY_DECODE, 7)
    B, L = 4, MAX_LEN
    s = mcfg.ssm
    di, H = s.expand * mcfg.d_model, s.expand * mcfg.d_model // s.head_dim
    conv = (s.d_conv - 1) * (di + 2 * s.n_groups * s.d_state)
    n_mamba, n_attn = mcfg.blocks.count("mamba2"), mcfg.blocks.count("attn")
    recurrent = 4 * B * n_mamba * (conv + H * s.head_dim * s.d_state)
    kv = 2 * 2 * B * n_attn * L * mcfg.n_kv_heads * mcfg.hd     # bf16 k, v
    assert eng.stats["state_bytes_recurrent"] == recurrent
    assert eng.stats["state_bytes_kv"] == kv
    assert state_bytes(eng.cache) == {"recurrent": recurrent, "kv": kv}
    snap = eng.metrics.snapshot()
    assert snap["serve_state_bytes_recurrent"] == recurrent
    assert snap["serve_state_bytes_kv"] == kv


def test_each_setting_reaches_the_program(model):
    """Without any one of the multipliers the logits move far beyond the
    tolerance; so does RoPE in place of NoPE (compared at the 1/sqrt(hd)
    scale: at the published 1/64 the tiny model's attention is nearly
    uniform, whatever the positions)."""
    _, params, mcfg = model
    toks = jnp.asarray(_sequence(20, 0)[None, :20])
    sharp = mcfg.replace(attention_multiplier=None)
    for a, b in ((mcfg, mcfg.replace(residual_multiplier=1.0)),
                 (mcfg, mcfg.replace(embedding_multiplier=1.0)),
                 (mcfg, mcfg.replace(logits_scaling=1.0)),
                 (mcfg, sharp), (sharp, sharp.replace(nope=False))):
        moved = prefill(params, toks, b, MAX_LEN)[1]
        base = prefill(params, toks, a, MAX_LEN)[1]
        assert float(jnp.abs(moved - base).max()) > 10 * LOGIT_TOL


def test_multihyena_program_logits_unchanged():
    """A tiny MultiHyena through the ModelConfig with its new defaults:
    bucketed prefill and pooled decode give the logits the program gave
    before those settings existed (hashes taken then), bit for bit."""
    mh = run.load_arch(ROOT, "multihyena")
    base = {"name": "tiny", "source": "test", "arch": "multihyena",
            "n_layers": 2, "d_model": 64, "n_filter_heads": 4, "d_ff": 128,
            "vocab": 257, "act": "gelu", "norm": "layernorm",
            "tie_embeddings": True, "filter_order": 16, "filter_emb": 9,
            "short_conv": 3, "sine_freq": 4.0, "modulate": True,
            "distill_order": 8, "dtype": "float32", "max_seq": 512}
    want = {"float32": "2af57b27ce56b60a07e744bc5c6574ddf2f1bf9d4ffb5a03"
                       "2e07dd142a1bb81c",
            "bfloat16": "f2c482412ec107e939bb741865e5dc9cf1807f84dfac5c29"
                        "baddb4d18b61a98c"}
    for dtype, digest in want.items():
        cfg = dict(base, dtype=dtype)
        mcfg = mh.model_config(cfg)
        params = mh.to_program(mh.make_weights(cfg, 11))
        rng = np.random.default_rng(5)
        toks = jnp.asarray(rng.integers(0, 257, size=(2, 32)).astype(np.int32))
        cache, last = prefill(params, toks, mcfg, 64,
                              lengths=jnp.asarray([29, 17], jnp.int32))
        pool = unzip(init_cache(mcfg, 3, 64, per_slot=True))[0]
        pool = write_cache_slots(pool, cache, jnp.asarray([0, 2], jnp.int32))
        out = [last]
        for _ in range(4):
            tok = rng.integers(0, 257, size=(3, 1)).astype(np.int32)
            pool, lg = decode_step(params, pool, jnp.asarray(tok), mcfg)
            out.append(lg[:, 0])
        flat = np.concatenate([np.asarray(x, np.float32).ravel()
                               for x in out])
        assert hashlib.sha256(flat.tobytes()).hexdigest() == digest, dtype


# --- the benchmark's cells ---------------------------------------------------

NEW_CELLS = {
    "g4hmicro.decode": ("granite-4.0-h-micro", "rag_decode",
                        ("setup_s", "output_tok_s")),
}
# the files the new cell brought, and the traffic and limits of the
# long-prompt cell, measured but not in the benchmark (PERF.md); every
# other file of the harness was there
NEW_FILES = {"archs/granite.py", "configs/granite-4.0-h-micro.json",
             "traffic/rag_decode.json", "traffic/long_prompt.json",
             "limits/g4hmicro.decode.json", "limits/mh153m.long_prompt.json",
             "metrics/kernel.mamba2_decode_roofline.tput.py"}


def _digest(root: Path) -> dict:
    return {str(p.relative_to(root / "bench")):
            hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "bench").rglob("*") if p.is_file()}


def test_new_cells_join_by_new_files_and_entries(tmp_path):
    """Take the new cell out (its files and its entries), and the
    benchmark is whole without it; put it back by new files and appended
    entries alone, and every other file keeps its digest and the harness
    finds all that the cell names."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    full = json.loads((ROOT / "BENCHMARK.json").read_text())
    names = set(NEW_CELLS)
    bare = json.loads(json.dumps(full))
    bare["configs"] = [c for c in bare["configs"]
                       if c["name"] != "granite-4.0-h-micro"]
    bare["workloads"] = [c for c in bare["workloads"]
                         if c["name"] not in names]
    bare["per_layer"] = [m for m in bare["per_layer"]
                         if m["name"] != "kernel.mamba2_decode_roofline.tput"]
    for m in bare["end_to_end"] + bare["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [w for w in m["workloads"] if w not in names]
    for rel in NEW_FILES:
        (tmp_path / "bench" / rel).unlink()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bare))
    before = _digest(tmp_path)
    for name in names:
        with pytest.raises(KeyError):
            run.Cell(tmp_path, name)
    for rel in NEW_FILES:
        shutil.copy(BENCH / rel, tmp_path / "bench" / rel)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(full))
    after = _digest(tmp_path)
    assert all(after[p] == h for p, h in before.items())
    assert set(after) - set(before) == NEW_FILES
    # every entry that was there stays, in place; lists only grow at the end
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        assert full[key][:len(bare[key])] == [
            dict(m, workloads=m["workloads"] + [w for w in full_m["workloads"]
                                                if w in names])
            if "workloads" in m else m
            for m, full_m in zip(bare[key], full[key])]
    for name, (config, mix, e2e) in NEW_CELLS.items():
        cell = run.Cell(tmp_path, name)
        assert cell.cfg["name"] == config and cell.mix == json.loads(
            (BENCH / "traffic" / f"{mix}.json").read_text())
        assert {m["name"] for m in cell.metrics("end_to_end")} == set(e2e)
        for m in cell.metrics("per_layer"):
            assert cell.reader(m["name"]).UNIT == m["unit"]
    g = run.Cell(tmp_path, "g4hmicro.decode")
    per_layer = {m["name"] for m in g.metrics("per_layer")}
    assert "kernel.mamba2_decode_roofline.tput" in per_layer
    assert "kernel.ssm_decode_roofline.tput" not in per_layer
