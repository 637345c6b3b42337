"""Auto-regressive generation engine (paper Sec. 2.2 / 3.4 / 5.4).

Drives prefill + decode for every architecture in the pool. For LCSMs the
engine exposes the paper's deployment modes:

  * "distilled"   — LaughingHyena recurrent mode: O(d) per token, O(d) state
  * "cached_conv" — Lemma 2.1 baseline: O(t) per token, O(L) kv-product cache
  * "epoch"       — FutureFill epoched convolution: exact output from the
                    TRUE long filter at amortized O(sqrt(L) log L) per token
  * (transformers use their native kv cache; SSM/hybrid their native state)

Both modes run through the same jitted `prefill` / `decode_step` pair — the
mode only selects which cache the Hyena layers carry (`cache_kind`). The
decode loop is a single jitted step re-invoked from Python; `generate_scanned`
provides a fully-jitted lax.scan loop for benchmarks. Multi-request serving
with per-slot state lives in `repro.serve.scheduler`.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import NOCTX, ShardCtx
from repro.models.model import (decode_step, finalize_prefill_cache,
                                materialize_conv_filters, prefill,
                                prefill_from_cache, slot_health)
from repro.serve.sampling import sample_token

# Shared jit memo: engines are cheap throwaway objects (tests/benchmarks
# build many), but functools.partial defeats jax's jit cache — so the jitted
# decode/prefill callables are memoized per (cfg, max_len, cache_kind, ctx)
# and shared across GenerationEngine and ContinuousBatchingEngine instances.
_JIT_CACHE: Dict = {}


def _per_slot_shard(step, out_shardings):
    """Run a pooled step once per slot shard (shard_map over the pool's
    mesh): params and any trailing arguments replicated, cache rows and
    tokens split along the slot axis, so each device advances only its own
    slots with no communication. GSPMD cannot partition a Pallas (Mosaic)
    kernel, so the decode kernel needs this on a sharded pool."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import shard_map_compat
    cache_sh, slot_sh = out_shardings[0], out_shardings[1]
    cache_spec = jax.tree.map(lambda s: s.spec, cache_sh)
    out_specs = jax.tree.map(lambda s: s.spec, out_shardings)

    def run(params, cache, tokens, *rest, conv_filters=None):
        def local(p, c, t, cf, *r):
            return step(p, c, t, *r, conv_filters=cf)
        in_specs = (P(), cache_spec, slot_sh.spec, P()) + (P(),) * len(rest)
        return shard_map_compat(local, slot_sh.mesh, in_specs, out_specs)(
            params, cache, tokens, conv_filters, *rest)
    return run


def _jit_pooled(step, out_shardings):
    if out_shardings is None:
        return jax.jit(step, donate_argnums=(1,))
    return jax.jit(_per_slot_shard(step, out_shardings), donate_argnums=(1,),
                   out_shardings=out_shardings)


def jitted_decode_step(cfg: ModelConfig, ctx: ShardCtx = NOCTX, *,
                       out_shardings=None, shard_key=None):
    """`out_shardings` pins the (cache, logits) output shardings for a
    sharded slot pool — the layout never drifts between ticks, so the
    steady state stays at zero recompiles — and runs the step per slot
    shard (`_per_slot_shard`). `shard_key` distinguishes the sharded
    executable from the single-device one in the shared memo."""
    key = ("decode", cfg, id(ctx), shard_key)
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = _jit_pooled(
            functools.partial(decode_step, cfg=cfg, ctx=ctx), out_shardings)
    return _JIT_CACHE[key]


def _decode_step_guarded(params, cache, tokens, bound, *, cfg, ctx,
                         conv_filters=None):
    cache, logits = decode_step(params, cache, tokens, cfg=cfg, ctx=ctx,
                                conv_filters=conv_filters)
    return cache, logits, slot_health(cache, logits[:, 0, :], bound)


def jitted_decode_step_guarded(cfg: ModelConfig, ctx: ShardCtx = NOCTX, *,
                               out_shardings=None, shard_key=None):
    """Pooled decode step with the per-slot state-integrity reduction fused
    into the same executable (`bound` is data — one compile covers every
    margin). A separate jitted health call costs a whole extra host dispatch
    per tick, which on CPU is ~25% of saturated decode throughput; fused,
    the guard rides the decode dispatch for (nearly) free.
    `out_shardings`/`shard_key`: see `jitted_decode_step`."""
    key = ("decode_guarded", cfg, id(ctx), shard_key)
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = _jit_pooled(
            functools.partial(_decode_step_guarded, cfg=cfg, ctx=ctx),
            out_shardings)
    return _JIT_CACHE[key]


def jitted_prefill(cfg: ModelConfig, max_len: int, cache_kind: str = "native",
                   ctx: ShardCtx = NOCTX):
    key = ("prefill", cfg, max_len, cache_kind, id(ctx))
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = jax.jit(
            functools.partial(prefill, cfg=cfg, max_len=max_len, ctx=ctx,
                              cache_kind=cache_kind))
    return _JIT_CACHE[key]


def jitted_prefill_chunk(cfg: ModelConfig, max_len: int,
                         cache_kind: str = "native", ctx: ShardCtx = NOCTX):
    """Resumable chunk step (prefill_from_cache): one executable per chunk
    shape, shared across engines. Call (params, pcache, tokens, start_pos,
    chunk_len=..., conv_filters=...); the scratch cache is donated."""
    key = ("prefill_chunk", cfg, max_len, cache_kind, id(ctx))
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = jax.jit(
            functools.partial(prefill_from_cache, cfg=cfg, max_len=max_len,
                              ctx=ctx, cache_kind=cache_kind),
            donate_argnums=(1,))
    return _JIT_CACHE[key]


def jitted_finalize_prefill(cfg: ModelConfig, max_len: int,
                            cache_kind: str = "native"):
    # no donation: the f32 scratch buffers cannot back the trimmed/bf16
    # decode-cache outputs, so donating them only produces warnings
    key = ("finalize_prefill", cfg, max_len, cache_kind)
    if key not in _JIT_CACHE:
        _JIT_CACHE[key] = jax.jit(
            functools.partial(finalize_prefill_cache, cfg=cfg,
                              max_len=max_len, cache_kind=cache_kind))
    return _JIT_CACHE[key]


class GenerationEngine:
    def __init__(self, params, cfg: ModelConfig, *, max_len: int = 4096,
                 ctx: ShardCtx = NOCTX, mode: str = "distilled",
                 tracer=None):
        if mode not in ("distilled", "cached_conv", "epoch"):
            raise ValueError(f"unknown mode {mode!r}")
        if mode in ("cached_conv", "epoch") and cfg.hyena is None:
            raise ValueError(f"{mode} mode requires a Hyena (LCSM) arch")
        from repro.serve.trace import NULL_TRACER
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.params = params
        self.cfg = cfg
        self.max_len = max_len
        self.ctx = ctx
        self.mode = mode
        self.cache_kind = {"distilled": "native", "cached_conv": "conv",
                           "epoch": "epoch"}[mode]
        self._decode = jitted_decode_step(cfg, ctx)
        self._prefill = jitted_prefill(cfg, max_len, self.cache_kind, ctx)
        # conv/epoch modes: materialize the long filters once, not per token
        self._conv_filters = (materialize_conv_filters(params, cfg, max_len)
                              if self.cache_kind in ("conv", "epoch")
                              else None)

    def generate(self, key, prompt: jnp.ndarray, n_tokens: int, *,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 frontend: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, Dict]:
        """prompt: (B, T) int32 -> (B, n_tokens) generated ids."""
        tr = self.tracer
        with tr.span("prefill", tokens=int(prompt.shape[-1])):
            cache, last_logits = self._prefill(self.params, prompt,
                                               frontend=frontend)
        toks = []
        logits = last_logits
        for i in range(n_tokens):
            key, sub = jax.random.split(key)
            nxt = sample_token(sub, logits, temperature=temperature,
                               top_k=top_k, top_p=top_p)
            toks.append(nxt)
            with tr.span("decode_step"):
                cache, logits = self._decode(self.params, cache, nxt[:, None],
                                             conv_filters=self._conv_filters)
            logits = logits[:, 0, :]
        return jnp.stack(toks, axis=1), {"cache_bytes": _tree_bytes(cache)}

    # ------------------------------------------------------------------
    def generate_scanned(self, key, prompt: jnp.ndarray, n_tokens: int,
                         frontend: Optional[jnp.ndarray] = None):
        """Fully-jitted greedy generation (used by benchmarks)."""
        cfg, ctx, cache_kind = self.cfg, self.ctx, self.cache_kind
        conv_filters = self._conv_filters

        @jax.jit
        def run(params, prompt):
            cache, last_logits = prefill(params, prompt, cfg,
                                         max_len=self.max_len, ctx=ctx,
                                         frontend=frontend,
                                         cache_kind=cache_kind)
            def body(carry, _):
                cache, logits = carry
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                cache, lg = decode_step(params, cache, nxt[:, None], cfg,
                                        ctx=ctx, conv_filters=conv_filters)
                return (cache, lg[:, 0, :]), nxt

            (_, _), toks = jax.lax.scan(body, (cache, last_logits), None,
                                        length=n_tokens)
            return jnp.moveaxis(toks, 0, 1)

        return run(self.params, prompt)


def _tree_bytes(tree) -> int:
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))
