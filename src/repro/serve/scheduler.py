"""Continuous-batching scheduler: a fixed pool of B state slots.

The paper's point of distilling Hyena filters into modal SSMs is O(1)
compute/memory per token at decode — which makes multi-request serving a
*slot* problem rather than a paged-KV problem: every request's entire decode
state is a fixed-size row of a pooled cache (modal SSM state, conv tail, or
kv/conv buffers for the baseline modes). This module schedules requests onto
those rows:

  * admission   — queued requests are prefilled and their caches scattered
                  into free slots. Prompts are right-padded to power-of-two
                  length BUCKETS and prefilled together as ONE fixed-batch
                  call (per-row `lengths` masking keeps padded positions out
                  of every cache), so the engine compiles O(#buckets) prefill
                  executables instead of O(#distinct lengths) and admission
                  cost amortizes across a burst of arrivals;
  * chunking    — prompts longer than `prefill_chunk` run through the
                  resumable `prefill_from_cache` path: one chunk-sized
                  executable covers any prompt length, and only one chunk is
                  consumed per tick, so a long prompt never stalls resident
                  decodes for more than one chunk;
  * decode      — ONE jitted `decode_step` over the full slot pool per tick,
                  each slot at its own position (per-slot `pos` vector);
                  inactive slots decode garbage that is ignored and fully
                  overwritten on readmission;
  * overlap     — the host loop exploits JAX async dispatch: tick N is
                  enqueued from device-resident last-token state BEFORE tick
                  N-1's sampled tokens are fetched to host, so EOS/eviction
                  bookkeeping and admissions run while the device crunches
                  the next step (`overlap=False` restores the fully
                  synchronous admit-then-decode tick);
  * sampling    — per-slot temperature/top-k/top-p in one batched jitted
                  `sample_token_slots` call, parameter vectors resident on
                  device and updated by a scatter at admission;
  * eviction    — on EOS or max-new-tokens the slot is freed (and optionally
                  zeroed) and the next queued request admitted.

Deployment modes (paper Sec. 2.2 / 5.4): "distilled" (LaughingHyena modal
recurrence), "cached_conv" (Lemma 2.1 O(t) baseline), "epoch" (FutureFill
epoched convolution — exact at amortized O(sqrt(L) log L) per token), and
the native mode of non-LCSM archs (attention KV cache, Mamba2/RG-LRU state).

Guarantee (tested): greedy outputs are token-for-token identical to
sequential single-request generation with bucketing, chunking, and the
overlapped loop all enabled. With temperature > 0 the per-request token
*distributions* are unchanged but the PRNG consumption order differs between
overlapped and synchronous runs.
"""
from __future__ import annotations

import dataclasses
import math
import os
import time
from collections import deque
from typing import (Any, Callable, Deque, Dict, List, Optional, Sequence,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.sharding import (SLOT_RULES, slot_axes, tree_shardings,
                                        unzip)
from repro.models.layers import NOCTX, ShardCtx
from repro.models.model import (gather_cache_rows, init_cache,
                                init_prefill_cache,
                                materialize_conv_filters, modal_state_bound,
                                reset_cache_slot, slot_health,
                                write_cache_slot, write_cache_slots)
from repro.serve.faults import FaultError, corrupt_cache_slot, drift_cache_slot
from repro.serve.metrics import (DRIFT_BUCKETS, MetricsRegistry,
                                 RATIO_BUCKETS, ResilienceCounters,
                                 WINDOW_BUCKETS)
from repro.serve.sampling import sample_token_slots
from repro.serve.trace import NULL_TRACER, SPANS, stat_key
from repro.serve.speculative import DRAW_TAG, token_keys

QUEUED, PREFILLING, RUNNING, FINISHED, ERROR = (
    "queued", "prefilling", "running", "finished", "error")

# Engine recovery ladder (serve/README.md "Exact fallback & drift sentinel"):
# distilled (O(d)/token, distillation error) -> cached_conv (exact, O(t)) ->
# epoch (exact, amortized O(sqrt(L) log L) — FutureFill). Demotions only walk
# right.
MODE_LADDER = ("distilled", "cached_conv", "epoch")
_MODE_KINDS = {"distilled": "native", "cached_conv": "conv", "epoch": "epoch"}

_SLOT_JITS: Dict[Any, Callable] = {}


def _log_softmax_np(x: np.ndarray) -> np.ndarray:
    """Host-side log-softmax over the last axis (drift-sentinel compare)."""
    x = x - x.max(axis=-1, keepdims=True)
    return x - np.log(np.exp(x).sum(axis=-1, keepdims=True))


def _jitted(name: str, fn, *, key=None, **jit_kw):
    """Shared jit memo for the slot-vector ops. `key` extends the memo key
    for variants whose jit options differ (a sharded engine pins
    out_shardings, so it cannot share the single-device executable)."""
    k = (name, key)
    if k not in _SLOT_JITS:
        _SLOT_JITS[k] = jax.jit(fn, **jit_kw)
    return _SLOT_JITS[k]


def _update_slot_meta(temps, top_ks, top_ps, last, keys, tok_idx, spec_len,
                      slots, t, k, p, tok, kv, ti, sl):
    """Scatter per-slot sampling params, request PRNG keys, stream counters
    and speculation windows + last token for newly admitted requests.
    Out-of-range slot indices (dummy admission rows) are dropped by an
    explicit mask — the same scatter-max marker as
    `model.write_cache_slots`, because OOB-index scatter semantics are not
    partition-stable on a sharded slot vector."""
    B = temps.shape[0]
    K = slots.shape[0]
    valid = (slots >= 0) & (slots < B)
    src = jnp.where(valid, jnp.arange(K, dtype=jnp.int32), -1)
    marker = jnp.full((B,), -1, jnp.int32).at[
        jnp.where(valid, slots, 0)].max(src)
    take_idx = jnp.maximum(marker, 0)
    keep = marker >= 0

    def put(vec, vals):
        g = jnp.take(vals.astype(vec.dtype), take_idx, axis=0)
        return jnp.where(keep.reshape((B,) + (1,) * (vec.ndim - 1)), g, vec)

    return (put(temps, t), put(top_ks, k), put(top_ps, p), put(last, tok),
            put(keys, kv), put(tok_idx, ti), put(spec_len, sl))


def _admit_sample(keyvec, logits, t, k, p):
    """First-token draw at admission: stream index 0 of each request's key
    tree (identical to what the decode loop would have drawn)."""
    keys = token_keys(keyvec, jnp.zeros((keyvec.shape[0],), jnp.int32),
                      DRAW_TAG)
    return sample_token_slots(keys, logits, temperature=t, top_k=k, top_p=p)


def _stream_sample(slot_keys, tok_idx, logits, temps, top_ks, top_ps):
    """Non-speculative decode draw: per-slot DRAW_TAG key at each slot's own
    stream index — the same key tree the speculative path consumes."""
    keys = token_keys(slot_keys, tok_idx, DRAW_TAG)
    toks = sample_token_slots(keys, logits, temperature=temps, top_k=top_ks,
                              top_p=top_ps)
    return toks, tok_idx + 1


def _slot_health_state(cache, bound):
    """Spec-path guard: cache-state-only (the fused spec round does not
    expose its verify logits). Covers the modal state and conv tails — the
    distilled serving path — while sequence-buffer corruption in a
    cached-conv spec engine surfaces as degenerate (argmax-fallback) tokens
    rather than a tripped guard."""
    B = jnp.asarray(cache["pos"]).shape[0]
    return slot_health(cache, jnp.zeros((B, 1), jnp.float32), bound)


def _clear_slot_meta(temps, top_ks, top_ps, spec_len, slot):
    """Reset a freed slot's sampling params and speculation window to the
    neutral values (greedy, window 1). Stale values on dead slots would
    otherwise defeat the all-greedy and all-fully-accepted fast paths (the
    fused executables branch on jnp.all over EVERY row, dead or alive).
    One-hot select rather than a scatter: slot == n_slots (the warmup dummy)
    matches no row, and the select is partition-stable on a sharded
    vector."""
    hit = jnp.arange(temps.shape[0], dtype=jnp.int32) == slot
    return (jnp.where(hit, jnp.float32(0.0), temps),
            jnp.where(hit, jnp.int32(0), top_ks),
            jnp.where(hit, jnp.float32(1.0), top_ps),
            jnp.where(hit, jnp.int32(1), spec_len))


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    temperature: float = 0.0       # <= 0 -> greedy
    top_k: int = 0                 # <= 0 -> disabled
    top_p: float = 1.0             # >= 1 -> disabled

GREEDY = SamplingParams()


@dataclasses.dataclass
class Request:
    """One generation request plus its lifecycle/latency bookkeeping."""
    rid: int
    prompt: np.ndarray                       # (T,) int32
    max_new_tokens: int
    sampling: SamplingParams = GREEDY
    eos_id: Optional[int] = None
    spec: bool = True                        # opt out of speculative decode
    deadline_s: Optional[float] = None       # end-to-end budget from submit
    # --- filled by the engine ---
    tokens: List[int] = dataclasses.field(default_factory=list)
    status: str = QUEUED
    slot: int = -1
    finish_reason: str = ""
    retries: int = 0                         # quarantine re-prefill attempts
    retry_at: int = 0                        # earliest tick for re-admission
    admit_seq: int = -1                      # dispatch seq at latest admission
    # lifecycle stamps on the engine's clock; the admission ones are set at
    # the first admission only (a recovery re-prefill keeps them):
    #   t_dequeued         left the queue for its prefill
    #   t_prefill_enqueued every device op of its admission enqueued, just
    #                      before the host waits for its first token
    #   t_admitted         that wait returned (the first token is on host)
    t_submit: float = math.nan
    t_dequeued: float = math.nan
    t_prefill_enqueued: float = math.nan
    t_admitted: float = math.nan
    t_first_token: float = math.nan
    t_finished: float = math.nan
    prefill_bucket: int = 0                  # padded length it was prefilled at
    prefill_rows: int = 0                    # real rows in its prefill call

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[0])

    @property
    def latency(self) -> float:
        return self.t_finished - self.t_submit

    @property
    def ttft(self) -> float:
        return self.t_first_token - self.t_submit

    @property
    def ok(self) -> bool:
        """Completed normally (ERROR-status requests carry the failure in
        finish_reason: "poisoned" / "deadline" / "rejected")."""
        return self.status == FINISHED


class ContinuousBatchingEngine:
    """Slot-pool serving engine. See module docstring.

    `mode`: "distilled" | "cached_conv" (LCSM archs) — non-LCSM archs serve
    their native cache in either setting. `reset_on_evict` zeroes a slot on
    eviction (hygiene / debugging; admission overwrites the slot anyway).

    Fast-path knobs:
      * bucket_prompts — pad prompts to power-of-two buckets (>= min_bucket)
        and prefill up to `max_prefills_per_step` same-bucket requests as one
        fixed-batch call: O(#buckets) prefill executables.
      * prefill_chunk  — prompts longer than this go through resumable
        chunked prefill, one chunk per tick (None disables).
      * overlap        — async host loop: enqueue the next pooled decode
        before fetching the previous tick's tokens.
      * spec_k         — self-speculative decoding: each tick drafts up to
        spec_k tokens per slot with a low-order modal truncation of the
        serving SSM (one fused K-step executable) and verifies them all in
        ONE multi-token step of the full-fidelity model, committing the
        longest accepted prefix + a correction token (serve/speculative.py).
        spec_k="auto" runs a construction-time autotune sweep
        (`speculative.autotune_spec`) that measures candidate
        (spec_k, draft_order, branch) configs against plain decode under a
        saturated workload and adopts the winner — or disables speculation
        when nothing beats plain by `spec_margin`; the report lands in
        `self.spec_report`. `draft_order` sets the draft's real state dim
        (default: half the serving order); `spec_branch >= 2` drafts a
        top-k token tree instead of a chain; `spec_adapt` (default on)
        drives per-slot windows from each request's running acceptance
        (`speculative.SlotSpecController`) — shrinking K, disabling
        speculation per slot, and probing it back on — with per-depth
        compiled executables so a narrow round costs a narrow round.
        `draft_model=(params, cfg)` overrides the draft entirely (testing).
        Requests can opt out per-request (Request.spec).

    Resilience knobs (serve/README.md "Failure handling"): `health_every`
    runs the per-slot state-integrity guard every N ticks (0 disables; the
    default of 2 amortizes the guard's reduction to a few percent of decode
    — corruption is persistent state, so detection slips by at most one
    tick, never escapes);
    `state_margin` scales the pole-derived modal-norm bound; `max_retries` /
    `retry_backoff_ticks` bound quarantine re-prefills before a request
    completes with ERROR status; `demote_spec_after` turns a repeatedly
    quarantined request's speculation off; `demote_engine_after` (opt-in)
    falls the whole distilled engine back to the exact cached-conv path;
    `deadline_s` / `max_queue` give per-request deadlines and bounded-queue
    backpressure; `watchdog_s` flags slow host ticks; `fault_injector`
    (serve/faults.FaultInjector) drives scripted chaos schedules.

    Observability knobs (serve/README.md "Observability"): `metrics` binds
    a serve.metrics.MetricsRegistry (one is created, enabled, when omitted
    — pass MetricsRegistry(enabled=False) to opt out); `tracer` binds a
    serve.trace.Tracer recording host-phase and request-lifecycle spans
    (default: the no-op NULL_TRACER); `events_limit` bounds the recovery-
    event log `self.events` as a ring buffer (None = unbounded,
    `self._events_total` counts everything ever recorded).
    """

    def __init__(self, params, cfg: ModelConfig, *, n_slots: int = 8,
                 max_len: int = 4096, mode: str = "distilled",
                 ctx: ShardCtx = NOCTX, seed: int = 0, mesh=None,
                 max_prefills_per_step: int = 1, reset_on_evict: bool = False,
                 bucket_prompts: bool = True, min_bucket: int = 8,
                 prefill_chunk: Optional[int] = None, overlap: bool = True,
                 spec_k=0, draft_order: Optional[int] = None,
                 spec_branch: int = 1, spec_adapt=True,
                 spec_candidates: Optional[Sequence[Any]] = None,
                 spec_margin: float = 0.05,
                 draft_model: Optional[Tuple[Any, ModelConfig]] = None,
                 clock: Callable[[], float] = time.monotonic,
                 health_every: int = 2, state_margin: float = 1e3,
                 max_retries: int = 2, retry_backoff_ticks: int = 0,
                 demote_spec_after: int = 2,
                 demote_engine_after: Optional[int] = None,
                 drift_check_every: int = 0,
                 drift_tol: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 max_queue: Optional[int] = None,
                 watchdog_s: Optional[float] = None,
                 fault_injector=None, tracer=None,
                 metrics: Optional[MetricsRegistry] = None,
                 events_limit: Optional[int] = 256):
        if mode not in MODE_LADDER:
            raise ValueError(f"unknown mode {mode!r}")
        if mode in ("cached_conv", "epoch") and cfg.hyena is None:
            raise ValueError(f"{mode} mode requires a Hyena (LCSM) arch")
        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}"
                             " (None disables chunked prefill)")
        if (prefill_chunk is not None and cfg.ssm is not None
                and prefill_chunk > cfg.ssm.chunk
                and prefill_chunk % cfg.ssm.chunk != 0):
            raise ValueError(
                f"prefill_chunk={prefill_chunk} must divide into the SSD "
                f"chunk length (cfg.ssm.chunk={cfg.ssm.chunk}): use a "
                f"multiple of it, or a value <= it")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.mode = mode
        self.ctx = ctx
        self.max_prefills_per_step = max_prefills_per_step
        self.reset_on_evict = reset_on_evict
        self._bucketed = bucket_prompts
        self._min_bucket = min_bucket
        self._chunk = prefill_chunk
        self._overlap = overlap
        self._prefill_batch = max(1, max_prefills_per_step)
        self._clock = clock
        cache_kind = _MODE_KINDS[mode]
        self._cache_kind = cache_kind
        # --- slot-pool sharding (serve/README.md "Sharded slot pool") ---
        # every per-slot buffer (the pooled cache + the metadata vectors)
        # shards its row axis over the mesh's data axis; each shard decodes
        # its own rows with no communication — the admission scatter and the
        # sampled-token fetch are the only cross-shard hops.
        mesh = self._resolve_mesh(mesh, n_slots)
        self.mesh = mesh
        if mesh is None:
            self._n_shards = 1
            self._slot_sh = None
        else:
            mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
            n_sh = mesh_shape.get("pod", 1) * mesh_shape.get("data", 1)
            if n_sh <= 1:
                raise ValueError("slot-pool mesh has no 'data' axis to "
                                 "shard over (or it has size 1)")
            if n_slots % n_sh != 0:
                raise ValueError(
                    f"n_slots={n_slots} does not divide across {n_sh} slot "
                    f"shards — pick n_slots as a multiple of the data-axis "
                    f"size")
            self._n_shards = n_sh
            self._slot_sh = NamedSharding(mesh, P("data"))
            # params (and later the draft params / long filters) are
            # replicated across the mesh: a committed single-device param
            # tree mixed with a sharded pool in one jit is a placement error
            params = jax.device_put(params, NamedSharding(mesh, P()))
        self.params = params
        # --- observability (serve/README.md "Observability") ---
        # the registry is always present and enabled by default: instrument
        # bumps are plain host-side python mirroring the stats-dict
        # increments; the tracer defaults to the shared no-op. Both are held
        # to <= 2% saturated-decode overhead by the `observability` row in
        # BENCH_serve.json (benchmarks/check_regression.py gate).
        self.tracer = NULL_TRACER if tracer is None else tracer
        self.metrics = MetricsRegistry() if metrics is None else metrics
        _m = self.metrics
        self._mc: Dict[str, Any] = {}    # stats-dict key -> mirror counter
        self._h_tick = _m.histogram("serve_tick_latency_s",
                                    help="host-loop tick latency")
        self._h_ttft = _m.histogram("serve_ttft_s",
                                    help="submit -> first token")
        self._h_latency = _m.histogram(
            "serve_request_latency_s",
            help="submit -> finished, ok requests only")
        self._h_fill = _m.histogram("serve_batch_fill_ratio", RATIO_BUCKETS,
                                    help="active slots / n_slots per tick")
        self._h_spec_win = _m.histogram(
            "serve_spec_window", WINDOW_BUCKETS,
            help="per-slot speculation window at dispatch")
        self._g_queue = _m.gauge("serve_queue_depth")
        self._g_active = _m.gauge("serve_active_slots")
        self._g_shard_occ = [
            _m.gauge(f"serve_shard_occupancy_{s}",
                     help="live slots resident on this mesh shard")
            for s in range(self._n_shards)]
        self._c_finished = _m.counter("serve_requests_finished")
        self._c_errors = _m.counter(
            "serve_requests_error", help="rejected / deadline / poisoned")
        self._c_events = _m.counter(
            "serve_events_total",
            help="recovery-log events (the `events` ring drops the oldest)")
        self.cache, self._cache_sh = self._make_pool(cfg, cache_kind)
        self._draft_sh = None
        self._meta = _jitted("slot_meta", _update_slot_meta,
                             key=self._shard_tag("meta"),
                             **self._vec_out(7))
        # long filters: cached-conv / epoch decode always needs them; chunked
        # prefill needs them for any Hyena layer in every mode
        need_filters = cfg.hyena is not None and (cache_kind in
                                                  ("conv", "epoch")
                                                  or prefill_chunk)
        self._conv_filters = (self._replicate(
            materialize_conv_filters(params, cfg, max_len))
            if cache_kind in ("conv", "epoch") else None)
        self._chunk_filters = (self._conv_filters
                               if cache_kind in ("conv", "epoch")
                               else (self._replicate(
                                   materialize_conv_filters(params, cfg,
                                                            max_len))
                                     if need_filters else None))
        self._build_pool_ops()
        # --- self-speculative decoding (serve/speculative.py) ---
        self.spec_report = None
        if isinstance(spec_k, str):
            if spec_k != "auto":
                raise ValueError(f"spec_k must be an int or 'auto', got "
                                 f"{spec_k!r}")
            from repro.serve import speculative as spec_mod
            self.spec_report = spec_mod.autotune_spec(
                params, cfg, mode=mode, n_slots=n_slots, max_len=max_len,
                ctx=ctx, seed=seed, candidates=spec_candidates,
                margin=spec_margin, draft_model=draft_model)
            ch = self.spec_report.chosen
            spec_k = ch.spec_k if ch is not None else 0
            if ch is not None:
                draft_order = ch.draft_order
                spec_branch = ch.branch
        self._spec_k = int(spec_k)
        self._spec = self._spec_k > 0
        self._spec_branch = int(spec_branch)
        self.draft_cache = None
        # native (distilled) serving: the draft's truncated modes are a
        # subset of the serving state, so the draft reads the serving cache
        # directly (embedded residues) — no second pool, no draft prefill.
        # cached-conv / epoch serving keeps a separate native draft pool:
        # that is the paper's classic pair (exact target, O(d) draft).
        self._draft_shared = cache_kind == "native"
        self._spec_ctl = None
        if self._spec:
            from repro.serve import speculative as spec_mod
            spec_mod.validate_spec_config(cfg, self._spec_k,
                                          branch=self._spec_branch)
            d_ord = (draft_order if draft_order is not None else
                     (cfg.hyena.distill_order // 2 if cfg.hyena else 0))
            self.draft_order = d_ord
            if draft_model is not None:
                self._draft_params, self._draft_cfg = draft_model
                if self._draft_shared and self._draft_cfg is not cfg \
                        and self._draft_cfg != cfg:
                    raise ValueError("shared-state draft requires the draft "
                                     "cfg to match the serving cfg")
            else:
                self._draft_params, self._draft_cfg = \
                    spec_mod.make_draft_params(params, cfg, d_ord,
                                               fit_len=min(max_len, 2048),
                                               embed=self._draft_shared)
            self._draft_params = self._replicate(self._draft_params)
            if not self._draft_shared:
                from repro.serve.engine import (jitted_finalize_prefill,
                                                jitted_prefill,
                                                jitted_prefill_chunk)
                self.draft_cache, self._draft_sh = self._make_pool(
                    self._draft_cfg, "native")
                (self._write_slot_d, self._write_slots_d,
                 self._reset_slot_d) = self._pool_write_ops(
                    self._draft_cfg, "native", self._draft_sh, "draft")
                self._draft_prefill = jitted_prefill(self._draft_cfg,
                                                     max_len, "native", ctx)
                if prefill_chunk:
                    self._draft_prefill_chunk = jitted_prefill_chunk(
                        self._draft_cfg, max_len, "native", ctx)
                    self._draft_finalize = jitted_finalize_prefill(
                        self._draft_cfg, max_len, "native")
            # per-depth executables: a controller-shrunk window dispatches
            # the smallest covering depth instead of masking inside the
            # full-K one, so a narrow round costs a narrow round. On a
            # sharded pool each round's outputs are pinned to the pool /
            # slot-vector shardings (same discipline as _build_pool_ops).
            spec_osh = spec_key = None
            if self.mesh is not None:
                s = self._slot_sh
                spec_osh = (self._cache_sh,
                            None if self._draft_shared else self._draft_sh,
                            s, s, s, s)
                spec_key = (self.mesh, cache_kind)
            self._spec_levels = spec_mod.spec_round_levels(self._spec_k)
            self._spec_rounds = {
                L: spec_mod.jitted_spec_round(cfg, self._draft_cfg, L,
                                              self._draft_shared, ctx,
                                              branch=self._spec_branch,
                                              out_shardings=spec_osh,
                                              shard_key=spec_key)
                for L in self._spec_levels}
            self._spec_round = self._spec_rounds[self._spec_k]
            if spec_adapt:
                # spec_adapt may be a SpecControllerConfig to override the
                # control-law knobs (tests shrink probe_every/min_rounds)
                ctl_cfg = (spec_adapt if isinstance(
                    spec_adapt, spec_mod.SpecControllerConfig) else None)
                self._spec_ctl = spec_mod.SlotSpecController(
                    n_slots, self._spec_k, ctl_cfg, metrics=self.metrics)
        # per-slot host-side bookkeeping; sampling params, last token, PRNG
        # keys, stream counters and speculation windows live on device so the
        # overlapped loop never waits on a host upload
        self.slots: List[Optional[Request]] = [None] * n_slots
        self.active = np.zeros(n_slots, bool)
        self._base_key = jax.random.PRNGKey(seed)
        # sharded pool: every per-slot vector lives row-sharded next to its
        # cache rows (_put_slot_vec is the identity without a mesh)
        self._temps = self._put_slot_vec(jnp.zeros((n_slots,), jnp.float32))
        self._top_ks = self._put_slot_vec(jnp.zeros((n_slots,), jnp.int32))
        self._top_ps = self._put_slot_vec(jnp.ones((n_slots,), jnp.float32))
        self._last = self._put_slot_vec(jnp.zeros((n_slots,), jnp.int32))
        self._slot_keys = self._put_slot_vec(
            jnp.zeros((n_slots,) + self._base_key.shape,
                      self._base_key.dtype))
        self._tok_idx = self._put_slot_vec(jnp.zeros((n_slots,), jnp.int32))
        self._spec_len = self._put_slot_vec(jnp.ones((n_slots,), jnp.int32))
        # host mirror of _spec_len plus a shadow of what the device holds:
        # admission/eviction scatters keep both in sync; controller window
        # changes mark the mirror dirty and _sync_spec_len uploads the whole
        # vector once per change (no per-slot device scatters on the hot
        # path, no recompiles — the executables take spec_len as data)
        self._spec_win = np.ones(n_slots, np.int32)
        self._spec_win_dev = self._spec_win.copy()
        self._admit_sample = _jitted("admit_sample", _admit_sample)
        self._stream_sample = _jitted("stream_sample", _stream_sample,
                                      key=self._shard_tag("stream"),
                                      **self._vec_out(2))
        self._clear_meta = _jitted("clear_slot_meta", _clear_slot_meta,
                                   key=self._shard_tag("clear"),
                                   **self._vec_out(4))
        self.queue: Deque[Request] = deque()
        self.finished: List[Request] = []
        self._pending: Optional[Tuple[list, Any, Any]] = None
        self._chunk_state: Optional[Dict[str, Any]] = None
        self._buckets_used: set = set()
        self._next_rid = 0
        # event counts, then the host seconds spent in each phase span
        # (`phase_s_<span>`, serve/trace.py SPANS)
        self.stats: Dict[str, float] = {"admitted": 0, "evicted": 0,
                                        "decode_steps": 0, "prefills": 0,
                                        "prefill_calls": 0, "chunk_steps": 0,
                                        "spec_rounds": 0, "spec_drafted": 0,
                                        "spec_accepted": 0,
                                        "spec_slot_rounds": 0,
                                        "spec_window_syncs": 0,
                                        "sampled_decode_steps": 0}
        self.stats.update((stat_key(n), 0.0) for n in SPANS)
        # resident requests with temperature > 0: a decode dispatch runs the
        # sampler's top-k/top-p filter iff this is nonzero
        self._n_sampled = 0
        # --- resilience layer (see serve/README.md "Failure handling") ---
        self._tick = 0
        self._dispatch_seq = 0     # monotonic dispatch counter (see _retire)
        self._health_every = max(0, int(health_every))
        self._guard = self._health_every > 0
        # pole-derived bound on the modal-state norm: |x| stays under
        # margin/(1-max|λ|) for stable poles; inf disables the norm check
        # (non-hyena archs, cached-conv kind — finiteness-only there)
        self._state_bound = (modal_state_bound(params, cfg,
                                               margin=state_margin)
                             if cache_kind == "native" else float("inf"))
        # decode-path guard is fused into the decode executable (_decode_g);
        # the spec path keeps a separate state-only health dispatch, built
        # alongside the other pool executables in _build_pool_ops (the
        # spec-round executables don't expose their verify logits, and one
        # extra dispatch amortizes over the round's multi-token yield)
        self.max_retries = int(max_retries)
        self._retry_backoff = max(0, int(retry_backoff_ticks))
        self._demote_spec_after = int(demote_spec_after)
        self._demote_engine_after = demote_engine_after
        self._distilled_faults = 0
        # --- drift sentinel (serve/README.md "Exact fallback & drift
        # sentinel") --- every `drift_check_every` ticks one resident slot
        # (rotating cursor) is shadow-decoded a single step through the
        # exact epoch path off the critical path; |log-softmax| divergence
        # beyond `drift_tol` demotes the engine straight to mode="epoch".
        # Only the distilled mode carries distillation error, so the
        # sentinel arms there and disarms after any demotion.
        self._drift_every = max(0, int(drift_check_every))
        self._drift_tol = drift_tol
        self._drift_cursor = 0
        self._drift_last: Optional[float] = None
        self._drift_certificate = None
        self._sentinel = (self._drift_every > 0 and mode == "distilled"
                          and cfg.hyena is not None)
        self._h_drift = _m.histogram(
            "serve_drift_logit_div", DRIFT_BUCKETS,
            help="sentinel max |log-softmax| gap, distilled vs exact path")
        if self._sentinel:
            from repro.serve.engine import (jitted_decode_step,
                                            jitted_prefill)
            self._drift_prefill = jitted_prefill(cfg, max_len, "epoch", ctx)
            # the shadow decode replays ONE gathered row; without pinned
            # out_shardings it takes the plain memo entry, so it never
            # aliases (or recompiles) the pool-pinned decode executable
            self._drift_decode = jitted_decode_step(cfg, ctx)
            self._drift_filters = (
                self._chunk_filters if self._chunk_filters is not None
                else self._replicate(
                    materialize_conv_filters(params, cfg, max_len)))
            self._gather_rows = _jitted("gather_rows", gather_cache_rows,
                                        key=self._shard_tag("drift"))
        self._deadline_s = deadline_s
        self._any_deadline = deadline_s is not None
        self._max_queue = max_queue
        self._watchdog_s = watchdog_s
        self._injector = fault_injector
        self.resilience = ResilienceCounters(registry=self.metrics)
        # recovery-event log: bounded ring (oldest dropped past
        # events_limit; None = unbounded). serve_events_total /
        # _events_total count every event ever recorded, and with a live
        # tracer each event also lands as an instant on the owning
        # request's trace track
        self.events: Deque[Dict[str, Any]] = deque(maxlen=events_limit)
        self._events_total = 0

    # ------------------------------------------------------------------
    # slot-pool sharding (see serve/README.md "Sharded slot pool")
    # ------------------------------------------------------------------
    @staticmethod
    def _resolve_mesh(mesh, n_slots: int):
        """An explicit `mesh` wins. Otherwise REPRO_SLOT_MESH opts the
        engine into sharding from the environment (the CI sharded-serve job
        sets it): "auto" takes every local device, an integer takes that
        many; either shrinks to the largest count that divides n_slots and
        degrades to single-device (None) at 1."""
        if mesh is not None:
            return mesh
        want = os.environ.get("REPRO_SLOT_MESH", "").strip()
        if not want:
            return None
        n = jax.device_count() if want == "auto" else int(want)
        n = min(n, jax.device_count())
        while n > 1 and n_slots % n != 0:
            n -= 1
        if n <= 1:
            return None
        from repro.launch.mesh import make_slot_mesh
        return make_slot_mesh(n)

    def _make_pool(self, cfg: ModelConfig, cache_kind: str):
        """Fresh pooled cache, placed row-sharded on the mesh when one is
        set. Returns (values_tree, shardings_tree-or-None); the shardings
        come from the logical 'slots' axis (sharding.slot_axes + SLOT_RULES)
        resolved against the mesh."""
        vals, axes = unzip(init_cache(cfg, self.n_slots, self.max_len,
                                      cache_kind=cache_kind, per_slot=True))
        if self.mesh is None:
            return vals, None
        sh = tree_shardings(vals, slot_axes(axes), SLOT_RULES, self.mesh)
        return jax.device_put(vals, sh), sh

    def _replicate(self, tree):
        """Pin a tree (params, long filters) replicated across the mesh."""
        if tree is None or self.mesh is None:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh, P()))

    def _put_slot_vec(self, v):
        """Place a per-slot vector ((n_slots,) or (n_slots, ...)) with the
        pool's row sharding; identity without a mesh."""
        v = jnp.asarray(v)
        return v if self.mesh is None else jax.device_put(v, self._slot_sh)

    def _put_pool(self, tree, shardings):
        """Reload a host-side cache snapshot onto the pool's placement."""
        vals = jax.tree.map(jnp.asarray, tree)
        return vals if shardings is None else jax.device_put(vals, shardings)

    def _shard_tag(self, tag: str):
        return None if self.mesh is None else (self.mesh, tag)

    def _vec_out(self, n: int):
        """out_shardings kwargs pinning n slot-vector outputs (no-op
        without a mesh)."""
        if self.mesh is None:
            return {}
        sh = self._slot_sh if n == 1 else (self._slot_sh,) * n
        return {"out_shardings": sh}

    def _shard_of(self, b: int) -> int:
        """Which mesh shard owns slot row b (P('data') shards the row axis
        in contiguous blocks)."""
        return b * self._n_shards // self.n_slots

    def _pool_write_ops(self, cfg: ModelConfig, cache_kind: str, sh, tag):
        """The three pool-mutating ops (single-row write, batched admission
        write, row reset) for one pool. Sharded pools pin the output to the
        pool's shardings and key the memo per (mesh, cfg, kind, pool) —
        the serving and draft pools have different tree structures, so they
        cannot share one pinned executable."""
        if self.mesh is None:
            return (_jitted("write", write_cache_slot, donate_argnums=(0,)),
                    _jitted("write_many", write_cache_slots,
                            donate_argnums=(0,)),
                    _jitted("reset", reset_cache_slot, donate_argnums=(0,)))
        key = (self.mesh, cfg, cache_kind, tag)
        return (_jitted("write", write_cache_slot, key=key,
                        out_shardings=sh, donate_argnums=(0,)),
                _jitted("write_many", write_cache_slots, key=key,
                        out_shardings=sh, donate_argnums=(0,)),
                _jitted("reset", reset_cache_slot, key=key,
                        out_shardings=sh, donate_argnums=(0,)))

    def _build_pool_ops(self) -> None:
        """(Re)create every executable whose output layout is pinned to the
        serving pool's structure/shardings — at construction, and again when
        a cache-kind demotion (_demote_to_conv) or pool rebuild swaps the
        pool structure. Pinning out_shardings is what keeps a sharded
        steady state at zero recompiles: the decode/spec outputs feed the
        next tick's inputs, so their layout must never drift."""
        from repro.serve.engine import (jitted_decode_step,
                                        jitted_decode_step_guarded,
                                        jitted_finalize_prefill,
                                        jitted_prefill, jitted_prefill_chunk)
        cfg, kind, ctx = self.cfg, self._cache_kind, self.ctx
        sk = None if self.mesh is None else (self.mesh, kind)
        osh = osh_g = None
        if self.mesh is not None:
            osh = (self._cache_sh, self._slot_sh)
            osh_g = (self._cache_sh, self._slot_sh, self._slot_sh)
        self._decode = jitted_decode_step(cfg, ctx, out_shardings=osh,
                                          shard_key=sk)
        self._decode_g = jitted_decode_step_guarded(cfg, ctx,
                                                    out_shardings=osh_g,
                                                    shard_key=sk)
        self._prefill = jitted_prefill(cfg, self.max_len, kind, ctx)
        (self._write_slot, self._write_slots, self._reset_slot) = \
            self._pool_write_ops(cfg, kind, self._cache_sh, "serve")
        self._health_state = _jitted("health_state", _slot_health_state,
                                     key=self._shard_tag("health"),
                                     **self._vec_out(1))
        self._prefill_chunk = (jitted_prefill_chunk(cfg, self.max_len, kind,
                                                    ctx)
                               if self._chunk else None)
        self._finalize = (jitted_finalize_prefill(cfg, self.max_len, kind)
                          if self._chunk else None)

    # ------------------------------------------------------------------
    # request intake
    # ------------------------------------------------------------------
    def submit(self, prompt, *, max_new_tokens: int,
               sampling: SamplingParams = GREEDY,
               eos_id: Optional[int] = None, rid: Optional[int] = None
               ) -> Request:
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        req = Request(rid=self._next_rid if rid is None else rid,
                      prompt=prompt, max_new_tokens=max_new_tokens,
                      sampling=sampling, eos_id=eos_id)
        self._next_rid = max(self._next_rid, req.rid) + 1
        return self.submit_request(req)

    def submit_request(self, req: Request) -> Request:
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # every conv-carrying block kind in the arch bounds the minimum
        # prompt length (the exact-length prefill tail slice needs >= W-1)
        cfg = self.cfg
        w = max((cfg.hyena.short_conv - 1) if cfg.hyena else 1,
                (cfg.ssm.d_conv - 1) if cfg.ssm else 1,
                (cfg.rglru.d_conv - 1) if cfg.rglru else 1, 1)
        if req.prompt_len < w:
            raise ValueError(f"prompt shorter than the short-conv tail ({w})")
        if req.prompt_len + req.max_new_tokens > self.max_len:
            raise ValueError(
                f"request needs {req.prompt_len + req.max_new_tokens} "
                f"positions > max_len={self.max_len}")
        req.t_submit = self._clock()
        if (self._max_queue is not None
                and len(self.queue) >= self._max_queue):
            # bounded-queue admission control: backpressure is an error
            # completion, not an exception — the caller's stream keeps going
            self.resilience.bump("rejected")
            self._record_event("rejected", rid=req.rid)
            self._finish_error(req, "rejected")
            return req
        req.status = QUEUED
        if req.deadline_s is not None:
            self._any_deadline = True
        self.queue.append(req)
        return req

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------
    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def n_free(self) -> int:
        return self.n_slots - self.n_active

    @property
    def has_work(self) -> bool:
        return (bool(self.queue) or self.n_active > 0
                or self._pending is not None
                or self._chunk_state is not None)

    def _slot_is_free(self, b: int) -> bool:
        # a slot reserved by an in-flight chunked prefill holds its Request
        # but is not yet active — it must not be handed out again
        return not self.active[b] and self.slots[b] is None

    def _free_slot(self) -> Optional[int]:
        free = self._free_slots_balanced()
        return free[0] if free else None

    def _free_slots_balanced(self) -> List[int]:
        """Free slots, ordered so admissions spread across mesh shards.
        Single-device this is plain ascending order (unchanged behaviour);
        sharded, each pick goes to the least-loaded shard so one shard never
        ends up crunching every live row while the others decode garbage."""
        free = [b for b in range(self.n_slots) if self._slot_is_free(b)]
        if self._n_shards <= 1 or not free:
            return free
        load = [0] * self._n_shards
        for b in range(self.n_slots):
            if not self._slot_is_free(b):
                load[self._shard_of(b)] += 1
        by_shard: Dict[int, List[int]] = {}
        for b in free:
            by_shard.setdefault(self._shard_of(b), []).append(b)
        out: List[int] = []
        while by_shard:
            s = min(by_shard, key=lambda s: (load[s], s))
            out.append(by_shard[s].pop(0))
            load[s] += 1
            if not by_shard[s]:
                del by_shard[s]
        return out

    def _bucket_of(self, L: int) -> int:
        b = max(self._min_bucket, 1 << max(L - 1, 0).bit_length())
        return min(b, self.max_len)

    def _use_chunked(self, L: int) -> bool:
        return self._chunk is not None and L > self._chunk

    @property
    def t_admit(self) -> float:
        """Host seconds spent in the admission phase (`phase_s_admit`)."""
        return self.stats["phase_s_admit"]

    @t_admit.setter
    def t_admit(self, seconds: float) -> None:
        self.stats["phase_s_admit"] = seconds

    def _span(self, name: str, **args):
        """A phase span: profiler annotation `serve.<name>`, the
        `phase_s_<name>` counter and, with a tracer bound, a ring event."""
        return self.tracer.span(name, stat=self._bump_stat, **args)

    def step(self) -> int:
        """One scheduler tick. Overlapped: (1) enqueue the next pooled decode
        (or speculative draft+verify round) from device-resident state,
        (2) retire the PREVIOUS tick's sampled tokens to host (append / EOS /
        eviction), (3) admit queued requests into freed slots — so host
        bookkeeping and prefills overlap the in-flight decode. Synchronous
        (`overlap=False`): admit, then decode and retire in the same tick
        (the original loop). Returns the number of tokens appended to
        requests during this call."""
        self._tick += 1
        with self._span("tick", step=self._tick):
            return self._step()

    def _step(self) -> int:
        t_step0 = self._clock()
        emitted = 0
        if self._injector is not None:
            with self._span("faults"):
                self._apply_scheduled_faults()
        if self._sentinel and self._tick % self._drift_every == 0:
            # sentinel sync point: retire the in-flight tick first so the
            # host-side token record matches the at-rest device cache
            with self._span("drift_check"):
                prev0, self._pending = self._pending, None
                emitted += self._retire(prev0)
                self._drift_check()
        dispatch = self._dispatch_spec if self._spec else self._dispatch_decode
        prev, self._pending = self._pending, None
        if self._overlap and self.n_active > 0:
            with self._span("dispatch"):
                self._pending = self._safe_dispatch(dispatch)
        with self._span("retire"):
            emitted += self._retire(prev)
        if self._any_deadline:
            with self._span("deadline_sweep"):
                self._sweep_deadlines()
        with self._span("admit"):
            emitted += self._admit_phase()
        if not self._overlap and self.n_active > 0:
            with self._span("dispatch"):
                pend = self._safe_dispatch(dispatch)
            with self._span("retire"):
                emitted += self._retire(pend)
        # per-tick telemetry: the tick-latency histogram is what the
        # watchdog reads, so its cost is the one clock call either way
        lat = self._clock() - t_step0
        self._h_tick.observe(lat)
        n_act = self.n_active
        self._g_queue.set(len(self.queue))
        self._g_active.set(n_act)
        self._h_fill.observe(n_act / self.n_slots)
        if self._n_shards > 1:
            occ = [0] * self._n_shards
            for b in np.nonzero(self.active)[0]:
                occ[self._shard_of(int(b))] += 1
            for g, n in zip(self._g_shard_occ, occ):
                g.set(n)
        if self._watchdog_s is not None and lat > self._watchdog_s:
            self.resilience.bump("watchdog_trips")
            self._record_event("watchdog", latency_s=round(lat, 4))
        return emitted

    # ------------------------------------------------------------------
    # resilience: fault application, guarded dispatch, deadlines
    # ------------------------------------------------------------------
    def _record_event(self, kind: str, **detail) -> None:
        self.events.append({"tick": self._tick, "kind": kind, **detail})
        self._events_total += 1
        self._c_events.inc()
        tr = self.tracer
        if tr.enabled:
            # fold the recovery stream into the trace: rid-carrying events
            # land on the request's own track, the rest on the host track
            tr.instant(kind, cat="recovery", rid=detail.get("rid"),
                       tick=self._tick,
                       **{k: v for k, v in detail.items() if k != "rid"})

    def _bump_stat(self, key: str, n: int = 1) -> None:
        """Increment a stats-dict counter and its mirrored registry counter
        (the dict stays the cheap delta the benches take; the registry
        carries the same series as `serve_<key>` for exposition)."""
        self.stats[key] += n
        c = self._mc.get(key)
        if c is None:
            c = self._mc[key] = self.metrics.counter("serve_" + key)
        c.inc(n)

    def _apply_scheduled_faults(self) -> None:
        """Fire this tick's scripted faults (corrupt / drift / expire /
        stall); the "raise" kind fires inside _safe_dispatch so it lands
        exactly where a real dispatch failure would."""
        inj = self._injector
        tick = self._tick
        residents = [b for b in range(self.n_slots) if self.active[b]]
        for e in inj.corruptions(tick):
            b = inj.pick_slot(e, tick, residents)
            if b is None:
                continue
            self.cache = corrupt_cache_slot(self.cache, b, e.where, e.value)
            inj.record(tick, "corrupt", slot=b, where=e.where)
        for e in inj.drifts(tick):
            b = inj.pick_slot(e, tick, residents)
            if b is None:
                continue
            eps = e.value if math.isfinite(e.value) else 0.05
            self.cache = drift_cache_slot(self.cache, b, eps)
            inj.record(tick, "drift", slot=b, eps=eps)
        for e in inj.expirations(tick):
            b = inj.pick_slot(e, tick, residents)
            if b is None or self.slots[b] is None:
                continue
            req = self.slots[b]
            inj.record(tick, "expire", slot=b, rid=req.rid)
            self.resilience.bump("deadline_expiries")
            self._record_event("deadline", rid=req.rid, forced=True)
            self._finish_error(req, "deadline")
        st = inj.stall_s(tick)
        if st > 0:
            time.sleep(st)

    def _safe_dispatch(self, dispatch):
        """Dispatch one tick, absorbing failures. An injected FaultError is
        raised BEFORE the jitted call, so the donated pool buffers are still
        valid and the tick is simply skipped; a genuine in-flight exception
        may have invalidated donated buffers, so the pool is rebuilt and
        every resident recovered from its committed tokens."""
        try:
            if self._injector is not None:
                self._injector.raise_if_scheduled(self._tick)
            return dispatch()
        except FaultError:
            self.resilience.bump("dispatch_faults")
            self._record_event("dispatch_fault", injected=True)
            return None
        except Exception as e:                        # noqa: BLE001
            self.resilience.bump("dispatch_faults")
            self._record_event("dispatch_fault", injected=False,
                              error=repr(e))
            self._rebuild_pool()
            return None

    def _sweep_deadlines(self) -> None:
        """Expire requests past their end-to-end budget (per-request
        deadline_s, falling back to the engine default): queued requests are
        rejected in place, a chunk-in-flight prefill is cancelled, running
        slots are released. All finish with ERROR status."""
        now = self._clock()

        def expired(req: Request) -> bool:
            dl = req.deadline_s if req.deadline_s is not None \
                else self._deadline_s
            return (dl is not None and not math.isnan(req.t_submit)
                    and now - req.t_submit > dl)

        for req in [r for r in self.queue if expired(r)]:
            self.resilience.bump("deadline_expiries")
            self._record_event("deadline", rid=req.rid, where="queued")
            self._finish_error(req, "deadline")
        if self._chunk_state is not None and expired(self._chunk_state["req"]):
            req = self._chunk_state["req"]
            self._chunk_state = None
            self.resilience.bump("deadline_expiries")
            self._record_event("deadline", rid=req.rid, where="prefilling")
            self._finish_error(req, "deadline")
        for b in range(self.n_slots):
            req = self.slots[b]
            if req is not None and req.status == RUNNING and expired(req):
                self.resilience.bump("deadline_expiries")
                self._record_event("deadline", rid=req.rid, where="running")
                self._finish_error(req, "deadline")

    # ------------------------------------------------------------------
    # drift sentinel (serve/README.md "Exact fallback & drift sentinel")
    # ------------------------------------------------------------------
    @property
    def drift_certificate(self):
        """Static distillation-error certificate
        (core.distill.distillation_certificate), computed lazily and
        cached — the bench drift gate compares the sentinel's measured
        divergence against its per-layer tail bounds."""
        if self._drift_certificate is None and self.cfg.hyena is not None:
            from repro.core.distill import distillation_certificate
            self._drift_certificate = distillation_certificate(
                self.params, self.cfg, self.max_len)
        return self._drift_certificate

    def _drift_check(self) -> None:
        """Shadow-verify one resident slot through the exact path: replay
        its prompt + committed tokens through the epoch-kind prefill (the
        TRUE long filter, full causal FFT) and decode the same last token
        once on a gathered copy of its distilled pool row — both produce
        the next-token distribution, so any |log-softmax| gap beyond
        float32 noise is accumulated distillation error or silent state
        corruption. Off the critical path: runs at the sentinel sync point
        (pending already retired, slot caches at rest), touches only a
        copy of the slot row, and costs one 1-row bucketed prefill.
        Divergence beyond `drift_tol` demotes the engine to mode="epoch"
        and re-prefills every resident through the exact path."""
        residents = [b for b in range(self.n_slots)
                     if self.active[b] and self.slots[b] is not None
                     and self.slots[b].status == RUNNING
                     and self.slots[b].tokens]
        if not residents:
            return
        b = residents[self._drift_cursor % len(residents)]
        self._drift_cursor += 1
        req = self.slots[b]
        seq = np.concatenate([req.prompt, np.asarray(req.tokens, np.int32)])
        L = int(len(seq))
        if L > self.max_len:
            return
        bkt = self._bucket_of(L)
        toks = np.zeros((1, bkt), np.int32)
        toks[0, :L] = seq
        _, exact = self._drift_prefill(self.params, jnp.asarray(toks),
                                       lengths=jnp.asarray([L], jnp.int32))
        # distilled side: decode on a host-round-tripped copy of the slot's
        # pool row — the copy keeps the pool out of the decode donation and
        # normalizes placement so the shadow decode holds ONE executable
        row = jax.device_get(self._gather_rows(self.cache,
                                               jnp.asarray([b], jnp.int32)))
        # numpy first: jnp.asarray on a nested python list dispatches a
        # convert_element_type executable; np -> jax is a plain device put
        tok = jnp.asarray(np.asarray([[req.tokens[-1]]], np.int32))
        _, approx = self._drift_decode(self.params, row, tok,
                                       conv_filters=None)
        # device_get whole arrays, index on host: slicing a jax array
        # here would dispatch tiny dynamic_slice/squeeze executables and
        # break the zero-steady-state-compiles guarantee
        e = _log_softmax_np(np.asarray(jax.device_get(exact),
                                       np.float64)[0])
        a = _log_softmax_np(np.asarray(jax.device_get(approx),
                                       np.float64)[0, 0])
        div = float(np.max(np.abs(e - a)))
        if not math.isfinite(div):
            # a NaN/Inf shadow comparison means the distilled row no longer
            # produces a distribution at all — maximal drift, not a skip
            div = float("inf")
        self._drift_last = div
        self._h_drift.observe(div)
        self.resilience.bump("drift_checks")
        if self._drift_tol is not None and div > self._drift_tol:
            self.resilience.bump("drift_alarms")
            self._record_event("drift_alarm", rid=req.rid, slot=b,
                               divergence=round(div, 6))
            self._demote_engine("epoch")

    def run(self) -> List[Request]:
        """Drain queue + residents to completion; returns finished requests."""
        while self.has_work:
            self.step()
        return self.finished

    def warmup(self, prompt_lens: Sequence[int]) -> None:
        """Compile the serving fast path before a timed run: ONE batched
        prefill per prompt-length *bucket* (not per distinct length), the
        chunked-prefill step + finalize when enabled, the pooled decode step,
        the batched sampler, and the slot-scatter ops. Side effect: idle
        slots advance one (ignored) decode position."""
        with self._span("warmup"):
            self._warmup(prompt_lens)

    def _warmup(self, prompt_lens: Sequence[int]) -> None:
        lens = sorted({int(x) for x in prompt_lens})
        direct = [L for L in lens if not self._use_chunked(L)]
        # host-side request-key derivation (fold_in + stack at admission)
        # compiles tiny executables on first use — warm them here (at every
        # admission-batch width) so the steady state stays at zero XLA
        # compiles in a fresh process
        rk = jax.random.fold_in(self._base_key, 0)
        for width in {1, self._prefill_batch}:
            jnp.stack([rk] * width)
        # eviction-time slot-meta clear (slot n_slots = dropped no-op)
        (self._temps, self._top_ks, self._top_ps, self._spec_len) = \
            self._clear_meta(self._temps, self._top_ks, self._top_ps,
                             self._spec_len, self.n_slots)

        def warm_admission_ops(K: int, logits) -> None:
            # first-token sampler + slot-meta scatter at admission batch size
            # K; slot index n_slots makes every row a dropped no-op
            tj = jnp.zeros((K,), jnp.float32)
            kj = jnp.zeros((K,), jnp.int32)
            pj = jnp.ones((K,), jnp.float32)
            keyvec = jnp.zeros((K,) + self._base_key.shape,
                               self._base_key.dtype)
            toks = self._admit_sample(keyvec, logits, tj, kj, pj)
            (self._temps, self._top_ks, self._top_ps, self._last,
             self._slot_keys, self._tok_idx, self._spec_len) = self._meta(
                self._temps, self._top_ks, self._top_ps, self._last,
                self._slot_keys, self._tok_idx, self._spec_len,
                jnp.full((K,), self.n_slots, jnp.int32), tj, kj, pj, toks,
                keyvec, jnp.ones((K,), jnp.int32), jnp.ones((K,), jnp.int32))

        if self._bucketed:
            K = self._prefill_batch
            for bkt in sorted({self._bucket_of(L) for L in direct}):
                cache1, logits = self._prefill(
                    self.params, jnp.zeros((K, bkt), jnp.int32),
                    lengths=jnp.full((K,), bkt, jnp.int32))
                # dummy scatter (slot index n_slots drops every row)
                self.cache = self._write_slots(
                    self.cache, cache1, jnp.full((K,), self.n_slots,
                                                 jnp.int32))
                if self._spec and not self._draft_shared:
                    dc1, _ = self._draft_prefill(
                        self._draft_params, jnp.zeros((K, bkt), jnp.int32),
                        lengths=jnp.full((K,), bkt, jnp.int32))
                    self.draft_cache = self._write_slots_d(
                        self.draft_cache, dc1,
                        jnp.full((K,), self.n_slots, jnp.int32))
                warm_admission_ops(K, logits)
                self._buckets_used.add(bkt)
        else:
            for L in direct:
                _, logits = self._prefill(self.params,
                                          jnp.zeros((1, L), jnp.int32))
                if self._spec and not self._draft_shared:
                    self._draft_prefill(self._draft_params,
                                        jnp.zeros((1, L), jnp.int32))
                warm_admission_ops(1, logits)
        if self._chunk is not None and any(self._use_chunked(L) for L in lens):
            pc = self._new_prefill_cache()
            pc, logits = self._prefill_chunk(
                self.params, pc, jnp.zeros((1, self._chunk), jnp.int32), 0,
                chunk_len=self._chunk, conv_filters=self._chunk_filters)
            dc = self._finalize(pc, self._chunk)
            # write + reset slot 0 (free at warmup time) to warm both ops
            self.cache = self._write_slot(self.cache, dc, 0)
            self.cache = self._reset_slot(self.cache, 0)
            if self._spec and not self._draft_shared:
                dpc = self._new_draft_prefill_cache()
                dpc, _ = self._draft_prefill_chunk(
                    self._draft_params, dpc,
                    jnp.zeros((1, self._chunk), jnp.int32), 0,
                    chunk_len=self._chunk, conv_filters=self._chunk_filters)
                ddc = self._draft_finalize(dpc, self._chunk)
                self.draft_cache = self._write_slot_d(self.draft_cache,
                                                      ddc, 0)
                self.draft_cache = self._reset_slot_d(self.draft_cache, 0)
            warm_admission_ops(1, logits)
        if self._spec:
            # one speculative round (fused draft scan + verify/commit) per
            # compiled depth level, so a controller-shrunk window never
            # compiles mid-run; slots are all idle here, so the garbage
            # advance is ignored exactly like the plain-decode warm tick
            for L in self._spec_levels:
                (self.cache, new_draft, _, _, self._last, self._tok_idx) = \
                    self._spec_rounds[L](
                        self.params, self._draft_params, self.cache,
                        self._last, self._spec_len,
                        None if self._draft_shared else self.draft_cache,
                        temperature=self._temps, top_k=self._top_ks,
                        top_p=self._top_ps, slot_keys=self._slot_keys,
                        tok_idx=self._tok_idx,
                        conv_filters=self._conv_filters)
                if not self._draft_shared:
                    self.draft_cache = new_draft
            # the engine falls back to the plain pooled decode whenever no
            # live slot speculates (all windows 1) — warm that path too
            self.cache, logits = self._decode(self.params, self.cache,
                                              self._last[:, None],
                                              conv_filters=self._conv_filters)
            self._stream_sample(self._slot_keys, self._tok_idx,
                                logits[:, 0, :], self._temps, self._top_ks,
                                self._top_ps)
            jax.block_until_ready((self.cache, self.draft_cache))
        else:
            self.cache, logits = self._decode(self.params, self.cache,
                                              self._last[:, None],
                                              conv_filters=self._conv_filters)
            self._stream_sample(self._slot_keys, self._tok_idx,
                                logits[:, 0, :], self._temps, self._top_ks,
                                self._top_ps)
            jax.block_until_ready(self.cache)
        if self._guard:
            # state-integrity guards ride the decode dispatch: warm the
            # fused guarded decode, the spec-path health variant and the
            # quarantine-path slot reset so the steady state stays at zero
            # XLA compiles with guards enabled
            self.cache, logits, h = self._decode_g(
                self.params, self.cache, self._last[:, None],
                self._state_bound, conv_filters=self._conv_filters)
            warm = [h]
            if self._spec:
                warm.append(self._health_state(self.cache, self._state_bound))
            self.cache = self._reset_slot(self.cache, 0)    # idle at warmup
            jax.block_until_ready(warm)
        if self._sentinel:
            # drift-sentinel dispatches: 1-row epoch-kind prefill at every
            # power-of-two bucket (a resident can be checked at any length
            # up to max_len), plus the row gather + 1-row shadow decode —
            # so a sentinel tick never compiles in the steady state
            bkt = self._min_bucket
            while True:
                bkt = min(bkt, self.max_len)
                self._drift_prefill(self.params,
                                    jnp.zeros((1, bkt), jnp.int32),
                                    lengths=jnp.asarray([bkt], jnp.int32))
                if bkt == self.max_len:
                    break
                bkt <<= 1
            row = jax.device_get(self._gather_rows(
                self.cache, jnp.asarray([0], jnp.int32)))
            _, lg = self._drift_decode(self.params, row,
                                       jnp.zeros((1, 1), jnp.int32),
                                       conv_filters=None)
            jax.block_until_ready(lg)

    def prefill_compile_stats(self) -> Dict[str, Any]:
        """Executable counts backing the O(#buckets) claim. Note the jit memo
        is shared across engines with the same (cfg, max_len, mode), so
        counts are per-configuration, not per-instance."""
        from repro.serve.metrics import jit_cache_size
        out: Dict[str, Any] = {
            "buckets_used": sorted(self._buckets_used),
            "prefill_executables": jit_cache_size(self._prefill),
        }
        if self._prefill_chunk is not None:
            out["chunk_executables"] = jit_cache_size(self._prefill_chunk)
        return out

    # ------------------------------------------------------------------
    # decode: overlapped dispatch / retire
    # ------------------------------------------------------------------
    def _dispatch_decode(self):
        """Enqueue one pooled decode + sample on device state; returns a
        pending record (slot->request snapshot, device token vector) to be
        retired after the NEXT dispatch."""
        self._dispatch_seq += 1
        health = None
        with self._span("decode_step"):
            if self._guard and self._tick % self._health_every == 0:
                # fused variant: the integrity reduction rides the decode
                # executable — no extra host dispatch on the hot path
                self.cache, logits, health = self._decode_g(
                    self.params, self.cache, self._last[:, None],
                    self._state_bound, conv_filters=self._conv_filters)
            else:
                self.cache, logits = self._decode(
                    self.params, self.cache, self._last[:, None],
                    conv_filters=self._conv_filters)
            nxt, self._tok_idx = self._stream_sample(
                self._slot_keys, self._tok_idx, logits[:, 0, :], self._temps,
                self._top_ks, self._top_ps)
        self._last = nxt
        self._bump_stat("decode_steps")
        if self._n_sampled:
            self._bump_stat("sampled_decode_steps")
        snapshot = [(int(b), self.slots[b], 1)
                    for b in np.nonzero(self.active)[0]]
        try:
            nxt.copy_to_host_async()           # double-buffered transfer
            if health is not None:
                health.copy_to_host_async()
        except AttributeError:
            pass
        return (self._dispatch_seq, snapshot, nxt, None, health)

    def _sync_spec_len(self) -> None:
        """Upload the per-slot window vector when the controller changed it.
        One whole-vector transfer, no recompile (spec_len is data). The
        upload goes through `_put_slot_vec`, so on a sharded pool each
        device receives only its own row block — a plain `jnp.asarray`
        would land the vector committed to device 0 and force an all-to-one
        layout change inside the next spec round. The mirror is uploaded
        as a copy: the CPU client may alias an aligned numpy buffer
        zero-copy, and the controller rewrites the mirror while the
        overlapped loop still has this round queued."""
        if not np.array_equal(self._spec_win, self._spec_win_dev):
            self._spec_len = self._put_slot_vec(
                np.array(self._spec_win, np.int32))
            self._spec_win_dev[:] = self._spec_win
            self._bump_stat("spec_window_syncs")
            self.resilience.bump("spec_window_syncs")

    def _dispatch_spec(self):
        """Enqueue one speculative round — fused K-step draft scan (on the
        serving cache itself for the shared-state draft, else on the draft
        pool; the scan's advanced state is discarded) + multi-token verify,
        acceptance, rollback and replay — as ONE device dispatch per up to
        window-1 + 1 tokens per slot. The controller picks each slot's
        window first; the round then runs the smallest compiled depth
        covering the widest live window, or falls back to the plain pooled
        decode when no live slot speculates this tick. Drafted-token stats
        are counted HERE, at dispatch — a slot evicted before its round
        retires still spent the draft work (the accounting bug the
        retire-time counter had)."""
        act = np.nonzero(self.active)[0]
        if self._spec_ctl is not None:
            for b in act:
                self._spec_win[b] = self._spec_ctl.on_round(int(b))
        need = int(max((self._spec_win[b] for b in act), default=1)) - 1
        if need <= 0:
            return self._dispatch_decode()
        self._dispatch_seq += 1
        self._sync_spec_len()
        K_r = next(L for L in self._spec_levels if L >= need)
        with self._span("spec_round", depth=K_r):
            (self.cache, new_draft, emitted, n_emit, last, tok_idx) = \
                self._spec_rounds[K_r](
                    self.params, self._draft_params, self.cache,
                    self._last, self._spec_len,
                    None if self._draft_shared else self.draft_cache,
                    temperature=self._temps,
                    top_k=self._top_ks, top_p=self._top_ps,
                    slot_keys=self._slot_keys,
                    tok_idx=self._tok_idx,
                    conv_filters=self._conv_filters)
        if not self._draft_shared:
            self.draft_cache = new_draft
        self._last, self._tok_idx = last, tok_idx
        self._bump_stat("decode_steps")
        if self._n_sampled:
            self._bump_stat("sampled_decode_steps")
        self._bump_stat("spec_rounds")
        snapshot = []
        for b in act:
            req = self.slots[b]
            win = int(self._spec_win[b])
            if req is not None and req.spec and win > 1:
                self._bump_stat("spec_drafted", win - 1)
                self._bump_stat("spec_slot_rounds")
                self._h_spec_win.observe(win)
            snapshot.append((int(b), req, win))
        health = None
        if self._guard and self._tick % self._health_every == 0:
            health = self._health_state(self.cache, self._state_bound)
        try:
            emitted.copy_to_host_async()
            n_emit.copy_to_host_async()
            if health is not None:
                health.copy_to_host_async()
        except AttributeError:
            pass
        return (self._dispatch_seq, snapshot, emitted, n_emit, health)

    def _retire(self, pending) -> int:
        """Fetch a dispatched tick's tokens (the only host sync point on the
        decode path) and do the EOS/eviction bookkeeping. Speculative
        pending records carry (emitted (B, C), n_emit (B,)): each slot
        appends its accepted prefix + correction, stopping early on EOS /
        max-tokens eviction (the remaining speculated tokens are dropped,
        exactly as a non-speculative run would never have produced them)."""
        if pending is None:
            return 0
        seq, snapshot, toks_dev, n_emit_dev, health_dev = pending
        with self._span("retire.wait"):
            toks = np.asarray(toks_dev)
            n_emit = None if n_emit_dev is None else np.asarray(n_emit_dev)
            health = None if health_dev is None else np.asarray(health_dev)
        emitted = 0
        for b, req, win in snapshot:
            # slot may have been evicted (and even re-admitted) since this
            # tick was dispatched — its speculative token is dropped (the
            # round's drafted tokens were already counted at dispatch, so
            # the acceptance denominator keeps the wasted work). The
            # admit_seq guard catches the SAME request re-admitted into the
            # same slot by a quarantine recovery: a pending dispatched at or
            # before the re-admission (admit_seq records the dispatch
            # counter at admission time, so this is ordering-exact in both
            # the overlapped and sync loops) must not touch the freshly
            # re-prefilled state with its stale tokens or health verdict.
            if (self.slots[b] is not req or req.status != RUNNING
                    or req.admit_seq >= seq):
                continue
            if health is not None and not bool(health[b]):
                # guard tripped: this tick's token(s) for the slot are
                # poisoned — drop them and quarantine the request (re-prefill
                # from its committed tokens, or error out past max_retries)
                self._quarantine(b, req)
                continue
            if n_emit is None:
                self._append_token(b, int(toks[b]))
                emitted += 1
                continue
            n = int(n_emit[b])
            applied = 0
            for j in range(n):
                self._append_token(b, int(toks[b, j]))
                applied += 1
                emitted += 1
                if self.slots[b] is not req or req.status != RUNNING:
                    break                      # evicted mid-speculation
            if req.spec and win > 1:
                # count only DELIVERED accepted drafts: tokens truncated by
                # an EOS/max-tokens eviction never reached the request. A
                # full delivery ends with the correction token (applied - 1
                # drafts); a truncated one delivered accepted drafts only.
                self._bump_stat("spec_accepted", (applied - 1 if applied == n
                                                  else applied))
                if self._spec_ctl is not None and self.slots[b] is req:
                    # feed the controller the round's raw acceptance (n - 1
                    # of win - 1 drafts accepted, eviction or not); skip if
                    # the request just finished — its slot state is reset
                    self._spec_win[b] = self._spec_ctl.observe(
                        b, win - 1, n - 1)
        return emitted

    # ------------------------------------------------------------------
    # admission: bucketed batches + chunked long prompts
    # ------------------------------------------------------------------
    def _eff_prompt(self, req: Request) -> np.ndarray:
        """The token sequence a (re-)admission must prefill: the prompt,
        plus — for a recovered request — all committed tokens but the last
        (which becomes the slot's `_last` input, exactly the state a
        fault-free run had after emitting it)."""
        if req.tokens:
            return np.concatenate([req.prompt,
                                   np.asarray(req.tokens[:-1], np.int32)])
        return req.prompt

    def _eff_len(self, req: Request) -> int:
        return req.prompt_len + max(0, len(req.tokens) - 1)

    def _eligible(self, req: Request) -> bool:
        return req.retry_at <= self._tick      # quarantine backoff

    def _admit_phase(self) -> int:
        emitted = 0
        budget = self.max_prefills_per_step
        if self._chunk_state is not None and budget > 0:
            emitted += self._advance_chunk()     # one chunk per tick
            budget -= 1
        while budget > 0 and self.queue and self._free_slot() is not None:
            idx = chunked = None
            for i, r in enumerate(self.queue):
                if not self._eligible(r):
                    continue
                if self._use_chunked(self._eff_len(r)):
                    if self._chunk_state is None:
                        idx, chunked = i, True
                        break
                    continue          # long prefill in flight; allow bypass
                idx, chunked = i, False
                break
            if idx is None:
                break
            if chunked:
                req = self._pop_queue([idx])[0]
                self._start_chunked(req, self._free_slot())
                emitted += self._advance_chunk()
                budget -= 1
                continue
            if self._bucketed:
                bkt = self._bucket_of(self._eff_len(self.queue[idx]))
                free = self._free_slots_balanced()
                limit = min(budget, len(free), self._prefill_batch)
                take = []
                for i in range(idx, len(self.queue)):
                    r = self.queue[i]
                    if (self._eligible(r)
                            and not self._use_chunked(self._eff_len(r))
                            and self._bucket_of(self._eff_len(r)) == bkt):
                        take.append(i)
                        if len(take) == limit:
                            break
                reqs = self._pop_queue(take)
                emitted += self._admit_batch(reqs, free[:len(reqs)], bkt)
                budget -= len(reqs)
            else:
                req = self._pop_queue([idx])[0]
                emitted += self._admit_batch([req], [self._free_slot()], None)
                budget -= 1
        return emitted

    def _pop_queue(self, indices: List[int]) -> List[Request]:
        picked = set(indices)
        out = [self.queue[i] for i in indices]
        now = self._clock()
        for req in out:
            if math.isnan(req.t_dequeued):
                req.t_dequeued = now
        self.queue = deque(r for i, r in enumerate(self.queue)
                           if i not in picked)
        return out

    def _admit_batch(self, reqs: List[Request], slots: List[int],
                     bucket: Optional[int]) -> int:
        """Prefill `reqs` together and scatter into `slots`. bucket=None is
        the legacy exact-length batch=1 path (bucket_prompts=False)."""
        padded = self._eff_len(reqs[0]) if bucket is None else bucket
        for req in reqs:
            if not req.prefill_rows:
                req.prefill_bucket, req.prefill_rows = padded, len(reqs)
        dspan = self._span("prefill", n=len(reqs), bucket=padded)
        if bucket is None:
            with dspan:
                prompt = jnp.asarray(self._eff_prompt(reqs[0]),
                                     jnp.int32)[None]
                cache1, logits = self._prefill(self.params, prompt)
                self.cache = self._write_slot(self.cache, cache1, slots[0])
                if self._spec and not self._draft_shared:
                    dc1, _ = self._draft_prefill(self._draft_params, prompt)
                    self.draft_cache = self._write_slot_d(self.draft_cache,
                                                          dc1, slots[0])
        else:
            with dspan:
                K = self._prefill_batch
                toks = np.zeros((K, bucket), np.int32)
                lens = np.full((K,), bucket, np.int32)     # dummy rows: full
                slot_idx = np.full((K,), self.n_slots,
                                   np.int32)               # dummies drop
                for j, (req, slot) in enumerate(zip(reqs, slots)):
                    ep = self._eff_prompt(req)
                    toks[j, :len(ep)] = ep
                    lens[j] = len(ep)
                    slot_idx[j] = slot
                cache1, logits = self._prefill(self.params, jnp.asarray(toks),
                                               lengths=jnp.asarray(lens))
                self.cache = self._write_slots(self.cache, cache1,
                                               jnp.asarray(slot_idx))
                if self._spec and not self._draft_shared:
                    dc1, _ = self._draft_prefill(self._draft_params,
                                                 jnp.asarray(toks),
                                                 lengths=jnp.asarray(lens))
                    self.draft_cache = self._write_slots_d(
                        self.draft_cache, dc1, jnp.asarray(slot_idx))
            self._buckets_used.add(bucket)
        self._bump_stat("prefills", len(reqs))
        self._bump_stat("prefill_calls")
        return self._register_admissions(reqs, slots, logits)

    def _register_admissions(self, reqs: List[Request], slots: List[int],
                             logits) -> int:
        """Sample first tokens from prefill logits (rows 0..len(reqs)-1 are
        the real requests) with each request's OWN stream-index-0 key, push
        sampling params + PRNG keys + stream counters + last tokens to the
        device slot vectors, and flip host bookkeeping to RUNNING."""
        K = logits.shape[0]
        t = np.zeros(K, np.float32)
        k = np.zeros(K, np.int32)
        p = np.ones(K, np.float32)
        sl = np.full(K, self.n_slots, np.int32)
        slen = np.ones(K, np.int32)
        ti = np.ones(K, np.int32)
        resume = np.zeros(K, bool)         # recovery: committed tokens exist
        last_tok = np.zeros(K, np.int32)
        for j, (req, slot) in enumerate(zip(reqs, slots)):
            sp = req.sampling
            t[j], k[j], p[j] = sp.temperature, sp.top_k, sp.top_p
            sl[j] = slot
            slen[j] = (self._spec_k + 1 if (self._spec and req.spec) else 1)
            if req.tokens:
                # recovered request: the cache was re-prefilled through
                # tokens[:-1]; tokens[-1] is the decode input and the stream
                # counter resumes at len(tokens) — the same per-(slot, index)
                # keys a fault-free run would consume next (bit-exactness)
                resume[j] = True
                last_tok[j] = req.tokens[-1]
                ti[j] = len(req.tokens)
        # per-request key tree roots: fold_in(engine_key, rid) — path- and
        # admission-order-independent, so spec and non-spec runs of the same
        # request set consume identical key streams (see serve/README.md)
        rk = [jax.random.fold_in(self._base_key, req.rid) for req in reqs]
        rk += [self._base_key] * (K - len(reqs))        # dummy rows: dropped
        keyvec = jnp.stack(rk)
        tj, kj, pj = jnp.asarray(t), jnp.asarray(k), jnp.asarray(p)
        toks = self._admit_sample(keyvec, logits, tj, kj, pj)
        if resume.any():
            toks = jnp.where(jnp.asarray(resume), jnp.asarray(last_tok), toks)
        (self._temps, self._top_ks, self._top_ps, self._last,
         self._slot_keys, self._tok_idx, self._spec_len) = self._meta(
            self._temps, self._top_ks, self._top_ps, self._last,
            self._slot_keys, self._tok_idx, self._spec_len,
            jnp.asarray(sl), tj, kj, pj, toks, keyvec,
            jnp.asarray(ti), jnp.asarray(slen))
        enqueued = self._clock()
        with self._span("admit.wait"):
            toks_h = np.asarray(toks)
        now = self._clock()
        for j, (req, slot) in enumerate(zip(reqs, slots)):
            # host mirror + shadow of the device window vector stay in sync
            # with the _meta scatter above (no upload needed this tick)
            self._spec_win[slot] = slen[j]
            self._spec_win_dev[slot] = slen[j]
            if self._spec_ctl is not None:
                self._spec_ctl.admit(slot,
                                     enabled=bool(self._spec and req.spec))
            req.status = RUNNING
            req.slot = slot
            req.admit_seq = self._dispatch_seq
            if math.isnan(req.t_admitted):
                req.t_prefill_enqueued = enqueued
                req.t_admitted = now
            self.slots[slot] = req
            self.active[slot] = True
            self._n_sampled += req.sampling.temperature > 0.0
            self._bump_stat("admitted")
            if resume[j]:
                continue          # recovery: no new token at re-admission
            # first generated token comes from the prefill logits (same
            # convention as GenerationEngine.generate)
            self._append_token(slot, int(toks_h[j]))
        return len(reqs) - int(resume[:len(reqs)].sum())

    # ------------------------------------------------------------------
    # chunked long-prompt admission
    # ------------------------------------------------------------------
    def _new_prefill_cache(self):
        # replicated-committed on a mesh: the chunk step's OUTPUT cache is
        # committed (its inputs carry the mesh), so a fresh scratch cache
        # must be too, or chunk 2 of a long prompt recompiles the step with
        # a committed-pcache signature chunk 1 never saw
        pc, _ = unzip(init_prefill_cache(self.cfg, 1, self.max_len,
                                         chunk=self._chunk,
                                         cache_kind=self._cache_kind))
        return self._replicate(pc)

    def _new_draft_prefill_cache(self):
        pc, _ = unzip(init_prefill_cache(self._draft_cfg, 1, self.max_len,
                                         chunk=self._chunk,
                                         cache_kind="native"))
        return self._replicate(pc)

    def _start_chunked(self, req: Request, slot: int) -> None:
        req.status = PREFILLING
        req.slot = slot
        if not req.prefill_rows:
            C = self._chunk
            req.prefill_bucket = -(-self._eff_len(req) // C) * C
            req.prefill_rows = 1
        self.slots[slot] = req                  # reserve (not yet active)
        self._chunk_state = {"req": req, "slot": slot,
                             "prompt": self._eff_prompt(req),
                             "pcache": self._new_prefill_cache(),
                             "dcache": (self._new_draft_prefill_cache()
                                        if self._spec
                                        and not self._draft_shared else None),
                             "start": 0}

    def _advance_chunk(self) -> int:
        """Consume one chunk of the in-flight long prompt; on the final chunk
        finalize into the reserved slot and emit the first token. With
        speculation on, the draft pool's chunked prefill advances in
        lockstep (one extra chunk executable per tick)."""
        st = self._chunk_state
        req: Request = st["req"]
        prompt = st["prompt"]                   # eff prompt (recovery-aware)
        plen = int(prompt.shape[0])
        C = self._chunk
        cl = min(C, plen - st["start"])
        buf = np.zeros((1, C), np.int32)
        buf[0, :cl] = prompt[st["start"]:st["start"] + cl]
        with self._span("prefill_chunk", rid=req.rid, start=st["start"]):
            st["pcache"], last_logits = self._prefill_chunk(
                self.params, st["pcache"], jnp.asarray(buf), st["start"],
                chunk_len=cl, conv_filters=self._chunk_filters)
            if self._spec and not self._draft_shared:
                st["dcache"], _ = self._draft_prefill_chunk(
                    self._draft_params, st["dcache"], jnp.asarray(buf),
                    st["start"], chunk_len=cl,
                    conv_filters=self._chunk_filters)
        st["start"] += cl
        self._bump_stat("chunk_steps")
        if st["start"] < plen:
            return 0
        dcache = self._finalize(st["pcache"], plen)
        slot = st["slot"]
        self.cache = self._write_slot(self.cache, dcache, slot)
        if self._spec and not self._draft_shared:
            ddc = self._draft_finalize(st["dcache"], plen)
            self.draft_cache = self._write_slot_d(self.draft_cache, ddc, slot)
        self._bump_stat("prefills")
        self._bump_stat("prefill_calls")
        self._chunk_state = None
        self.slots[slot] = None                 # _register re-claims it
        return self._register_admissions([req], [slot], last_logits)

    # ------------------------------------------------------------------
    def _append_token(self, slot: int, tok: int) -> None:
        req = self.slots[slot]
        assert req is not None
        if math.isnan(req.t_first_token):
            req.t_first_token = self._clock()
        req.tokens.append(tok)
        if req.eos_id is not None and tok == req.eos_id:
            self._evict(slot, "eos")
        elif len(req.tokens) >= req.max_new_tokens:
            self._evict(slot, "max_tokens")

    def _release_slot(self, slot: int) -> None:
        """Free a slot without finishing its request: host bookkeeping plus
        the device-metadata neutralization every departure needs (a stale
        temperature or speculation window on a dead row would force the slow
        branch of every jnp.all fast path — greedy sampler, full-accept
        commit)."""
        req = self.slots[slot]
        if self.active[slot] and req.sampling.temperature > 0.0:
            self._n_sampled -= 1
        self.slots[slot] = None
        self.active[slot] = False
        (self._temps, self._top_ks, self._top_ps, self._spec_len) = \
            self._clear_meta(self._temps, self._top_ks, self._top_ps,
                             self._spec_len, slot)
        self._spec_win[slot] = 1
        self._spec_win_dev[slot] = 1
        if self._spec_ctl is not None:
            self._spec_ctl.evict(slot)

    def _trace_request(self, req: Request) -> None:
        """Emit the request's lifecycle spans from its own recorded
        timestamps at its terminal transition: queue_wait
        [t_submit, t_dequeued], prefill [t_dequeued, t_first_token] (host
        glue, the device work and the wait for the first token), decode
        [t_first_token, t_finished], plus a `retire` instant. TTFT is
        queue_wait + prefill and end-to-end latency is the full span chain
        — the trace reconstructs the measured numbers exactly, by
        construction. Stages a request never reached (errored while queued
        or prefilling) are simply absent."""
        tr = self.tracer
        if not tr.enabled or math.isnan(req.t_submit):
            return
        rid, t_end = req.rid, req.t_finished
        if math.isnan(req.t_dequeued):
            tr.complete("queue_wait", req.t_submit, t_end, rid=rid)
        else:
            tr.complete("queue_wait", req.t_submit, req.t_dequeued, rid=rid)
            t_first = req.t_first_token
            if math.isnan(t_first):
                tr.complete("prefill", req.t_dequeued, t_end, rid=rid)
            else:
                tr.complete("prefill", req.t_dequeued, t_first, rid=rid)
                tr.complete("decode", t_first, t_end, rid=rid,
                            tokens=len(req.tokens))
        tr.instant("retire", rid=rid, ts=t_end, reason=req.finish_reason,
                   status=req.status)

    def _evict(self, slot: int, reason: str) -> None:
        req = self.slots[slot]
        req.status = FINISHED
        req.finish_reason = reason
        req.t_finished = self._clock()
        req.slot = -1
        self._release_slot(slot)
        self._bump_stat("evicted")
        self.finished.append(req)
        self._c_finished.inc()
        if not math.isnan(req.t_submit):
            self._h_latency.observe(req.latency)
            if not math.isnan(req.t_first_token):
                self._h_ttft.observe(req.ttft)
        self._trace_request(req)
        if self.reset_on_evict:
            self.cache = self._reset_slot(self.cache, slot)
            if self._spec and not self._draft_shared:
                self.draft_cache = self._reset_slot_d(self.draft_cache, slot)

    # ------------------------------------------------------------------
    # resilience: quarantine / recovery / degradation
    # ------------------------------------------------------------------
    def _finish_error(self, req: Request, reason: str) -> None:
        """Complete a request with ERROR status from any lifecycle stage
        (queued, prefilling, or running on a slot)."""
        try:
            self.queue.remove(req)
        except ValueError:
            pass
        if 0 <= req.slot < self.n_slots and self.slots[req.slot] is req:
            self._release_slot(req.slot)
            self._bump_stat("evicted")
        req.status = ERROR
        req.finish_reason = reason
        req.t_finished = self._clock()
        req.slot = -1
        self.finished.append(req)
        self._c_errors.inc()
        self._trace_request(req)

    def _requeue_for_recovery(self, req: Request) -> None:
        """Put a (slot-released) request at the FRONT of the queue for exact
        re-prefill from prompt + committed tokens, with linear backoff."""
        req.status = QUEUED
        req.slot = -1
        req.retry_at = self._tick + self._retry_backoff * req.retries
        self.queue.appendleft(req)

    def _quarantine(self, slot: int, req: Request) -> None:
        """A guard flagged this slot: zero the poisoned row, release it, and
        either re-prefill the request exactly from its committed tokens
        (bounded retries with backoff) or — past max_retries — complete it
        with ERROR status. Repeated quarantines demote the request to plain
        decode, and (opt-in) repeated corruption demotes the whole engine
        one rung down the MODE_LADDER (distilled -> cached_conv -> epoch)."""
        self.resilience.bump("health_failures")
        req.retries += 1
        self._record_event("quarantine", rid=req.rid, slot=slot,
                           retries=req.retries)
        self._release_slot(slot)
        self.cache = self._reset_slot(self.cache, slot)
        if self._spec and not self._draft_shared:
            self.draft_cache = self._reset_slot_d(self.draft_cache, slot)
        if self.mode in ("distilled", "cached_conv"):
            self._distilled_faults += 1      # faults since the last demotion
        if req.retries > self.max_retries:
            self.resilience.bump("poisoned")
            self._record_event("poisoned", rid=req.rid)
            self._finish_error(req, "poisoned")
        else:
            if req.spec and req.retries >= self._demote_spec_after:
                req.spec = False
                self.resilience.bump("spec_demotions")
                self._record_event("spec_demotion", rid=req.rid)
            self.resilience.bump("slot_reprefills")
            self._requeue_for_recovery(req)
        if (self._demote_engine_after is not None
                and self.mode in ("distilled", "cached_conv")
                and self._distilled_faults >= self._demote_engine_after):
            nxt = MODE_LADDER[MODE_LADDER.index(self.mode) + 1]
            self._demote_engine(nxt)

    def _rebuild_pool(self) -> None:
        """A dispatch raised mid-flight: the jitted step donates the pool
        buffers, so the old cache may be invalid. Re-initialize the pool(s)
        and recover every resident request from its committed tokens; an
        in-flight chunked prefill restarts from scratch (its request has no
        committed tokens yet)."""
        self.cache, self._cache_sh = self._make_pool(self.cfg,
                                                     self._cache_kind)
        if self.draft_cache is not None:
            self.draft_cache, self._draft_sh = self._make_pool(
                self._draft_cfg, "native")
        self._pending = None
        if self._chunk_state is not None:
            req = self._chunk_state["req"]
            slot = self._chunk_state["slot"]
            self._chunk_state = None
            self.slots[slot] = None
            req.status = QUEUED
            req.slot = -1
            self.queue.appendleft(req)
        for b in range(self.n_slots):
            req = self.slots[b]
            if req is None:
                continue
            req.retries += 1
            self._release_slot(b)
            if req.retries > self.max_retries:
                self.resilience.bump("poisoned")
                self._finish_error(req, "poisoned")
            else:
                self.resilience.bump("slot_reprefills")
                self._requeue_for_recovery(req)
        self._record_event("pool_rebuild")

    def _demote_to_conv(self) -> None:
        self._demote_engine("cached_conv")

    def _demote_engine(self, target: str) -> None:
        """Engine-wide graceful degradation down the MODE_LADDER: repeated
        corruption walks one rung (distilled -> cached_conv -> epoch), a
        drift alarm jumps straight to "epoch" (the FutureFill path serves
        the TRUE filter exactly at amortized near-linear cost, so there is
        no distillation error left to drift). Residents are recovered
        through the normal re-prefill path — through the exact path, for a
        drift demotion; speculation is disabled (the shared-state draft
        read the distilled cache). A one-time recompile of prefill/decode
        for the new kind is the accepted cost of the fallback."""
        if self.cfg.hyena is None or target not in MODE_LADDER:
            return
        if MODE_LADDER.index(target) <= MODE_LADDER.index(self.mode):
            return                             # demotions only walk down
        # drop (don't retire) the in-flight tick: its tokens are uncommitted
        # and every resident is about to re-prefill from committed tokens —
        # retiring here could recursively re-trigger demotion
        self._pending = None
        if self._chunk_state is not None:
            req = self._chunk_state["req"]
            slot = self._chunk_state["slot"]
            self._chunk_state = None
            self.slots[slot] = None
            req.status = QUEUED
            req.slot = -1
            self.queue.appendleft(req)
        for b in range(self.n_slots):
            req = self.slots[b]
            if req is not None:
                self._release_slot(b)
                self.resilience.bump("slot_reprefills")
                self._requeue_for_recovery(req)
        self.mode = target
        kind = _MODE_KINDS[target]
        self._cache_kind = kind
        self.cache, self._cache_sh = self._make_pool(self.cfg, kind)
        self._conv_filters = self._replicate(
            materialize_conv_filters(self.params, self.cfg, self.max_len))
        self._chunk_filters = self._conv_filters
        # the new pool has a different tree structure (and shardings), so
        # every pool-pinned executable is rebuilt for the new cache kind
        self._build_pool_ops()
        self._spec = False
        self._spec_ctl = None
        self.draft_cache = None
        self._state_bound = float("inf")   # exact kinds: finiteness only
        self._distilled_faults = 0
        self._sentinel = False         # only the distilled path can drift
        self.resilience.bump("engine_demotions")
        self._record_event("engine_demotion", to=target)


# ---------------------------------------------------------------------------
# Request-stream workload: Poisson arrivals, mixed prompt lengths.
# ---------------------------------------------------------------------------
def synthesize_request_stream(rng: np.random.Generator, n_requests: int, *,
                              rate: float, prompt_lens: Sequence[int],
                              gen_tokens: Tuple[int, int], vocab: int,
                              sampling: SamplingParams = GREEDY,
                              eos_id: Optional[int] = None
                              ) -> List[Tuple[float, Request]]:
    """(arrival_time_s, Request) pairs: exponential inter-arrival gaps at
    `rate` req/s, prompt lengths drawn from `prompt_lens`, generation lengths
    uniform over [gen_tokens[0], gen_tokens[1]]."""
    t = 0.0
    out = []
    for rid in range(n_requests):
        t += float(rng.exponential(1.0 / rate))
        plen = int(rng.choice(np.asarray(prompt_lens)))
        n_gen = int(rng.integers(gen_tokens[0], gen_tokens[1] + 1))
        prompt = rng.integers(0, vocab, size=plen).astype(np.int32)
        out.append((t, Request(rid=rid, prompt=prompt, max_new_tokens=n_gen,
                               sampling=sampling, eos_id=eos_id)))
    return out


def run_request_stream(engine: ContinuousBatchingEngine,
                       stream: Sequence[Tuple[float, Request]],
                       *, clock: Callable[[], float] = time.monotonic
                       ) -> Dict[str, float]:
    """Replay a timed request stream through the engine and report
    tokens/s plus p50/p99 end-to-end and first-token latency."""
    pending = sorted(stream, key=lambda p: p[0])
    t0 = clock()
    i = 0
    while i < len(pending) or engine.has_work:
        now = clock() - t0
        while i < len(pending) and pending[i][0] <= now:
            engine.submit_request(pending[i][1])
            i += 1
        if engine.has_work:
            engine.step()
        elif i < len(pending):
            time.sleep(min(1e-3, max(0.0, pending[i][0] - (clock() - t0))))
    wall = clock() - t0
    done = engine.finished
    # latency percentiles over successful requests only: an error-status
    # completion (rejected / deadline / poisoned) may never have produced a
    # first token and would poison the percentiles with NaN
    ok = [r for r in done if r.ok]
    n_tokens = int(sum(len(r.tokens) for r in done))
    decode_wall = max(wall - engine.t_admit, 1e-9)

    def pcts(hist_name: str, values: List[float]) -> Tuple[float, float]:
        # one source of truth with the live exposition: the engine's
        # registry histogram (what /metrics serves) when it saw these
        # completions; exact numpy over the request list otherwise (registry
        # disabled). Histogram percentiles are bucket-interpolated
        # estimates, clamped to the observed min/max and monotone in q.
        h = engine.metrics.get(hist_name)
        if h is not None and h.count >= len(values) > 0:
            return h.percentile(50), h.percentile(99)
        if not values:
            return math.nan, math.nan
        arr = np.asarray(values)
        return float(np.percentile(arr, 50)), float(np.percentile(arr, 99))

    p50_lat, p99_lat = pcts("serve_request_latency_s",
                            [r.latency for r in ok])
    p50_ttft, p99_ttft = pcts("serve_ttft_s",
                              [r.ttft for r in ok
                               if not math.isnan(r.t_first_token)])
    return {
        "n_requests": len(done),
        "n_ok": len(ok),
        "n_errors": len(done) - len(ok),
        "n_tokens": n_tokens,
        "wall_s": wall,
        "tok_per_s": n_tokens / wall if wall > 0 else float("inf"),
        "decode_tok_per_s": n_tokens / decode_wall,
        "p50_latency_s": p50_lat,
        "p99_latency_s": p99_lat,
        "p50_ttft_s": p50_ttft,
        "p99_ttft_s": p99_ttft,
        "resilience": engine.resilience.snapshot(),
    }


def measure_saturated_decode(engine: ContinuousBatchingEngine, *,
                             prompt_len: int = 32,
                             target_tokens: Optional[int] = None,
                             warmup_ticks: int = 4,
                             max_ticks: int = 10_000,
                             seed: int = 0,
                             clock: Callable[[], float] = time.monotonic
                             ) -> Dict[str, Any]:
    """Steady-state decode throughput with every slot busy.

    The stream benchmark's decode_tok_per_s is arrival-diluted (slots idle
    between Poisson arrivals), which both understates throughput and adds
    enough noise to drown a 30% speculation win. This fills all n_slots with
    long greedy requests, burns `warmup_ticks` to get past compile/admission
    transients, then times pure decode ticks until `target_tokens` have been
    emitted (default 48 per slot). Probes get all the decode headroom
    max_len allows; when that is short (small-max_len engines), warmup and
    target shrink to fit so the window still measures real ticks instead of
    breaking empty on a probe that finished during warmup.

    Returns decode_tok_per_s plus the window's speculation deltas:
    acceptance (None when nothing was drafted) and tokens_per_slot_round.
    """
    rng = np.random.default_rng(seed)
    n_slots = engine.n_slots
    headroom = engine.max_len - prompt_len - 1
    if headroom < 2:
        raise ValueError("prompt_len leaves no decode headroom")
    # the earliest-admitted probe decodes through the other slots' admission
    # ticks and the warmup ticks before the window opens; each tick commits
    # at most spec_k+1 tokens
    burst = (engine._spec_k + 1) if engine._spec else 1
    while warmup_ticks > 1 and \
            headroom - (n_slots - 1 + warmup_ticks) * burst < 4 * burst:
        warmup_ticks -= 1
    avail = headroom - (n_slots - 1 + warmup_ticks) * burst
    if target_tokens is None:
        target_tokens = 48 * n_slots
    if avail > 0:
        target_tokens = min(target_tokens, n_slots * avail)
    probes = []
    for rid in range(n_slots):
        prompt = rng.integers(0, engine.cfg.vocab, size=prompt_len)
        probes.append(Request(
            rid=10_000_000 + rid, prompt=prompt.astype(np.int32),
            max_new_tokens=headroom, sampling=GREEDY))
        engine.submit_request(probes[-1])
    # drain admission (prefill ticks) until all slots are decoding
    ticks = 0
    while int(engine.active.sum()) < n_slots:
        if not engine.has_work or ticks >= max_ticks:
            raise RuntimeError("saturation fill failed")
        engine.step()
        ticks += 1
    for _ in range(warmup_ticks):
        engine.step()
    # count via the probe Request objects: their token lists survive
    # eviction, so a probe hitting max_tokens mid-window still contributes
    base = int(sum(len(r.tokens) for r in probes))
    s0 = dict(engine.stats)
    jax.block_until_ready(engine._last)
    t0 = clock()
    ticks = 0
    tokens = 0
    while tokens < target_tokens and ticks < max_ticks:
        engine.step()
        ticks += 1
        tokens = int(sum(len(r.tokens) for r in probes)) - base
        if int(engine.active.sum()) < n_slots:
            break                               # a probe hit max_tokens
    jax.block_until_ready(engine._last)
    wall = max(clock() - t0, 1e-9)
    drafted = engine.stats["spec_drafted"] - s0.get("spec_drafted", 0)
    accepted = engine.stats["spec_accepted"] - s0.get("spec_accepted", 0)
    rounds = (engine.stats.get("spec_slot_rounds", 0)
              - s0.get("spec_slot_rounds", 0))
    # flush: finish the oversized probe requests so the engine is reusable;
    # the in-flight overlapped tick (if any) only carries tokens for the
    # now-evicted probes, so its pending record is dropped too
    for slot in range(n_slots):
        if engine.slots[slot] is not None:
            engine._evict(slot, "probe_done")
    engine._pending = None
    return {
        "decode_tok_per_s": tokens / wall,
        "tokens": tokens,
        "ticks": ticks,
        "acceptance": (accepted / drafted) if drafted > 0 else None,
        "tokens_per_slot_round": (tokens / rounds) if rounds > 0 else None,
    }
