"""Serving launcher: batched auto-regressive generation and the
continuous-batching request-stream mode.

Fixed-batch generation (original behavior):

  PYTHONPATH=src python -m repro.launch.serve --arch multihyena-153m --smoke \
      --batch 8 --prompt-len 64 --gen 32 [--ckpt /tmp/run1] [--distill]

Request-stream serving (Poisson arrivals, mixed prompt lengths, slot-pool
continuous batching; reports tokens/s and p50/p99 latency):

  PYTHONPATH=src python -m repro.launch.serve --arch multihyena-153m --smoke \
      --distill --stream --n-requests 16 --rate 20 --slots 4 \
      --mode distilled            # or cached_conv / epoch (exact FFT path)

The distilled path can be guarded by the online drift sentinel
(--drift-check-every N [--drift-tol T]): every N ticks one resident slot's
next token is re-derived through the exact epoched-FFT path and compared;
divergence beyond the tolerance demotes the engine to the epoch mode.

Serving fast path (all on by default in --stream mode): prompt-length
bucketing (one batched prefill executable per power-of-two bucket), the
async overlapped tick loop, and optional chunked prefill for long prompts
(--chunk N). --no-bucket / --sync-loop restore the legacy per-length,
fully-synchronous engine for comparison.

For LCSM archs, --distill runs LaughingHyena distillation before serving
(recurrent O(d) decode); without it the model still serves via the distilled
slot's random init (useless outputs) — so in practice always pass --distill
or a --ckpt of a trained+distilled model.

Observability (serve/README.md "Observability"): --metrics-port N serves the
engine's live metrics registry over HTTP while the stream runs (/metrics
Prometheus text, /metrics.json snapshot, /trace.json live trace);
--trace-out FILE records host-phase + request-lifecycle spans and writes a
Chrome-trace JSON to open in Perfetto; --events-limit bounds the recovery-
event ring.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core.distill import distill_model
from repro.distributed.sharding import unzip
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import init_params
from repro.serve.engine import GenerationEngine
from repro.serve.scheduler import (ContinuousBatchingEngine, SamplingParams,
                                   run_request_stream,
                                   synthesize_request_stream)
from repro.train.checkpoint import Checkpointer


def _spec_k_arg(v: str):
    return v if v == "auto" else int(v)


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--ckpt", type=str, default=None)
    ap.add_argument("--distill", action="store_true")
    ap.add_argument("--distill-order", type=int, default=None,
                    help="default: cfg.hyena.distill_order (the order the "
                         "decode cache is sized for)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mode", choices=("distilled", "cached_conv", "epoch"),
                    default="distilled")
    # request-stream serving
    ap.add_argument("--stream", action="store_true",
                    help="continuous-batching request-stream mode")
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=20.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-lens", type=str, default=None,
                    help="comma list of prompt lengths (default: "
                         "prompt-len/2,prompt-len)")
    ap.add_argument("--max-len", type=int, default=None,
                    help="slot capacity in tokens (default: longest prompt "
                         "+ --gen)")
    # serving fast path
    ap.add_argument("--no-bucket", action="store_true",
                    help="disable prompt-length bucketing (compile one "
                         "prefill executable per distinct length)")
    ap.add_argument("--chunk", type=int, default=None,
                    help="chunked prefill: prompts longer than this run "
                         "through the resumable chunk executable, one chunk "
                         "per tick")
    ap.add_argument("--sync-loop", action="store_true",
                    help="disable the async overlapped host loop")
    ap.add_argument("--prefills-per-step", type=int, default=2,
                    help="max admissions per tick == bucketed prefill batch")
    # self-speculative decoding (serve/speculative.py)
    ap.add_argument("--spec-k", type=_spec_k_arg, default=0,
                    help="speculative decoding: draft this many tokens per "
                         "slot per tick with the low-order modal truncation "
                         "of the serving SSM and verify them in one "
                         "multi-token step (0 disables). 'auto' runs the "
                         "construction-time autotune sweep and adopts the "
                         "measured winner (or disables speculation)")
    ap.add_argument("--draft-order", type=int, default=None,
                    help="real state dim of the draft's modal truncation "
                         "(default: half the serving distill order)")
    ap.add_argument("--spec-branch", type=int, default=1,
                    help="top-k tree drafts: draft this many chains per "
                         "slot (branching once at depth 0) and verify them "
                         "all in one call (1 = single chain)")
    # resilience (serve/README.md "Failure handling")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="per-request end-to-end deadline; expired requests "
                         "finish with ERROR status instead of queueing "
                         "forever")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded-queue admission control: submissions past "
                         "this queue depth are rejected with ERROR status")
    ap.add_argument("--fault-schedule", type=str, default=None,
                    help="JSON fault schedule (file path or inline) driving "
                         "a seeded serve/faults.FaultInjector: corrupt slot "
                         "state, raise in dispatch, stall the loop, expire "
                         "deadlines")
    ap.add_argument("--drift-check-every", type=int, default=0,
                    help="distillation-drift sentinel: every N ticks, "
                         "re-decode one resident slot's next token through "
                         "the exact epoched-FFT path and record the "
                         "log-softmax divergence vs the distilled engine "
                         "(0 disables; distilled mode only)")
    ap.add_argument("--drift-tol", type=float, default=None,
                    help="sentinel alarm threshold: divergence above this "
                         "demotes the engine to the exact epoch path")
    ap.add_argument("--restore", type=str, default=None,
                    help="resume from an engine checkpoint written by "
                         "serve.checkpoint.save_engine (bit-exact for "
                         "resident slots)")
    # observability (serve/README.md "Observability")
    ap.add_argument("--metrics-port", type=int, default=None,
                    help="serve the engine's metrics registry over HTTP on "
                         "this port while the stream runs (/metrics "
                         "Prometheus text, /metrics.json snapshot, "
                         "/trace.json live Chrome trace; 0 picks a free "
                         "port)")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="record request-lifecycle + host-phase spans and "
                         "write a Chrome-trace JSON here at the end (open "
                         "in https://ui.perfetto.dev)")
    ap.add_argument("--events-limit", type=int, default=256,
                    help="ring-buffer capacity of the recovery-event log "
                         "(0 = unbounded)")
    return ap


def load_model(args):
    """Config + params (seeded init, optional checkpoint restore, optional
    distillation). Returns (cfg, params)."""
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    key = jax.random.PRNGKey(args.seed)
    params, _ = unzip(init_params(key, cfg))
    if args.ckpt:
        ck = Checkpointer(args.ckpt)
        (params, _), step = ck.restore((params, None))
        print(f"[serve] restored step {step}")
    if args.distill and cfg.hyena is not None:
        t0 = time.time()
        order = args.distill_order or cfg.hyena.distill_order
        params, errs = distill_model(params, cfg, d=order)
        worst = max(float(jnp.max(e)) for e in errs.values())
        print(f"[serve] distilled {cfg.n_layers} layers (d_model "
              f"{cfg.d_model}) to order {order} in {time.time()-t0:.1f}s "
              f"(worst rel l2 err {worst:.3e})")
    return cfg, params


def main():
    args = build_parser().parse_args()
    enable_compile_cache()
    cfg, params = load_model(args)
    if args.stream:
        _, m = serve_stream(params, cfg, args)
        problems = stream_problems(m, args)
        if problems:
            raise SystemExit(f"[serve] FAILED: {'; '.join(problems)}")
        return

    key = jax.random.PRNGKey(args.seed)
    engine = GenerationEngine(params, cfg,
                              max_len=args.prompt_len + args.gen,
                              mode=args.mode)
    prompt = jax.random.randint(key, (args.batch, args.prompt_len), 0, cfg.vocab)
    t0 = time.time()
    toks, info = engine.generate(key, prompt, args.gen,
                                 temperature=args.temperature,
                                 top_k=args.top_k, top_p=args.top_p)
    jax.block_until_ready(toks)
    dt = time.time() - t0
    print(f"[serve] generated {toks.shape} in {dt:.2f}s "
          f"({args.batch*args.gen/dt:.1f} tok/s), cache={info['cache_bytes']/1e6:.2f}MB")
    print(toks[0][:16])


def stream_problems(m, args):
    """Why a served stream counts as failed: any ERROR completion or any
    dispatch fault, unless a fault schedule injected them on purpose."""
    if args.fault_schedule:
        return []
    out = []
    if m["n_errors"]:
        out.append(f"{m['n_errors']} of {m['n_requests']} requests ended in "
                   f"ERROR")
    if m["resilience"].get("dispatch_faults"):
        out.append(f"{m['resilience']['dispatch_faults']} dispatch faults")
    return out


def serve_stream(params, cfg, args, *, mesh=None):
    """Continuous-batching request stream: build the engine (its slot pool
    sharded over `mesh` when given), warm it up, replay a seeded Poisson
    stream and print the report. Returns (engine, metrics); the metrics add
    `warmup_s` and `stream_compiles` (XLA compiles inside the replayed
    stream) to run_request_stream's."""
    from repro.serve.metrics import count_compiles
    if args.prompt_lens:
        plens = tuple(int(x) for x in args.prompt_lens.split(","))
    else:
        plens = (max(args.prompt_len // 2, 4), args.prompt_len)
    max_len = args.max_len or max(plens) + args.gen
    injector = None
    if args.fault_schedule:
        from repro.serve.faults import FaultInjector
        injector = FaultInjector.from_json(args.fault_schedule)
        print(f"[serve] fault schedule: {len(injector.events)} events "
              f"(seed {injector.seed})")
    tracer = None
    if args.trace_out:
        from repro.serve.trace import Tracer
        tracer = Tracer()
    eng = ContinuousBatchingEngine(params, cfg, n_slots=args.slots,
                                   max_len=max_len, mode=args.mode,
                                   seed=args.seed, mesh=mesh,
                                   bucket_prompts=not args.no_bucket,
                                   prefill_chunk=args.chunk,
                                   overlap=not args.sync_loop,
                                   max_prefills_per_step=args.prefills_per_step,
                                   spec_k=args.spec_k,
                                   draft_order=args.draft_order,
                                   spec_branch=args.spec_branch,
                                   deadline_s=(args.deadline_ms / 1e3
                                               if args.deadline_ms else None),
                                   max_queue=args.max_queue,
                                   fault_injector=injector,
                                   tracer=tracer,
                                   events_limit=args.events_limit or None,
                                   drift_check_every=args.drift_check_every,
                                   drift_tol=args.drift_tol)
    server = None
    if args.metrics_port is not None:
        from repro.serve.metrics import start_metrics_server
        server = start_metrics_server(
            eng.metrics, args.metrics_port, tracer=eng.tracer,
            extra=lambda: {"stats": dict(eng.stats),
                           "resilience": eng.resilience.snapshot(),
                           "tick": eng._tick})
        print(f"[serve] metrics endpoint: "
              f"http://{server.server_address[0]}:{server.server_address[1]}"
              f"/metrics (also /metrics.json, /trace.json)")
    if args.restore:
        from repro.serve.checkpoint import restore_engine
        restore_engine(eng, args.restore)
        print(f"[serve] restored engine checkpoint {args.restore} "
              f"(tick {eng._tick}, {eng.n_active} resident slots, "
              f"{len(eng.queue)} queued)")
    if eng.spec_report is not None:
        print(f"[serve] autotune sweep (spec_k=auto):\n"
              f"{eng.spec_report.pretty()}")
    spec_desc = (f", spec_k={eng._spec_k}" if eng._spec else "")
    print(f"[serve] warming up prompt lengths {plens} "
          f"({'bucketed' if not args.no_bucket else 'exact-length'} prefill"
          f"{', chunk=%d' % args.chunk if args.chunk else ''}, "
          f"{'overlapped' if not args.sync_loop else 'sync'} loop"
          f"{spec_desc}) ...")
    t0 = time.time()
    eng.warmup(plens)
    warmup_s = time.time() - t0
    print(f"[serve] warmup compiled in {warmup_s:.1f}s")
    sampling = SamplingParams(temperature=args.temperature, top_k=args.top_k,
                              top_p=args.top_p)
    stream = synthesize_request_stream(
        np.random.default_rng(args.seed), args.n_requests, rate=args.rate,
        prompt_lens=plens, gen_tokens=(max(args.gen // 2, 1), args.gen),
        vocab=cfg.vocab, sampling=sampling)
    with count_compiles() as scope:
        m = run_request_stream(eng, stream)
    m["warmup_s"] = warmup_s
    m["stream_compiles"] = scope.compiles
    print(f"[serve] mode={args.mode} slots={args.slots} "
          f"{int(m['n_requests'])} requests / {int(m['n_tokens'])} tokens "
          f"in {m['wall_s']:.2f}s")
    print(f"[serve] tok/s={m['tok_per_s']:.1f} "
          f"decode_tok/s={m['decode_tok_per_s']:.1f}  "
          f"latency p50={m['p50_latency_s']*1e3:.1f}ms "
          f"p99={m['p99_latency_s']*1e3:.1f}ms  "
          f"ttft p50={m['p50_ttft_s']*1e3:.1f}ms "
          f"p99={m['p99_ttft_s']*1e3:.1f}ms")
    if eng._spec:
        from repro.serve.metrics import speculative_summary
        s = speculative_summary(eng.stats)
        acc = s["acceptance_rate"]
        tpr = s["tokens_per_slot_round"]
        print(f"[serve] speculative: "
              f"acceptance={acc if acc is not None else float('nan'):.2f} "
              f"tokens/slot-round="
              f"{tpr if tpr is not None else float('nan'):.2f} "
              f"(draft order {eng.draft_order}, K={eng._spec_k}, "
              f"branch={eng._spec_branch})")
    if eng.resilience.get("drift_checks"):
        h = eng.metrics.get("serve_drift_logit_div")
        print(f"[serve] drift sentinel: "
              f"{eng.resilience.get('drift_checks')} checks, "
              f"{eng.resilience.get('drift_alarms')} alarms, "
              f"last divergence "
              f"{eng._drift_last if eng._drift_last is not None else float('nan'):.3e} "
              f"(max {h._max:.3e}, tol "
              f"{args.drift_tol if args.drift_tol is not None else 'off'}), "
              f"final mode {eng.mode}")
    print(f"[serve] scheduler stats: {eng.stats}")
    print(f"[serve] prefill compile stats: {eng.prefill_compile_stats()}; "
          f"{m['stream_compiles']} compiles inside the stream")
    res = {k: v for k, v in m["resilience"].items() if v}
    if res or m["n_errors"]:
        print(f"[serve] resilience: {m['n_errors']} error completions, "
              f"counters {res}")
    if eng.events:
        dropped = eng._events_total - len(eng.events)
        print(f"[serve] recovery events ({len(eng.events)} of "
              f"{eng._events_total} retained):" if dropped
              else f"[serve] recovery events ({len(eng.events)}):")
        for ev in eng.events:
            detail = {k: v for k, v in ev.items()
                      if k not in ("tick", "kind")}
            print(f"  tick {ev['tick']:>5}  {ev['kind']:<16} {detail}")
    if tracer is not None:
        tracer.save(args.trace_out)
        print(f"[serve] wrote trace ({len(tracer)} events, "
              f"{tracer.dropped} dropped) to {args.trace_out} — open in "
              f"https://ui.perfetto.dev")
    if server is not None:
        server.shutdown()
    return eng, m


if __name__ == "__main__":
    main()
