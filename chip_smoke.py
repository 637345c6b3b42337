#!/usr/bin/env python3
"""Bring-up smoke of the distilled serving path on a TPU.

  python3 chip_smoke.py [--seed N]        # one chip
  python3 chip_smoke.py --four-chips      # sharded slot pool on 4 chips

Runs the path that `python -m repro.launch.serve --stream --distill --mode
distilled` runs, through the same functions, at the full published width of
MultiHyena-153M (18 layers, d_model 864, 8 filter heads, vocab 50304, bf16
compute) with random weights from --seed:

  device  jax.devices() must be TPUs (anything else exits non-zero);
  model   build the registry config, distill every layer (distill_model
          with its default arguments), print set-up time and worst error;
  kernel  the compiled Pallas decode kernel (interpret=False) against the
          jnp reference at the served state shape;
  serve   8-slot, max_len-1024 continuous-batching engine: warmup, then a
          stream of 8 greedy requests (prompts 128..512, 32..64 new tokens);
          every request ok, no dispatch fault, no compile inside the stream,
          and the decode executable holds the Pallas kernel;
  check   one request's tokens equal GenerationEngine.generate's, and the
          prefill logits are finite.

--four-chips instead distills the same model and serves the same stream
twice in float32 (with float32 matmuls), on one device and on a 4-way slot
mesh (make_slot_mesh(4)), and requires identical greedy tokens with no
compile inside either stream. No other phase runs.

Every failure exits non-zero. Only a run in which every phase passed prints
its last line, one JSON object:
  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

ARCH = "multihyena-153m"
N_SLOTS, MAX_LEN, N_REQUESTS = 8, 1024, 8
PROMPT_LENS = (128, 256, 384, 512)
GEN = 64                      # new tokens drawn uniformly from GEN/2..GEN
FLOAT32 = "highest"           # matmul precision of the float32 identity runs


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    log(f"ok: {what}")


def serve_args(seed: int, extra=()):
    from repro.launch import serve
    return serve.build_parser().parse_args([
        "--arch", ARCH, "--distill", "--stream", "--mode", "distilled",
        "--slots", str(N_SLOTS), "--max-len", str(MAX_LEN),
        "--n-requests", str(N_REQUESTS), "--rate", "1000",
        "--prompt-lens", ",".join(map(str, PROMPT_LENS)), "--gen", str(GEN),
        "--seed", str(seed), *extra])


def phase_device(min_count: int) -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    log(f"devices {devs}: platform={info['platform']} "
        f"kind={info['kind']} count={info['count']}")
    check(d.platform == "tpu", f"platform is tpu (got {d.platform})")
    check(len(devs) >= min_count, f"at least {min_count} device(s)")
    return info


def phase_model(args):
    from repro.launch import serve
    t0 = time.time()
    cfg, params = serve.load_model(args)
    log(f"model {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.hyena.n_filter_heads} filter heads, vocab {cfg.vocab}, "
        f"{cfg.dtype}; set-up incl. distillation {time.time() - t0:.1f}s")
    check((cfg.n_layers, cfg.d_model, cfg.vocab) == (18, 864, 50304),
          "full published width")
    return cfg, params


def phase_kernel(cfg) -> None:
    """Compiled Pallas decode vs the jnp reference (f32 matmuls) on random
    state at the served pool shape."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.ssm_decode.ref import ssm_decode_ref
    from repro.kernels.ssm_decode.ssm_decode import ssm_decode_pallas
    B, C, d = N_SLOTS, cfg.d_model, cfg.hyena.distill_order // 2
    ks = jax.random.split(jax.random.PRNGKey(0), 8)
    args = (jax.random.normal(ks[0], (B, C, d)),
            jax.random.normal(ks[1], (B, C, d)),
            jax.random.normal(ks[2], (B, C)),
            jnp.log(jax.random.uniform(ks[3], (C, d), minval=0.5,
                                       maxval=0.99)),
            jax.random.uniform(ks[4], (C, d), maxval=np.pi),
            jax.random.normal(ks[5], (C, d)),
            jax.random.normal(ks[6], (C, d)),
            jax.random.normal(ks[7], (C,)))
    out = ssm_decode_pallas(*args, interpret=False)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(ssm_decode_ref)(*args)
    errs = [float(np.max(np.abs(np.asarray(o) - np.asarray(r))))
            for o, r in zip(out, ref)]
    log(f"pallas ssm_decode (B={B}, C={C}, d={d}) max |kernel - ref| "
        f"for y, x_re', x_im': {errs}")
    for name, o, r in zip(("y", "x_re'", "x_im'"), out, ref):
        check(np.allclose(np.asarray(o), np.asarray(r), rtol=1e-5, atol=1e-5),
              f"pallas decode {name} allclose to the reference")


def phase_serve(params, cfg, args, mesh=None):
    from repro.launch import serve
    eng, m = serve.serve_stream(params, cfg, args, mesh=mesh)
    where = "one device" if mesh is None else f"{eng._n_shards}-way mesh"
    log(f"[{where}] warmup {m['warmup_s']:.1f}s; {m['n_ok']}/"
        f"{m['n_requests']} requests ok, {m['n_tokens']} tokens in "
        f"{m['wall_s']:.2f}s; dispatch_faults="
        f"{m['resilience']['dispatch_faults']}; compiles in stream="
        f"{m['stream_compiles']}")
    check(m["n_requests"] == N_REQUESTS and m["n_ok"] == N_REQUESTS,
          f"[{where}] all {N_REQUESTS} requests ok")
    check(not serve.stream_problems(m, args),
          f"[{where}] no ERROR and no dispatch fault")
    check(m["stream_compiles"] == 0, f"[{where}] 0 compiles in the stream")
    return eng


def phase_decode_has_kernel(eng) -> None:
    text = eng._decode_g.lower(eng.params, eng.cache, eng._last[:, None],
                               eng._state_bound, conv_filters=None).as_text()
    check("tpu_custom_call" in text,
          "served decode executable calls the Pallas kernel")


def phase_identity(eng, params, cfg) -> None:
    """The engine's greedy tokens for one prompt must equal
    GenerationEngine.generate's. Exact identity is a float32 invariant (the
    repo's tests hold it there): in bf16 the pooled and the single-request
    paths round differently, and random weights put near-ties in the logits.
    So the served bf16 run only reports how long its prefix agrees, and a
    float32 copy of the same model serves the same stream for the check,
    under float32 matmuls (FLOAT32): at the TPU's default precision a
    float32 matmul takes one bf16 pass, and the 8-slot pool and the batch-1
    reference need not lower their matmuls alike."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.serve.engine import GenerationEngine, jitted_prefill
    from repro.serve.scheduler import ContinuousBatchingEngine
    req = min(eng.finished, key=lambda r: (r.prompt_len, r.rid))
    prompt = jnp.asarray(req.prompt)[None]
    _, logits = jitted_prefill(cfg, MAX_LEN)(params, prompt)
    check(bool(np.all(np.isfinite(np.asarray(logits, np.float32)))),
          "prefill logits finite")

    got = np.asarray(req.tokens)
    ref = GenerationEngine(params, cfg, max_len=MAX_LEN, mode="distilled")
    want = np.asarray(
        ref.generate(jax.random.PRNGKey(0), prompt, len(got))[0][0])
    agree = int(np.argmin(np.append(got == want, False)))
    log(f"{cfg.dtype}: request {req.rid} (prompt {req.prompt_len}) engine "
        f"and generate agree on the first {agree} of {len(got)} tokens")
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision(FLOAT32):
        eng32 = ContinuousBatchingEngine(params, cfg32, n_slots=N_SLOTS,
                                         max_len=MAX_LEN, mode="distilled",
                                         max_prefills_per_step=2)
        reqs = [eng32.submit(r.prompt, max_new_tokens=r.max_new_tokens)
                for r in sorted(eng.finished, key=lambda r: r.rid)]
        eng32.run()
        got = np.asarray(reqs[req.rid].tokens)
        ref = GenerationEngine(params, cfg32, max_len=MAX_LEN,
                               mode="distilled")
        want = np.asarray(
            ref.generate(jax.random.PRNGKey(0), prompt, len(got))[0][0])
        if not np.array_equal(got, want):
            log(f"float32: request {req.rid} engine {got.tolist()}")
            log(f"float32: request {req.rid} generate {want.tolist()}")
    check(np.array_equal(got, want),
          f"float32 engine greedy tokens == GenerationEngine.generate "
          f"({len(got)} tokens, {N_REQUESTS} requests in {N_SLOTS} slots)")


def run_one_chip(seed: int) -> dict:
    dev = phase_device(1)
    args = serve_args(seed)
    cfg, params = phase_model(args)
    phase_kernel(cfg)
    eng = phase_serve(params, cfg, args)
    phase_decode_has_kernel(eng)
    phase_identity(eng, params, cfg)
    return dev


def run_four_chips(seed: int) -> dict:
    """Same stream on one device and on a 4-way slot mesh, in float32: in
    bf16 a 2-slot shard and the 8-slot pool round differently, so only the
    float32 tokens are held to identity (as in phase_identity)."""
    import dataclasses
    import jax
    from repro.launch.mesh import make_slot_mesh
    dev = phase_device(4)
    check(dev["count"] == 4, "exactly 4 devices")
    args = serve_args(seed)
    cfg, params = phase_model(args)
    cfg32 = dataclasses.replace(cfg, dtype="float32")
    with jax.default_matmul_precision(FLOAT32):
        one = phase_serve(params, cfg32, args)
        four = phase_serve(params, cfg32, args, mesh=make_slot_mesh(4))
    check(four._n_shards == 4, "slot pool sharded 4 ways")
    want = {r.rid: list(r.tokens) for r in one.finished}
    got = {r.rid: list(r.tokens) for r in four.finished}
    check(got == want, f"float32 4-chip greedy tokens == 1-chip for all "
                       f"{len(want)} requests")
    return dev


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="sharded slot pool on 4 chips vs one device only")
    a = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    t0 = time.time()
    try:
        dev = run_four_chips(a.seed) if a.four_chips else run_one_chip(a.seed)
    except SmokeFailure as e:
        log(f"FAILED: {e}")
        return 1
    log(f"all phases passed in {time.time() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
