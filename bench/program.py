"""What the benchmark asks of the serving path, for every architecture.

The harness reaches the serving path only through these functions and an
architecture's module (`bench/archs/<arch>.py`, which builds the model
configuration and the parameter tree): the continuous-batching engine the
launcher's `--stream` mode drives (`submit_request` and `step()`), its
warmup, its counters and the program's compile counter. Nothing of the
yardstick (traffic, reference, counts, trace reduction) lives here.
"""
from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def _import_path() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def enable_compile_cache() -> str:
    """The program's persistent compile cache (its fixed directory inside
    the checkout, or $JAX_COMPILATION_CACHE_DIR), caching every program."""
    _import_path()
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def make_engine(params: dict, mcfg, mix: dict, seed: int):
    """The engine `launch/serve.py --stream --mode distilled` builds, with
    its defaults: bucketed prefill, the overlapped loop, the guard every 2
    ticks, no speculation, no chunking."""
    _import_path()
    from repro.serve.scheduler import ContinuousBatchingEngine
    return ContinuousBatchingEngine(
        params, mcfg, n_slots=mix["slots"], max_len=mix["max_len"],
        mode="distilled", seed=int(seed) % (2 ** 31),
        max_prefills_per_step=mix["prefills_per_step"])


def make_request(p):
    """An engine Request for a planned one (`traffic.Planned`)."""
    _import_path()
    from repro.serve.scheduler import Request, SamplingParams
    return Request(rid=p.idx, prompt=p.prompt, max_new_tokens=p.max_new_tokens,
                   sampling=SamplingParams(temperature=p.temperature,
                                           top_p=p.top_p))


def count_compiles():
    """Context manager counting XLA compiles; `.compiles` after exit."""
    _import_path()
    from repro.serve.metrics import count_compiles as counter
    return counter()


def is_finished(req) -> bool:
    return req.status == "finished"


def is_failed(req) -> bool:
    return req.status == "error"


def decode_steps(eng) -> int:
    """Pooled decode dispatches so far."""
    return int(eng.stats["decode_steps"])


def prefill_calls(eng) -> int:
    """Bucketed prefill dispatches so far."""
    return int(eng.stats["prefill_calls"])


def health(eng) -> dict:
    """The engine's own counters of faults and recoveries that are not 0."""
    return {k: v for k, v in eng.resilience.snapshot().items() if v}

