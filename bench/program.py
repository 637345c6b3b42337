"""Everything the benchmark asks of the system under test, in one place.

The harness reaches the serving path only through these functions: the
model configuration built from a config file, the continuous-batching
engine the launcher's `--stream` mode drives (`submit_request` and
`step()`), its warmup, and the program's compile counter. Nothing of the
yardstick (traffic, reference, counters, trace reduction) lives here.
"""
from __future__ import annotations

import sys
from pathlib import Path

SRC = Path(__file__).resolve().parents[1] / "src"


def _import_path() -> None:
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def model_config(cfg: dict):
    """The program's ModelConfig for a MultiHyena config file."""
    _import_path()
    from repro.configs.base import HYENA, HyenaConfig, ModelConfig
    M = cfg["n_filter_heads"]
    return ModelConfig(
        name=cfg["name"], family="lcsm", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=M, n_kv_heads=M,
        head_dim=cfg["d_model"] // M, d_ff=cfg["d_ff"], vocab=cfg["vocab"],
        act=cfg["act"], norm=cfg["norm"], pattern=(HYENA,),
        hyena=HyenaConfig(n_filter_heads=M, filter_order=cfg["filter_order"],
                          filter_emb=cfg["filter_emb"],
                          short_conv=cfg["short_conv"],
                          sine_freq=float(cfg["sine_freq"]),
                          modulate=bool(cfg.get("modulate", True)),
                          distill_order=cfg["distill_order"]),
        tie_embeddings=bool(cfg["tie_embeddings"]), dtype=cfg["dtype"],
        max_seq=cfg["max_seq"])


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


def state_itemsize(eng) -> int:
    """Bytes per element of the served modal state."""
    return int(eng.cache["groups"]["l0"]["x_re"].dtype.itemsize)
