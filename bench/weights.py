"""Seeded random weights of a MultiHyena model, made by the benchmark.

One jitted call makes every weight on the device from the seed, in float32,
the type the serving path keeps its parameters in (it casts to the compute
dtype at use). The layout here is the benchmark's own: a flat dict of arrays
stacked over layers. `bench/reference.py` reads it as it is, and
`to_program` hands the same arrays to the system under test in the shape of
its parameter tree, so the reference takes nothing the program has made.

Distributions follow the usual initialisations (normal with 1/sqrt(fan-in)
for projections), except that the layer norms, the filter passthrough and
the modal residues are drawn at random too, so that a fault in any of them
changes the output. The modal poles are a stable random system, magnitudes
in [0.7, 0.99] and angles in [0, pi), as a distilled filter would have.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key from a seed of any size (two 32-bit halves folded in)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def shapes(cfg: dict) -> dict:
    """Name -> shape of every weight, for a config file's dict."""
    L, D, F, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    M, order, emb = cfg["n_filter_heads"], cfg["filter_order"], cfg["filter_emb"]
    W, d = cfg["short_conv"], cfg["distill_order"] // 2
    return {
        "tok": (V, D),
        "norm1_scale": (L, D), "norm1_bias": (L, D),
        "wqkv": (L, D, 3, D), "wo": (L, D, D),
        "conv_w": (L, W, 3 * D),
        "filt_w1": (L, emb, order), "filt_w2": (L, order, order),
        "filt_w3": (L, order, M), "filt_decay": (L, M), "filt_bias": (L, M),
        "log_a": (L, M, d), "theta": (L, M, d),
        "R_re": (L, M, d), "R_im": (L, M, d), "h0": (L, M),
        "norm2_scale": (L, D), "norm2_bias": (L, D),
        "mlp_wi": (L, D, F), "mlp_wo": (L, F, D),
        "final_scale": (D,), "final_bias": (D,),
    }


_PROJECTIONS = ("wqkv", "wo", "mlp_wi", "mlp_wo", "conv_w", "filt_w1",
                "filt_w2", "filt_w3")


def _draw(key, name: str, shape) -> jnp.ndarray:
    n = lambda: jax.random.normal(key, shape, jnp.float32)
    u = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    if name in _PROJECTIONS:                # (layers, fan_in, ...)
        return n() / math.sqrt(shape[1])
    if name == "tok":
        return n() * 0.02
    if name.endswith("_scale"):
        return 1.0 + 0.1 * n()
    if name.endswith("_bias") or name == "h0":
        return 0.1 * n()
    if name == "filt_decay":
        return u(0.5, 3.5)
    if name == "log_a":
        return jnp.log(u(0.7, 0.99))
    if name == "theta":
        return u(0.0, math.pi)
    if name in ("R_re", "R_im"):
        return n() / shape[-1]
    raise KeyError(name)


@functools.lru_cache(maxsize=None)
def _maker(items: tuple):
    def make(key):
        keys = jax.random.split(key, len(items))
        return {name: _draw(k, name, shape)
                for k, (name, shape) in zip(keys, items)}
    return jax.jit(make)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight of the config, on the default device, from `seed`."""
    items = tuple(sorted((k, tuple(v)) for k, v in shapes(cfg).items()))
    return _maker(items)(seed_key(seed))


def to_program(w: dict) -> dict:
    """The same arrays in the serving path's parameter tree (one stacked
    group of one Hyena block per layer, tied embedding)."""
    return {
        "embed": {"tok": w["tok"]},
        "final_norm": {"scale": w["final_scale"], "bias": w["final_bias"]},
        "groups": {"l0": {
            "norm1": {"scale": w["norm1_scale"], "bias": w["norm1_bias"]},
            "mix": {
                "wqkv": w["wqkv"], "wo": w["wo"],
                "short_conv": {"w": w["conv_w"]},
                "filter": {"w1": w["filt_w1"], "w2": w["filt_w2"],
                           "w3": w["filt_w3"], "decay": w["filt_decay"],
                           "bias": w["filt_bias"]},
                "distilled": {"log_a": w["log_a"], "theta": w["theta"],
                              "R_re": w["R_re"], "R_im": w["R_im"],
                              "h0": w["h0"]},
            },
            "norm2": {"scale": w["norm2_scale"], "bias": w["norm2_bias"]},
            "mlp": {"wi": w["mlp_wi"], "wo": w["mlp_wo"]},
        }},
    }
