"""A tiny GQA Transformer, a second architecture for the harness's tests.

It joins the benchmark by new files alone (this module copied to
`bench/archs/gqa.py`, a config with `"arch": "gqa"`, traffic and limits),
and is served by the program's attention slot pool (`pattern=(ATTN,)`):
pre-norm blocks of RMSNorm, grouped-query causal attention with rotary
positions, and a SwiGLU MLP, with a tied embedding. Weights are float32.

The reference is written from the usual description of such a model (RoPE
as in GPT-NeoX: the two halves of each head rotated as a pair), in plain
jax.numpy at HIGHEST precision, over the whole sequence at once. The
program keeps its KV cache in bfloat16, so the two differ by that rounding.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

import program
import weights
from reference import matmul


def model_config(cfg: dict):
    program._import_path()
    from repro.configs.base import ATTN, ModelConfig
    return ModelConfig(
        name=cfg["name"], family="dense", n_layers=cfg["n_layers"],
        d_model=cfg["d_model"], n_heads=cfg["n_heads"],
        n_kv_heads=cfg["n_kv_heads"], head_dim=cfg["head_dim"],
        d_ff=cfg["d_ff"], vocab=cfg["vocab"], act="swiglu", norm="rmsnorm",
        rope_theta=float(cfg["rope_theta"]), tie_embeddings=True,
        pattern=(ATTN,), dtype=cfg["dtype"], max_seq=cfg["max_seq"])


def shapes(cfg: dict) -> dict:
    L, D, F, V = cfg["n_layers"], cfg["d_model"], cfg["d_ff"], cfg["vocab"]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return {"tok": (V, D), "final_scale": (D,),
            "norm1_scale": (L, D), "norm2_scale": (L, D),
            "wq": (L, D, H, hd), "wk": (L, D, K, hd), "wv": (L, D, K, hd),
            "wo": (L, H, hd, D), "mlp_wi": (L, D, 2, F), "mlp_wo": (L, F, D)}


def _draw(key, name: str, shape):
    n = jax.random.normal(key, shape, jnp.float32)
    if name == "tok":
        return n * 0.02
    if name.endswith("_scale"):
        return 1.0 + 0.1 * n
    fan_in = shape[1] * shape[2] if name == "wo" else shape[1]
    return n / math.sqrt(fan_in)


def make_weights(cfg: dict, seed: int) -> dict:
    return weights.make(shapes(cfg), _draw, seed)


def to_program(w: dict) -> dict:
    return {"embed": {"tok": w["tok"]},
            "final_norm": {"scale": w["final_scale"]},
            "groups": {"l0": {
                "norm1": {"scale": w["norm1_scale"]},
                "mix": {k: w[k] for k in ("wq", "wk", "wv", "wo")},
                "norm2": {"scale": w["norm2_scale"]},
                "mlp": {"wi": w["mlp_wi"], "wo": w["mlp_wo"]}}}}


# --- the plain reference -----------------------------------------------------


def rms_norm(x, scale, eps=1e-6):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x, theta: float):
    """x (L, heads, hd): each position's pairs (i, i + hd/2) rotated by
    position / theta^(2i/hd)."""
    L, _, hd = x.shape
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(L, dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang),
                            b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _layer(x, lw, cfg, precision):
    L, D = x.shape
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    mm = lambda a, b: matmul(a, b, precision)
    h = rms_norm(x, lw["norm1_scale"])
    q = rope(mm(h, lw["wq"].reshape(D, H * hd)).reshape(L, H, hd),
             cfg["rope_theta"])
    k = rope(mm(h, lw["wk"].reshape(D, K * hd)).reshape(L, K, hd),
             cfg["rope_theta"])
    v = mm(h, lw["wv"].reshape(D, K * hd)).reshape(L, K, hd)
    k, v = (jnp.repeat(a, H // K, axis=1).transpose(1, 0, 2) for a in (k, v))
    s = mm(q.transpose(1, 0, 2), k.transpose(0, 2, 1)) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    o = mm(jax.nn.softmax(s, axis=-1), v).transpose(1, 0, 2)
    x = x + mm(o.reshape(L, H * hd), lw["wo"].reshape(H * hd, D))
    h = rms_norm(x, lw["norm2_scale"])
    g = mm(h, lw["mlp_wi"].reshape(D, -1)).reshape(L, 2, -1)
    return x + mm(jax.nn.silu(g[:, 0]) * g[:, 1], lw["mlp_wo"])


@functools.partial(jax.jit, static_argnames=("cfg", "n_out", "precision"))
def _logits(w, tokens, T, *, cfg, n_out, precision):
    cfg = dict(cfg)
    layers = {k: v for k, v in w.items() if k not in ("tok", "final_scale")}
    x, _ = jax.lax.scan(lambda x, lw: (_layer(x, lw, cfg, precision), None),
                        w["tok"][tokens], layers)
    pos = jnp.clip(T - 1 + jnp.arange(n_out), 0, tokens.shape[0] - 1)
    return matmul(rms_norm(x[pos], w["final_scale"]), w["tok"].T, precision)


def logits_at(w, tokens, T, *, cfg: dict, max_len: int, n_out: int,
              precision: str = "f32"):
    """Logits (n_out, V) at positions T-1 .. T+n_out-2 of `tokens`."""
    keys = ("n_heads", "n_kv_heads", "head_dim", "rope_theta")
    return _logits(w, tokens, T, cfg=tuple((k, cfg[k]) for k in keys),
                   n_out=n_out, precision=precision)


# --- counts, trace, state, faults --------------------------------------------


def _matmul_params(cfg: dict) -> int:
    D, F = cfg["d_model"], cfg["d_ff"]
    H, K, hd = cfg["n_heads"], cfg["n_kv_heads"], cfg["head_dim"]
    return D * hd * (2 * H + 2 * K) + 3 * D * F


def decode_flops_per_token(cfg: dict) -> float:
    """Projections, MLP and LM head; the attention over the context, which
    grows with it, is not counted."""
    return (cfg["n_layers"] * 2 * _matmul_params(cfg)
            + 2 * cfg["vocab"] * cfg["d_model"])


def prefill_flops(cfg: dict, T: int) -> float:
    """Projections and MLP of T tokens, causal scores and values, LM head."""
    attn = 2 * 2 * cfg["n_heads"] * cfg["head_dim"] * T * (T + 1) / 2
    return (cfg["n_layers"] * (2 * T * _matmul_params(cfg) + attn)
            + 2 * cfg["vocab"] * cfg["d_model"])


KERNELS = {}            # no kernel of its own: the program's jnp attention
DECODE_RUNS = None      # decode and prefill runs are not told apart yet
PREFILL_RUNS = None


def state_itemsize(eng) -> int:
    """Bytes per element of the served KV cache."""
    return int(eng.cache["groups"]["l0"]["k"].dtype.itemsize)


def use_tpu_kernels() -> None:
    """No kernel to switch on."""


def _kv_unchanged(orig):
    def f(params, cache, *a, **kw):
        _, y = orig(params, cache, *a, **kw)
        return cache, y
    return f


FAULTS = {
    # the decode step returns its KV cache unchanged
    "kv_unchanged": ("repro.models.attention", "attention_decode",
                     _kv_unchanged),
}
