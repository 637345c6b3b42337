"""Shared neural-net layers (raw JAX, no framework deps)."""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import Param, ShardingRules, constrain


@dataclasses.dataclass
class ShardCtx:
    """Threaded through model code to apply activation sharding constraints."""
    mesh: Optional[object] = None
    rules: Optional[ShardingRules] = None

    def cs(self, x, axes):
        if self.mesh is None or self.rules is None:
            return x
        return constrain(x, axes, self.rules, self.mesh)


NOCTX = ShardCtx()


# ---------------------------------------------------------------------------
# Initializers
# ---------------------------------------------------------------------------
def dense_init(key, shape, axes, in_dim=None, dtype=jnp.float32) -> Param:
    in_dim = in_dim if in_dim is not None else shape[0]
    scale = 1.0 / np.sqrt(max(in_dim, 1))
    w = jax.random.normal(key, shape, dtype) * scale
    return Param(w, tuple(axes))


def zeros_init(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.zeros(shape, dtype), tuple(axes))


def ones_init(shape, axes, dtype=jnp.float32) -> Param:
    return Param(jnp.ones(shape, dtype), tuple(axes))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(kind: str, d: int):
    if kind == "rmsnorm":
        return {"scale": ones_init((d,), ("act_embed",))}
    return {"scale": ones_init((d,), ("act_embed",)),
            "bias": zeros_init((d,), ("act_embed",))}


def apply_norm(params, x, kind: str, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * params["scale"]
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps) * params["scale"] + params["bias"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE (including Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x, positions, theta: float,
               m_rope_sections: Optional[Tuple[int, ...]] = None):
    """x: (..., S, H, hd); positions: (..., S) int32 (or (...,3,S) for m-rope).

    For M-RoPE with text-only inputs all three position streams coincide, so
    we accept (..., S) and broadcast across sections — this matches Qwen2-VL
    semantics for pure-text spans while keeping the sectioned layout.
    """
    if theta <= 0.0:
        return x
    hd = x.shape[-1]
    inv = jnp.asarray(rope_freqs(hd, theta))          # (hd/2,)
    pos = positions.astype(jnp.float32)[..., None]    # (..., S, 1)
    ang = pos * inv                                   # (..., S, hd/2)
    if m_rope_sections:
        # section s of the rotary dims uses position stream s; with shared
        # positions the angles are identical, but we keep the structure.
        sec = np.zeros(hd // 2, dtype=np.int32)
        start = 0
        for i, width in enumerate(m_rope_sections):
            sec[start:start + width] = i
            start += width
        ang = ang  # shared positions: streams coincide (text-only stand-in)
    cos = jnp.cos(ang)[..., None, :]                  # (..., S, 1, hd/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------
def init_mlp(key, d: int, f: int, act: str):
    k1, k2, k3 = jax.random.split(key, 3)
    if act in ("swiglu", "geglu"):
        return {
            "wi": dense_init(k1, (d, 2, f), ("embed", None, "mlp"), in_dim=d),
            "wo": dense_init(k2, (f, d), ("mlp", "embed"), in_dim=f),
        }
    return {
        "wi": dense_init(k1, (d, f), ("embed", "mlp"), in_dim=d),
        "wo": dense_init(k2, (f, d), ("mlp", "embed"), in_dim=f),
    }


def apply_mlp(params, x, act: str, ctx: ShardCtx = NOCTX):
    if act in ("swiglu", "geglu"):
        h = jnp.einsum("...d,dgf->...gf", x, params["wi"].astype(x.dtype))
        gate, up = h[..., 0, :], h[..., 1, :]
        g = jax.nn.silu(gate) if act == "swiglu" else jax.nn.gelu(gate)
        h = g * up
    else:
        h = jax.nn.gelu(jnp.einsum("...d,df->...f", x, params["wi"].astype(x.dtype)))
    h = ctx.cs(h, ("batch", None, "mlp"))
    return jnp.einsum("...f,fd->...d", h, params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def init_embed(key, vocab: int, d: int, tie: bool, max_seq: int = 0,
               learned_pos: bool = False):
    keys = jax.random.split(key, 3)
    p = {"tok": Param(jax.random.normal(keys[0], (vocab, d)) * 0.02,
                      ("vocab", "embed"))}
    if not tie:
        p["unembed"] = dense_init(keys[1], (d, vocab), ("embed", "vocab"), in_dim=d)
    if learned_pos:
        p["pos"] = Param(jax.random.normal(keys[2], (max_seq, d)) * 0.02,
                         (None, "embed"))
    return p


def embed_tokens(params, tokens, ctx: ShardCtx = NOCTX, dtype=jnp.bfloat16,
                 multiplier: float = 1.0):
    x = jnp.take(params["tok"], tokens, axis=0).astype(dtype)
    if multiplier != 1.0:
        x = x * jnp.asarray(multiplier, dtype)
    return ctx.cs(x, ("batch", None, "act_embed"))


def unembed(params, x, tie: bool, softcap: float = 0.0, ctx: ShardCtx = NOCTX,
            scaling: float = 1.0):
    """Logits of x; divided by `scaling` (Granite's logits_scaling), then
    soft-capped."""
    if tie:
        logits = jnp.einsum("...d,vd->...v", x, params["tok"].astype(x.dtype))
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["unembed"].astype(x.dtype))
    if scaling != 1.0:
        logits = logits / jnp.asarray(scaling, logits.dtype)
    if softcap > 0.0:
        logits = softcap * jnp.tanh(logits / softcap)
    return ctx.cs(logits, ("batch", None, "vocab"))


# ---------------------------------------------------------------------------
# Depthwise causal short conv (Hyena / Mamba / RG-LRU frontends)
# ---------------------------------------------------------------------------
def init_short_conv(key, d: int, width: int, bias: bool = False):
    w = jax.random.normal(key, (width, d)) / np.sqrt(width)
    p = {"w": Param(w, ("conv", "act_embed"))}
    if bias:
        p["b"] = zeros_init((d,), ("act_embed",))
    return p


def conv_bias(params, y):
    """y plus the conv's bias where its parameters hold one ("b")."""
    if "b" not in params:
        return y
    return y + params["b"].astype(y.dtype)


def apply_short_conv(params, x):
    """x: (B, S, D) -> causal depthwise conv, same length."""
    w = params["w"].astype(x.dtype)                    # (W, D)
    width = w.shape[0]
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(x)
    for i in range(width):
        out = out + pad[:, i:i + x.shape[1], :] * w[i]
    return conv_bias(params, out)


def short_conv_step(params, cache, u):
    """Single-token conv step. cache: (B, W-1, D); u: (B, D)."""
    w = params["w"].astype(u.dtype)
    width = w.shape[0]
    window = jnp.concatenate([cache, u[:, None, :]], axis=1)  # (B, W, D)
    y = jnp.einsum("bwd,wd->bd", window, w)
    new_cache = window[:, 1:, :] if width > 1 else cache
    return new_cache, conv_bias(params, y)


def conv_tail_gather(x, width: int, lengths):
    """Last `width` rows of x (B, S, D) ending at each row's true length —
    the short-conv tail a decode cache carries. Positions before the start
    of the sequence (length < width) are zeros, matching the causal conv's
    left zero-padding. lengths=None means every row is full length."""
    if lengths is None:
        return x[:, x.shape[1] - width:, :]
    idx = lengths[:, None] - width + jnp.arange(width)[None, :]     # (B, W)
    out = jnp.take_along_axis(x, jnp.clip(idx, 0)[..., None], axis=1)
    return jnp.where(idx[..., None] >= 0, out, 0)


def short_conv_chunk(params, tail, x, chunk_len=None):
    """Chunked causal conv with a carried tail (resumable prefill).

    tail: (B, W-1, D) — the W-1 inputs preceding this chunk (zeros for the
    first chunk, which makes chunk 0 bit-identical to `apply_short_conv`);
    x: (B, C, D). Returns (new_tail, y (B, C, D)). `chunk_len` (traced
    scalar) marks how many of the C positions are real: the new tail is the
    W-1 inputs ending at `chunk_len`, so a padded final chunk leaves the
    carried state exactly where the prompt ends.
    """
    w = params["w"].astype(x.dtype)
    width = w.shape[0]
    C = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # (B, W-1+C, D)
    y = jnp.zeros_like(x)
    for i in range(width):
        y = y + ext[:, i:i + C, :] * w[i]
    y = conv_bias(params, y)
    if width == 1:
        return tail, y
    if chunk_len is None:
        new_tail = ext[:, C:, :]
    else:
        idx = chunk_len + jnp.arange(width - 1)       # ext[chunk_len : +W-1]
        new_tail = jnp.take(ext, idx, axis=1)
    return new_tail, y
