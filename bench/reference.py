"""Plain float32 reference of a served MultiHyena model.

Written from the paper's description (Massaroli et al. 2023, Sec. 2-4, and
the Hyena operator it distills), in plain jax.numpy with every matrix
product at HIGHEST precision: no kernel, no cache, no batching, one request
at a time and one layer after another (`lax.scan` over the stacked
weights). It imports nothing of the program and reads only the weights
that `bench/weights.py` made.

Each layer is pre-norm: x += (q * y) Wo, with q, k, v from a projection and
a causal depthwise short convolution, u = k * v and y a causal long
convolution of u; then x += gelu(LN(x) W1) W2. The long filter is where the
served model splits, and the reference mirrors the split:

  * at prompt positions (t < T) the program prefills with the implicit
    filter, a sine MLP over positional features materialised at `max_len`,
    with its passthrough `bias`:  y_t = sum_j h[t-j] u_j + bias u_t;
  * at every decoded position (t >= T) it runs the distilled modal SSM over
    the whole history: y_t = Re[R . x_t] + h0 u_t with
    x_t = sum_{j<t} lam^{t-1-j} u_j, which is the causal convolution with
    the filter h'[0] = h0, h'[k] = Re[sum_n R_n lam_n^(k-1)].

Both convolutions are evaluated over the whole sequence by FFT in float32
and the position picks one, so the reference shares no recurrence with the
program. `precision="fp8"` is the control: every matrix product's operands
rounded to float8 e4m3 with one scale per tensor, the next precision below
the bfloat16 that the configurations state.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                       # largest finite float8 e4m3 value


class Dims(NamedTuple):
    n_layers: int
    d_model: int
    n_filter_heads: int
    filter_emb: int
    sine_freq: float
    distill_order: int
    modulate: bool

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        return cls(cfg["n_layers"], cfg["d_model"], cfg["n_filter_heads"],
                   cfg["filter_emb"], float(cfg["sine_freq"]),
                   cfg["distill_order"], bool(cfg.get("modulate", True)))


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(a, b, precision: str):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def layer_norm(x, scale, bias, eps=1e-6):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * scale + bias


def gelu(x):
    """GPT-2's tanh form of the GELU."""
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def implicit_filter(w1, w2, w3, decay, max_len: int, dims: Dims):
    """(M, max_len) Hyena filters: sine MLP over [t, cos 2pi f t,
    -sin 2pi f t], an exponential decay window, each filter scaled to unit
    l1 norm."""
    t = jnp.linspace(0.0, 1.0, max_len)[:, None]
    nb = (dims.filter_emb - 1) // 2
    f = jnp.linspace(1e-4, nb - 1, nb)[None, :]
    ang = f * t * 2 * math.pi
    z = jnp.concatenate([t, jnp.cos(ang), -jnp.sin(ang)], axis=-1)
    w0 = dims.sine_freq
    h = jnp.sin(w0 * jnp.matmul(z, w1, precision=HIGHEST))
    h = jnp.sin(w0 * jnp.matmul(h, w2, precision=HIGHEST))
    h = jnp.matmul(h, w3, precision=HIGHEST)                   # (L, M)
    if dims.modulate:
        h = h * jnp.exp(-jnp.abs(decay)[None, :] * t * 8.0)
    h = h / (jnp.sum(jnp.abs(h), axis=0, keepdims=True) + 1e-8)
    return h.T


def modal_filter(log_a, theta, R_re, R_im, h0, length: int):
    """(M, length) impulse response of the modal SSM, h0 at lag 0."""
    k = jnp.arange(length - 1, dtype=jnp.float32)
    mag = jnp.exp(log_a[..., None] * k)                        # (M, d, L-1)
    ang = theta[..., None] * k
    tail = jnp.sum(R_re[..., None] * mag * jnp.cos(ang)
                   - R_im[..., None] * mag * jnp.sin(ang), axis=1)
    return jnp.concatenate([h0[:, None], tail], axis=1)


def causal_conv(u, h):
    """u (L, D), h (D, L) -> (L, D): y_t = sum_{j<=t} h[t-j] u_j."""
    L = u.shape[0]
    uf = jnp.fft.rfft(u, n=2 * L, axis=0)
    hf = jnp.fft.rfft(h, n=2 * L, axis=1)
    return jnp.fft.irfft(uf * hf.T, n=2 * L, axis=0)[:L]


def short_conv(x, w):
    """Causal depthwise conv: out_t = sum_i w[i] x[t - (W-1) + i]."""
    W = w.shape[0]
    pad = jnp.pad(x, ((W - 1, 0), (0, 0)))
    return sum(pad[i:i + x.shape[0]] * w[i] for i in range(W))


def _layer(x, lw, T, max_len: int, dims: Dims, precision: str):
    L, D = x.shape
    rep = D // dims.n_filter_heads
    h = layer_norm(x, lw["norm1_scale"], lw["norm1_bias"])
    qkv = short_conv(_mm(h, lw["wqkv"].reshape(D, 3 * D), precision),
                     lw["conv_w"])
    q, k, v = qkv[:, :D], qkv[:, D:2 * D], qkv[:, 2 * D:]
    u = k * v
    h_imp = implicit_filter(lw["filt_w1"], lw["filt_w2"], lw["filt_w3"],
                            lw["filt_decay"], max_len, dims)[:, :L]
    h_mod = modal_filter(lw["log_a"], lw["theta"], lw["R_re"], lw["R_im"],
                         lw["h0"], L)
    y_imp = (causal_conv(u, jnp.repeat(h_imp, rep, axis=0))
             + u * jnp.repeat(lw["filt_bias"], rep)[None, :])
    y_mod = causal_conv(u, jnp.repeat(h_mod, rep, axis=0))
    y = jnp.where(jnp.arange(L)[:, None] < T, y_imp, y_mod)
    x = x + _mm(q * y, lw["wo"], precision)
    h = layer_norm(x, lw["norm2_scale"], lw["norm2_bias"])
    return x + _mm(gelu(_mm(h, lw["mlp_wi"], precision)), lw["mlp_wo"],
                   precision)


_TOP = ("tok", "final_scale", "final_bias")


@functools.partial(jax.jit, static_argnames=("dims", "max_len", "n_out",
                                             "precision"))
def logits_at(w, tokens, T, *, dims: Dims, max_len: int, n_out: int,
              precision: str = "f32"):
    """Logits (n_out, V) at positions T-1 .. T+n_out-2 of `tokens` (L,):
    the prompt's last position, then one position per served token. Rows
    past the end of `tokens` repeat its last position."""
    layers = {k: v for k, v in w.items() if k not in _TOP}
    x = w["tok"][tokens]

    def body(x, lw):
        return _layer(x, lw, T, max_len, dims, precision), None

    x, _ = jax.lax.scan(body, x, layers)
    pos = jnp.clip(T - 1 + jnp.arange(n_out), 0, tokens.shape[0] - 1)
    h = layer_norm(x[pos], w["final_scale"], w["final_bias"])
    return _mm(h, w["tok"].T, precision)


def gaps(ref_logits, tokens, n):
    """Per position j < n: how far the logit of tokens[j] lies below the
    reference's best (0 where it is the reference's argmax)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.where(jnp.arange(tokens.shape[0]) < n, best - got, 0.0)


@jax.jit
def sampled(ref_logits, tokens, n, temperature, top_p):
    """What the reference says of tokens sampled at `temperature` with
    nucleus `top_p`, per position j < n (0 beyond):

      * `above`: the probability mass, at that temperature, of the tokens
        more likely than tokens[j]; the nucleus holds a token iff this is
        below `top_p`;
      * `surprise`: -log p(tokens[j]) at that temperature;
      * `mean`, `var`: the surprise's mean and variance when the token is
        drawn from the reference's own nucleus, renormalised.

    A sampler that draws from the right distribution gives surprises whose
    sum, less the sum of means, is about as large as the root of the
    summed variances; a wrong temperature or a missing nucleus cut moves it
    by many of those."""
    logp = jax.nn.log_softmax(ref_logits / temperature, axis=-1)
    p = jnp.exp(logp)
    tok = jnp.take_along_axis(logp, tokens[:, None], axis=-1)
    above = jnp.sum(jnp.where(logp > tok, p, 0.0), axis=-1)
    srt = jnp.sort(logp, axis=-1)[:, ::-1]
    before = jnp.cumsum(jnp.exp(srt), axis=-1) - jnp.exp(srt)
    k = jnp.sum(before < top_p, axis=-1, keepdims=True) - 1
    keep = logp >= jnp.take_along_axis(srt, k, axis=-1)
    q = jnp.where(keep, p, 0.0)
    q = q / jnp.sum(q, axis=-1, keepdims=True)
    mean = jnp.sum(q * -logp, axis=-1)
    var = jnp.sum(q * jnp.square(logp), axis=-1) - jnp.square(mean)
    live = jnp.arange(tokens.shape[0]) < n
    z = lambda x: jnp.where(live, x, 0.0)
    return {"above": z(above), "surprise": z(-tok[:, 0]), "mean": z(mean),
            "var": z(var)}
