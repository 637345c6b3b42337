"""MultiHyena (Massaroli et al. 2023, arXiv:2310.18780): the architecture's
part of the harness. A configuration file with `"arch": "multihyena"` is
run, compiled, calibrated and checked through this module alone.

  * `model_config`: the program's ModelConfig for the config file.
  * `make_weights`, `to_program`: seeded random weights, in float32, the
    type the serving path keeps its parameters in (it casts to the compute
    dtype at use), and the same arrays in the program's parameter tree.
  * `logits_at`: the plain float32 reference (below), with `precision="fp8"`
    as the control.
  * `decode_flops_per_token`, `prefill_flops`, `KERNELS`: the yardstick's
    counts, from shapes.
  * `DECODE_RUNS`, `PREFILL_RUNS`: how the trace tells the engine's decode
    and prefill executable runs apart.
  * `state_itemsize`, `FAULTS`, `use_tpu_kernels`.

Weights. The layout is the benchmark's own: a flat dict of arrays stacked
over layers. Distributions follow the usual initialisations (normal with
1/sqrt(fan-in) for projections), except that the layer norms, the filter
passthrough and the modal residues are drawn at random too, so that a
fault in any of them changes the output. The modal poles are a stable
random system, magnitudes in [0.7, 0.99] and angles in [0, pi), as a
distilled filter would have.

Reference. Written from the paper's description (Sec. 2-4, and the Hyena
operator it distills), in plain jax.numpy with every matrix product at
HIGHEST precision: no kernel, no cache, no batching, one request at a time
and one layer after another (`lax.scan` over the stacked weights). It
imports nothing of the program and reads only the weights made here.

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
program. The control rounds every matrix product's operands to float8 e4m3
with one scale per tensor, the next precision below the bfloat16 that the
configurations state.

Counts. Model FLOPs count what serving a token requires: the projections
(q, k, v and output), the MLP, the short convolution, the long convolution
(FFTs at prefill, the modal recurrence at decode, and the modal state
prefill hands to decode) and one row of the tied LM head per token
produced. The implicit filter's MLP is recomputed on every prefill and is
not counted; nor is bucket padding. The `ssm_decode` count is of the
algorithm at the pool's shapes: the state (B, D, d/2), real and imaginary,
read and written once in its served dtype; u and y (B, D) in float32; the
modal parameters at per-head size (M, d/2) and h0 (D). Its time bound is
bytes over HBM bandwidth: the kernel does about 11 d/2 FLOPs per 16 d/2
bytes of state.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

import program
import weights
from reference import HIGHEST, matmul

# --- the program's configuration ---------------------------------------------


def model_config(cfg: dict):
    """The program's ModelConfig for a MultiHyena config file."""
    program._import_path()
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


# --- weights -----------------------------------------------------------------


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


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight of the config, in float32, on the default device."""
    return weights.make(shapes(cfg), _draw, seed)


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


# --- the plain reference -----------------------------------------------------


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
    qkv = short_conv(matmul(h, lw["wqkv"].reshape(D, 3 * D), precision),
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
    x = x + matmul(q * y, lw["wo"], precision)
    h = layer_norm(x, lw["norm2_scale"], lw["norm2_bias"])
    return x + matmul(gelu(matmul(h, lw["mlp_wi"], precision)), lw["mlp_wo"],
                      precision)


_TOP = ("tok", "final_scale", "final_bias")


@functools.partial(jax.jit, static_argnames=("dims", "max_len", "n_out",
                                             "precision"))
def _logits(w, tokens, T, *, dims: Dims, max_len: int, n_out: int,
            precision: str):
    layers = {k: v for k, v in w.items() if k not in _TOP}
    x = w["tok"][tokens]

    def body(x, lw):
        return _layer(x, lw, T, max_len, dims, precision), None

    x, _ = jax.lax.scan(body, x, layers)
    pos = jnp.clip(T - 1 + jnp.arange(n_out), 0, tokens.shape[0] - 1)
    h = layer_norm(x[pos], w["final_scale"], w["final_bias"])
    return matmul(h, w["tok"].T, precision)


def logits_at(w, tokens, T, *, cfg: dict, max_len: int, n_out: int,
              precision: str = "f32"):
    """Logits (n_out, V) at positions T-1 .. T+n_out-2 of `tokens` (L,):
    the prompt's last position, then one position per served token. Rows
    past the end of `tokens` repeat its last position."""
    return _logits(w, tokens, T, dims=Dims.of(cfg), max_len=max_len,
                   n_out=n_out, precision=precision)


# --- the yardstick's counts --------------------------------------------------


def _fft(n: int) -> float:
    """FLOPs of one real FFT of length n (the usual 2.5 n log2 n)."""
    return 2.5 * n * math.log2(n)


def layer_matmul_params(cfg: dict) -> int:
    D, F = cfg["d_model"], cfg["d_ff"]
    return 4 * D * D + 2 * D * F


def decode_flops_per_token(cfg: dict) -> float:
    """One decoded token: projections, MLP, short conv, the modal step
    (output 4 d/2 + 2 and update 7 d/2 per channel) and the LM head."""
    L, D, V = cfg["n_layers"], cfg["d_model"], cfg["vocab"]
    d2, W = cfg["distill_order"] // 2, cfg["short_conv"]
    per_layer = (2 * layer_matmul_params(cfg) + 2 * W * 3 * D
                 + D * (11 * d2 + 2) + D)
    return L * per_layer + 2 * V * D


def long_conv_flops(T: int, D: int, M: int) -> float:
    """Causal FFT convolution of T positions: per channel a forward and an
    inverse transform of length 2T and the complex product; per filter
    head one forward transform."""
    n = 2 * T
    return D * (2 * _fft(n) + 6 * (n // 2 + 1)) + M * _fft(n)


def prefill_flops(cfg: dict, T: int) -> float:
    """A prompt of T tokens, up to its first output token."""
    L, D, V, M = cfg["n_layers"], cfg["d_model"], cfg["vocab"], cfg["n_filter_heads"]
    d2, W = cfg["distill_order"] // 2, cfg["short_conv"]
    per_layer = (T * (2 * layer_matmul_params(cfg) + 2 * W * 3 * D + 2 * D)
                 + long_conv_flops(T, D, M)
                 + 2 * 2 * T * D * d2)                 # modal state, re + im
    return L * per_layer + 2 * V * D


def ssm_decode(B: int, cfg: dict, state_itemsize: int = 4) -> dict:
    """FLOPs and bytes of one ssm_decode call over a (B, D, d/2) pool."""
    D, M, d2 = cfg["d_model"], cfg["n_filter_heads"], cfg["distill_order"] // 2
    flops = B * D * (11 * d2 + 2)
    nbytes = (2 * 2 * B * D * d2 * state_itemsize      # re, im; read + write
              + 2 * B * D * 4                         # u in, y out
              + 4 * M * d2 * 4 + D * 4)               # modal params, h0
    return {"flops": float(flops), "bytes": float(nbytes)}


# --- the trace ---------------------------------------------------------------
# Names as `trace_reduce.stable_name` gives them. The Pallas modal decode
# kernel's custom call is named after the function that calls it
# (`ssm_decode_pallas` in kernels/ssm_decode). The engine jits its decode and
# prefill steps as `functools.partial`s, which JAX names `jit__unknown`: a
# run that holds the kernel is a pooled decode, one that holds none a
# bucketed prefill.
_KERNEL_OP = r"^ssm_decode_pallas$"
KERNELS = {"ssm_decode": (_KERNEL_OP, ssm_decode)}
DECODE_RUNS = {"holding": _KERNEL_OP}
PREFILL_RUNS = {"name": r"^jit__unknown$", "lacking": _KERNEL_OP}


def state_itemsize(eng) -> int:
    """Bytes per element of the served modal state."""
    return int(eng.cache["groups"]["l0"]["x_re"].dtype.itemsize)


def use_tpu_kernels() -> None:
    """Make the program take the Pallas decode kernel when it is compiled
    for a described chip (it asks the backend, which is the CPU there)."""
    program._import_path()
    from repro.kernels.ssm_decode import ops
    ops._on_tpu = lambda: True


# --- faults of the modal path (bench/faults.py) ------------------------------


def _state_unchanged(orig):
    def f(x_re, x_im, *a):
        y, _, _ = orig(x_re, x_im, *a)
        return y, x_re, x_im
    return f


def _zero_state(orig):
    def f(dp, u, hcfg, lengths=None):
        xr, xi = orig(dp, u, hcfg, lengths=lengths)
        return xr * 0, xi * 0
    return f


# name -> (program module, attribute, wrapper)
FAULTS = {
    # the decode step returns its modal state unchanged
    "state_unchanged": ("repro.models.hyena", "ssm_decode", _state_unchanged),
    # prefill hands decode a zero modal state
    "zero_prefill_state": ("repro.models.hyena", "modal_prefill_state",
                           _zero_state),
}
