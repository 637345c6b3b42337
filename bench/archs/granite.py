"""Granite-4.0-H (IBM, `granitemoehybrid`, dense): the architecture's part of
the harness. A configuration file with `"arch": "granite"` holds the keys of
the model's published `config.json` and is run, compiled, calibrated and
checked through this module alone.

  * `model_config`: the program's ModelConfig: the period of `layer_types`
    as the block pattern (Mamba-2 and GQA attention in one slot pool), NoPE,
    the four multipliers, the norm eps and the Mamba-2 conv bias.
  * `make_weights`, `to_program`: seeded random weights in bfloat16, the
    configuration's dtype (3.19B parameters are 6.4 GB; float32 would not
    fit beside the pool), and the same arrays in the program's tree.
  * `logits_at`: the plain float32 reference (below), `precision="fp8"` the
    control.
  * `decode_flops_per_token`, `prefill_flops`, `KERNELS`, `DECODE_RUNS`,
    `PREFILL_RUNS`, `state_itemsize`, `FAULTS`, `use_tpu_kernels`.

Weights. A flat dict of arrays. Each weight of a layer is stacked over the
model's repeats of its `layer_types` period (`l<i>_<name>`, i the position in
the period, leading axis the repeat): the program scans the period, so its
parameter tree takes these arrays as they are, with no copy. Projections are
normal with 1/sqrt(fan-in); norm scales, D and the conv bias are drawn at
random too, so that a fault in any of them changes the output; A_log and
dt_bias follow Mamba-2's initialisation (A uniform in [1, 16], dt
log-uniform in [0.001, 0.1]). The token embedding is normal with std
1/(embedding_multiplier sqrt(hidden_size)), so that a scaled row has about
unit norm: at a usual initialisation scale (0.02-0.1) the multiplier of 12
makes the embedding outweigh every layer's output, and a random model then
predicts its own input token at every position (all of them at a tiny
size), which would hide a fault of any mixer from a greedy check. The
configuration lists these under `assumed`.

Reference. Written from the published description of the model (the
Hugging Face `granitemoehybrid` modelling code and the Mamba-2 paper,
arXiv:2405.21060), in plain jax.numpy in float32 under
`jax.default_matmul_precision("highest")`. It imports nothing of the
program and reads only the weights made here, cast to float32 one layer at
a time (the bfloat16 weights stay as they are, so the reference fits beside
them). Per layer, pre-norm:

    x = tok[tokens] * embedding_multiplier
    h = rmsnorm(x); x = x + residual_multiplier * mixer(h)
    h = rmsnorm(x); x = x + residual_multiplier * (silu(h W_g) * (h W_u)) W_o
    logits = rmsnorm(x) tok^T / logits_scaling

The attention mixer is causal grouped-query attention with no positions
(NoPE), its softmax scaled by `attention_multiplier`. The Mamba-2 mixer:
z, xBC, dt from the input projection; xBC through the causal depthwise conv
of width `mamba_d_conv` plus its bias, then SiLU; x, B, C split from it;
dt = softplus(dt + dt_bias), A = -exp(A_log); then the per-token
recurrence, one token after another,

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t,

from h_0 = 0 (not the chunked SSD the program prefills with), then the
gated RMSNorm rmsnorm(y * silu(z)) over the inner width and the output
projection. Every norm takes `rms_norm_eps`. The control rounds the operands
of every projection and of the LM head to float8 e4m3 (one scale per
tensor), the next precision below the bfloat16 the configuration states.

Departures from the published model: random weights; no MoE layer (the
configuration has `num_local_experts` 0, so its block is the shared MLP
alone, as here); no sliding window or cache limit (none is configured).

Counts. Model FLOPs of a decoded token: every projection (Mamba-2 in and
out, q, k, v, o), the MLP, the depthwise conv, the state update and readout
of each Mamba-2 layer and the LM head; attention over the context, which
grows with it, is not counted. Of a prompt: the same per token, the causal
scores and values of each attention layer, and the LM head once. The
`mamba2_decode` count is of the algorithm at the pool's shapes: the
(B, H, P, N) state read and written once in its served dtype, x in and y out
(B, H, P), B and C (B, G, N), dt and the decay (B, H) in float32, and D.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

import program
import weights
from reference import matmul

# --- the program's configuration ---------------------------------------------


def period(cfg: dict) -> int:
    """The shortest period of `layer_types` that repeats to all of it."""
    types = cfg["layer_types"]
    n = len(types)
    return next(p for p in range(1, n + 1) if n % p == 0
                and all(t == types[i % p] for i, t in enumerate(types)))


def _check(cfg: dict) -> None:
    D, H, P = cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if H * P != cfg["mamba_expand"] * D:
        raise ValueError("mamba_n_heads * mamba_d_head must be "
                         "mamba_expand * hidden_size")
    if cfg["num_local_experts"] or cfg["num_hidden_layers"] != len(
            cfg["layer_types"]):
        raise ValueError("a dense model with one layer type per layer")


def model_config(cfg: dict):
    """The program's ModelConfig for a Granite-4.0-H config file."""
    program._import_path()
    from repro.configs.base import ATTN, MAMBA2, ModelConfig, SSMConfig
    _check(cfg)
    kinds = {"mamba": MAMBA2, "attention": ATTN}
    D, Hq = cfg["hidden_size"], cfg["num_attention_heads"]
    return ModelConfig(
        name=cfg["name"], family="hybrid", n_layers=cfg["num_hidden_layers"],
        d_model=D, n_heads=Hq, n_kv_heads=cfg["num_key_value_heads"],
        head_dim=D // Hq, d_ff=cfg["shared_intermediate_size"],
        vocab=cfg["vocab_size"], act="swiglu", norm="rmsnorm",
        rope_theta=float(cfg["rope_theta"]),
        nope=cfg["position_embedding_type"] == "nope",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        pattern=tuple(kinds[t] for t in cfg["layer_types"][:period(cfg)]),
        ssm=SSMConfig(d_state=cfg["mamba_d_state"], d_conv=cfg["mamba_d_conv"],
                      expand=cfg["mamba_expand"], head_dim=cfg["mamba_d_head"],
                      chunk=cfg["mamba_chunk_size"],
                      n_groups=cfg["mamba_n_groups"],
                      conv_bias=bool(cfg["mamba_conv_bias"])),
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["dtype"],
        max_seq=cfg["max_position_embeddings"])


# --- weights -----------------------------------------------------------------


class Dims(NamedTuple):
    """The shapes and constants the reference needs (hashable: static)."""
    d_model: int
    heads: int           # attention query heads
    kv_heads: int
    head_dim: int
    m_heads: int         # Mamba-2 heads
    m_head_dim: int
    d_state: int
    groups: int
    d_conv: int
    eps: float
    attention_multiplier: float
    residual_multiplier: float
    embedding_multiplier: float
    logits_scaling: float

    @classmethod
    def of(cls, cfg: dict) -> "Dims":
        D, Hq = cfg["hidden_size"], cfg["num_attention_heads"]
        return cls(D, Hq, cfg["num_key_value_heads"], D // Hq,
                   cfg["mamba_n_heads"], cfg["mamba_d_head"],
                   cfg["mamba_d_state"], cfg["mamba_n_groups"],
                   cfg["mamba_d_conv"], float(cfg["rms_norm_eps"]),
                   float(cfg["attention_multiplier"]),
                   float(cfg["residual_multiplier"]),
                   float(cfg["embedding_multiplier"]),
                   float(cfg["logits_scaling"]))

    @property
    def inner(self) -> int:
        return self.m_heads * self.m_head_dim

    @property
    def conv_dim(self) -> int:
        return self.inner + 2 * self.groups * self.d_state


def _mixer_shapes(kind: str, d: Dims, R: int) -> dict:
    D = d.d_model
    if kind == "attention":
        return {"wq": (R, D, d.heads, d.head_dim),
                "wk": (R, D, d.kv_heads, d.head_dim),
                "wv": (R, D, d.kv_heads, d.head_dim),
                "wo": (R, d.heads, d.head_dim, D)}
    return {"in_proj": (R, D, 2 * d.inner + 2 * d.groups * d.d_state
                        + d.m_heads),
            "conv_w": (R, d.d_conv, d.conv_dim), "conv_b": (R, d.conv_dim),
            "A_log": (R, d.m_heads), "D": (R, d.m_heads),
            "dt_bias": (R, d.m_heads), "norm_scale": (R, d.inner),
            "out_proj": (R, d.inner, D)}


def shapes(cfg: dict) -> dict:
    """Name -> shape of every weight, for a config file's dict."""
    d, p = Dims.of(cfg), period(cfg)
    R = cfg["num_hidden_layers"] // p
    D, F, V = d.d_model, cfg["shared_intermediate_size"], cfg["vocab_size"]
    out = {"tok": (V, D), "final_scale": (D,)}
    for i, kind in enumerate(cfg["layer_types"][:p]):
        layer = dict(_mixer_shapes(kind, d, R), norm1=(R, D), norm2=(R, D),
                     mlp_wi=(R, D, 2, F), mlp_wo=(R, F, D))
        out.update((f"l{i}_{k}", s) for k, s in layer.items())
    return out


@functools.lru_cache(maxsize=None)
def _drawer(embedding_multiplier: float):
    """The draw of every weight (one function per multiplier, so that
    `weights.make` compiles once for each)."""
    return functools.partial(_draw, embedding_multiplier)


def _draw(embedding_multiplier: float, key, name: str, shape) -> jnp.ndarray:
    n = lambda: jax.random.normal(key, shape, jnp.float32)
    u = lambda lo, hi: jax.random.uniform(key, shape, jnp.float32, lo, hi)
    kind = name.split("_", 1)[-1] if name.startswith("l") else name
    if kind == "tok":                        # scaled rows of unit norm
        w = n() / (embedding_multiplier * math.sqrt(shape[1]))
    elif kind in ("final_scale", "norm1", "norm2", "norm_scale", "D"):
        w = 1.0 + 0.1 * n()
    elif kind == "A_log":
        w = jnp.log(u(1.0, 16.0))
    elif kind == "dt_bias":                  # inverse softplus of dt
        dt = jnp.exp(u(math.log(1e-3), math.log(0.1)))
        w = dt + jnp.log(-jnp.expm1(-dt))
    elif kind == "conv_w":                   # depthwise: fan-in d_conv
        w = n() / math.sqrt(shape[1])
    elif kind == "conv_b":
        w = 0.1 * n()
    elif kind == "wo":                       # (R, heads, head_dim, D)
        w = n() / math.sqrt(shape[1] * shape[2])
    else:                                    # (R, fan_in, ...)
        w = n() / math.sqrt(shape[1])
    return w.astype(jnp.bfloat16)


def make_weights(cfg: dict, seed: int) -> dict:
    """Every weight of the config, in bfloat16, on the default device."""
    return weights.make(shapes(cfg),
                        _drawer(float(cfg["embedding_multiplier"])), seed)


def to_program(w: dict) -> dict:
    """The same arrays in the serving path's parameter tree: one group of
    the period's blocks, each stacked over the repeats; tied embedding."""
    positions = sorted({int(k[1:].split("_", 1)[0]) for k in w
                        if k[0] == "l" and k[1].isdigit()})

    def block(i: int) -> dict:
        g = lambda k: w[f"l{i}_{k}"]
        if f"l{i}_wq" in w:
            mix = {k: g(k) for k in ("wq", "wk", "wv", "wo")}
        else:
            mix = {"in_proj": g("in_proj"),
                   "conv": {"w": g("conv_w"), "b": g("conv_b")},
                   "A_log": g("A_log"), "D": g("D"), "dt_bias": g("dt_bias"),
                   "norm_scale": g("norm_scale"), "out_proj": g("out_proj")}
        return {"norm1": {"scale": g("norm1")}, "mix": mix,
                "norm2": {"scale": g("norm2")},
                "mlp": {"wi": g("mlp_wi"), "wo": g("mlp_wo")}}

    return {"embed": {"tok": w["tok"]},
            "final_norm": {"scale": w["final_scale"]},
            "groups": {f"l{i}": block(i) for i in positions}}


# --- the plain reference -----------------------------------------------------


def rms_norm(x, scale, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * scale


def _layer_weights(lw: dict, r):
    """Repeat r of a period position's stacked weights, in float32."""
    return {k: v[r].astype(jnp.float32) for k, v in lw.items()}


def _mlp(x, lw, d: Dims, precision: str):
    h = rms_norm(x, lw["norm2"], d.eps)
    g = matmul(h, lw["mlp_wi"].reshape(d.d_model, -1), precision)
    g = g.reshape(x.shape[0], 2, -1)
    y = matmul(jax.nn.silu(g[:, 0]) * g[:, 1], lw["mlp_wo"], precision)
    return x + d.residual_multiplier * y


def _attention(h, lw, d: Dims, precision: str):
    """Causal GQA over the whole sequence, no positions, one KV head's
    group of query heads at a time."""
    L, D = h.shape
    Hq, K, hd = d.heads, d.kv_heads, d.head_dim
    q = matmul(h, lw["wq"].reshape(D, Hq * hd), precision).reshape(L, Hq, hd)
    k = matmul(h, lw["wk"].reshape(D, K * hd), precision).reshape(L, K, hd)
    v = matmul(h, lw["wv"].reshape(D, K * hd), precision).reshape(L, K, hd)
    causal = jnp.tril(jnp.ones((L, L), bool))
    rep = Hq // K
    outs = []
    for j in range(K):
        qj = q[:, j * rep:(j + 1) * rep].transpose(1, 0, 2)     # (rep, L, hd)
        s = jnp.einsum("hqd,kd->hqk", qj, k[:, j]) * d.attention_multiplier
        s = jnp.where(causal, s, -jnp.inf)
        outs.append(jnp.einsum("hqk,kd->qhd", jax.nn.softmax(s, axis=-1),
                               v[:, j]))
    o = jnp.concatenate(outs, axis=1).reshape(L, Hq * hd)
    return matmul(o, lw["wo"].reshape(Hq * hd, D), precision)


def _mamba(h, lw, d: Dims, precision: str):
    L = h.shape[0]
    H, P, N, G, W = d.m_heads, d.m_head_dim, d.d_state, d.groups, d.d_conv
    di = d.inner
    proj = matmul(h, lw["in_proj"], precision)
    z, xBC, dt = (proj[:, :di], proj[:, di:di + d.conv_dim],
                  proj[:, di + d.conv_dim:])
    pad = jnp.pad(xBC, ((W - 1, 0), (0, 0)))
    xBC = sum(pad[i:i + L] * lw["conv_w"][i] for i in range(W)) + lw["conv_b"]
    xBC = jax.nn.silu(xBC)
    x = xBC[:, :di].reshape(L, H, P)
    B = jnp.repeat(xBC[:, di:di + G * N].reshape(L, G, N), H // G, axis=1)
    C = jnp.repeat(xBC[:, di + G * N:].reshape(L, G, N), H // G, axis=1)
    dt = jax.nn.softplus(dt + lw["dt_bias"])                    # (L, H)
    A = -jnp.exp(lw["A_log"])

    def step(s, t):
        x_t, dt_t, B_t, C_t = t
        s = (jnp.exp(dt_t * A)[:, None, None] * s
             + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :])
        return s, jnp.einsum("hpn,hn->hp", s, C_t) + lw["D"][:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, dt, B, C), unroll=8)
    y = y.reshape(L, di) * jax.nn.silu(z)
    y = rms_norm(y, lw["norm_scale"], d.eps)
    return matmul(y, lw["out_proj"], precision)


@functools.partial(jax.jit, static_argnames=("kind", "dims", "precision"))
def _layer(x, lw, r, *, kind: str, dims: Dims, precision: str):
    lw = _layer_weights(lw, r)
    h = rms_norm(x, lw["norm1"], dims.eps)
    mixer = _attention if kind == "attention" else _mamba
    x = x + dims.residual_multiplier * mixer(h, lw, dims, precision)
    return _mlp(x, lw, dims, precision)


@functools.partial(jax.jit, static_argnames=("dims",))
def _embed(tok, tokens, *, dims: Dims):
    return tok[tokens].astype(jnp.float32) * dims.embedding_multiplier


@functools.partial(jax.jit, static_argnames=("dims", "n_out", "precision"))
def _head(x, tok, final_scale, T, *, dims: Dims, n_out: int,
          precision: str):
    pos = jnp.clip(T - 1 + jnp.arange(n_out), 0, x.shape[0] - 1)
    h = rms_norm(x[pos], final_scale.astype(jnp.float32), dims.eps)
    return (matmul(h, tok.astype(jnp.float32).T, precision)
            / dims.logits_scaling)


def logits_at(w, tokens, T, *, cfg: dict, max_len: int, n_out: int,
              precision: str = "f32"):
    """Logits (n_out, V) at positions T-1 .. T+n_out-2 of `tokens` (L,):
    the prompt's last position, then one position per served token. Rows
    past the end of `tokens` repeat its last position."""
    dims, p = Dims.of(cfg), period(cfg)
    with jax.default_matmul_precision("highest"):
        x = _embed(w["tok"], tokens, dims=dims)
        for layer, kind in enumerate(cfg["layer_types"]):
            r, i = divmod(layer, p)
            lw = {k.split("_", 1)[1]: v for k, v in w.items()
                  if k.startswith(f"l{i}_")}
            x = _layer(x, lw, r, kind=kind, dims=dims, precision=precision)
        return _head(x, w["tok"], w["final_scale"], T, dims=dims,
                     n_out=n_out, precision=precision)


# --- the yardstick's counts --------------------------------------------------


def _layer_params(cfg: dict, kind: str) -> int:
    """Matrix parameters of one layer's mixer and MLP."""
    d, F = Dims.of(cfg), cfg["shared_intermediate_size"]
    D = d.d_model
    if kind == "attention":
        mixer = D * d.head_dim * 2 * (d.heads + d.kv_heads)
    else:
        mixer = (D * (2 * d.inner + 2 * d.groups * d.d_state + d.m_heads)
                 + d.inner * D)
    return mixer + 3 * D * F


def _mamba_token_flops(d: Dims) -> float:
    """A Mamba-2 layer's per-token work outside its projections: the conv
    (2 W per channel, and the bias), the state update (3 per element:
    h * decay + (dt x) B) and readout (2 per element), dt x and D x."""
    return (d.conv_dim * (2 * d.d_conv + 1)
            + d.m_heads * d.m_head_dim * (5 * d.d_state + 3))


def decode_flops_per_token(cfg: dict) -> float:
    d = Dims.of(cfg)
    per_layer = sum(2 * _layer_params(cfg, kind)
                    + (_mamba_token_flops(d) if kind == "mamba" else 0)
                    for kind in cfg["layer_types"])
    return per_layer + 2 * cfg["vocab_size"] * d.d_model


def prefill_flops(cfg: dict, T: int) -> float:
    """A prompt of T tokens, up to its first output token."""
    d = Dims.of(cfg)
    attn = 2 * 2 * d.heads * d.head_dim * T * (T + 1) / 2
    return (sum(T * (2 * _layer_params(cfg, kind)
                     + (_mamba_token_flops(d) if kind == "mamba" else 0))
                + (attn if kind == "attention" else 0)
                for kind in cfg["layer_types"])
            + 2 * cfg["vocab_size"] * d.d_model)


def mamba2_decode(B: int, cfg: dict, state_itemsize: int = 4) -> dict:
    """FLOPs and bytes of one mamba2_decode call over a (B, H, P, N) pool."""
    d = Dims.of(cfg)
    H, P, N, G = d.m_heads, d.m_head_dim, d.d_state, d.groups
    flops = B * H * P * (5 * N + 3)
    nbytes = (2 * B * H * P * N * state_itemsize     # state read + write
              + 2 * B * H * P * 4                    # x in, y out
              + 2 * B * G * N * 4                    # B, C
              + 2 * B * H * 4 + H * 4)               # dt, decay; D
    return {"flops": float(flops), "bytes": float(nbytes)}


# --- the trace ---------------------------------------------------------------
# Names as `trace_reduce.stable_name` gives them. The Pallas Mamba-2 decode
# kernel's custom call is named after the function that calls it
# (`mamba2_decode_pallas` in kernels/mamba2_decode); the engine's decode and
# prefill executables are both `jit__unknown`: a run that holds the kernel
# is a pooled decode, one that holds none a bucketed prefill.
_KERNEL_OP = r"^mamba2_decode_pallas$"
KERNELS = {"mamba2_decode": (_KERNEL_OP, mamba2_decode)}
DECODE_RUNS = {"holding": _KERNEL_OP}
PREFILL_RUNS = {"name": r"^jit__unknown$", "lacking": _KERNEL_OP}


def state_itemsize(eng) -> int:
    """Bytes per element of the served Mamba-2 state."""
    return next(int(c["ssm"].dtype.itemsize)
                for c in eng.cache["groups"].values() if "ssm" in c)


def use_tpu_kernels() -> None:
    """Make the program take the Pallas decode kernel when it is compiled
    for a described chip (it asks the backend, which is the CPU there)."""
    program._import_path()
    from repro.kernels.mamba2_decode import ops
    ops._on_tpu = lambda: True


# --- faults of the hybrid path (bench/faults.py) -----------------------------


def _state_unchanged(orig):
    def f(h, *a):
        y, _ = orig(h, *a)
        return y, h
    return f


def _zero_prefill_state(orig):
    def f(*a, return_state=False, **kw):
        out = orig(*a, return_state=return_state, **kw)
        if not return_state:
            return out
        y, cache = out
        return y, dict(cache, ssm=cache["ssm"] * 0)
    return f


def _kv_not_written(orig):
    def f(params, cache, *a, **kw):
        _, y = orig(params, cache, *a, **kw)
        return cache, y
    return f


def _residual_ignored(orig):
    def f(x, y, cfg):
        return x + y
    return f


# name -> (program module, attribute, wrapper)
FAULTS = {
    # the Mamba-2 decode step returns its state unchanged
    "mamba_state_unchanged": ("repro.models.ssm", "mamba2_state_step",
                              _state_unchanged),
    # bucketed prefill hands decode a zero Mamba-2 state
    "mamba_zero_prefill_state": ("repro.models.ssm", "mamba2_block",
                                 _zero_prefill_state),
    # the attention decode step does not write its token's k and v
    "kv_not_written": ("repro.models.attention", "attention_decode",
                       _kv_not_written),
    # every residual add ignores the residual multiplier
    "residual_ignored": ("repro.models.model", "_residual",
                         _residual_ignored),
}
