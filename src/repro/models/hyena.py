"""Multi-head Hyena (paper Sec. 4) and its three inference modes.

Training/prefill mode evaluates the long convolution with an FFT; decode mode
either (a) uses the LaughingHyena-distilled modal SSM (O(d) per token), or
(b) falls back to the cached-convolution baseline (Lemma 2.1, O(t) per token)
for pre-distillation models.

Filter parametrization follows Hyena: an implicit MLP with sine activations
over positional features, modulated by a learned exponential-decay window.
MultiHyena ties filters across channels into M heads: head m's single filter
h^m is applied to all D/M channels of that head.

Deployment form. The paper's Sec.-4 operator is written with a per-head outer
product z^m = k^m (x) v^m in R^{L x N x N}. Materializing that tensor costs
L*N*D activations; the paper's own memory measurements (Fig. 5.4: constant,
small) imply the deployed operator is the elementwise Hyena gating with tied
filters (the N=1-per-subhead specialization). We therefore use the elementwise
form y = q . (h * (k . v)) with M tied filters as the production operator, and
provide `outer_product_op` (the literal Sec.-4 form) for the associative-recall
validation of Theorem 4.1 at small widths. See DESIGN.md #hardware-adaptation.
"""
from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import Param
from repro.kernels.ssm_decode.ops import ssm_decode
from repro.models.layers import (
    NOCTX, ShardCtx, apply_short_conv, conv_tail_gather, dense_init,
    init_short_conv, short_conv_chunk, short_conv_step,
)


# ---------------------------------------------------------------------------
# Implicit filter (sine-activated MLP over positional features)
# ---------------------------------------------------------------------------
def positional_features(L: int, emb: int) -> jnp.ndarray:
    """(L, emb) features: normalized time + exponentially spaced sinusoids."""
    t = jnp.linspace(0.0, 1.0, L)[:, None]
    nb = (emb - 1) // 2
    f = jnp.asarray(np.linspace(1e-4, nb - 1, nb))[None, :]
    z = jnp.exp(-1j * f * t * 2 * math.pi)
    return jnp.concatenate([t, z.real, z.imag], axis=-1).astype(jnp.float32)


def init_filter_mlp(key, hcfg, M: int):
    """Implicit filter MLP producing M tied filters."""
    order, emb = hcfg.filter_order, hcfg.filter_emb
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        "w1": dense_init(k1, (emb, order), (None, "filters"), in_dim=emb),
        "w2": dense_init(k2, (order, order), ("filters", "filters"), in_dim=order),
        "w3": dense_init(k3, (order, M), ("filters", None), in_dim=order),
        "decay": Param(jnp.linspace(0.5, 3.5, M), (None,)),   # window rates
        "bias": Param(jax.random.normal(k4, (M,)) * 0.1, (None,)),  # h0 term
    }


def init_filter_ssm(key, hcfg, M: int):
    """H3-style filter: a trainable diagonal SSM in modal form (App. E.3.1's
    family). The filter IS an order-ssm_state recurrence already, so
    distillation to a lower order is exact model-order reduction."""
    d = hcfg.ssm_state
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "log_a": Param(jnp.log(jax.random.uniform(k1, (M, d), minval=0.6,
                                                  maxval=0.999)),
                       (None, "state")),
        "theta": Param(jax.random.uniform(k2, (M, d), maxval=math.pi),
                       (None, "state")),
        "R_re": Param(jax.random.normal(k3, (M, d)) / d, (None, "state")),
        "R_im": Param(jnp.zeros((M, d)), (None, "state")),
        "bias": Param(jnp.zeros((M,)), (None,)),
    }


def materialize_filters(params, L: int, hcfg) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (h, h0): h (M, L) long filters, h0 (M,) passthrough."""
    if hcfg.filter_param == "ssm":
        # h[t] = Re sum_n R_n lam_n^(t-1), t >= 1; h[0] = 0 (bias is the
        # passthrough) — Lemma 3.1 evaluation, same math as eval_filter.
        t = jnp.arange(L - 1, dtype=jnp.float32)
        mag = jnp.exp(params["log_a"][..., None] * t)
        ang = params["theta"][..., None] * t
        tail = jnp.einsum("md,mdl->ml", params["R_re"], mag * jnp.cos(ang)) \
            - jnp.einsum("md,mdl->ml", params["R_im"], mag * jnp.sin(ang))
        h = jnp.concatenate([jnp.zeros_like(tail[:, :1]), tail], axis=-1)
        return h, params["bias"]
    z = positional_features(L, hcfg.filter_emb)
    w0 = hcfg.sine_freq
    h = jnp.sin(w0 * (z @ params["w1"]))
    h = jnp.sin(w0 * (h @ params["w2"]))
    h = h @ params["w3"]                                   # (L, M)
    if hcfg.modulate:
        t = jnp.linspace(0.0, 1.0, L)[:, None]
        window = jnp.exp(-jnp.abs(params["decay"])[None, :] * t * 8.0)
        h = h * window
    # normalize per filter (stabilizes training; standard in Hyena impls)
    h = h / (jnp.sum(jnp.abs(h), axis=0, keepdims=True) + 1e-8)
    return h.T, params["bias"]                             # (M, L), (M,)


# ---------------------------------------------------------------------------
# FFT long convolution
# ---------------------------------------------------------------------------
def fft_conv(u: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """Causal conv of u (B, L, D) with per-channel filters h (D, L) or (M, L)
    broadcast over channel groups. Returns (B, L, D) in u.dtype."""
    B, L, D = u.shape
    n = 2 * L
    uf = jnp.fft.rfft(u.astype(jnp.float32), n=n, axis=1)       # (B, F, D)
    hf = jnp.fft.rfft(h.astype(jnp.float32), n=n, axis=-1)      # (D|M, F)
    if hf.shape[0] != D:                                         # tied heads
        M = hf.shape[0]
        hf = jnp.repeat(hf, D // M, axis=0)
    y = jnp.fft.irfft(uf * hf.T[None], n=n, axis=1)[:, :L, :]
    return y.astype(u.dtype)


def fft_conv_sharded(u: jnp.ndarray, h: jnp.ndarray, ctx) -> jnp.ndarray:
    """fft_conv under shard_map: GSPMD cannot partition FFT ops and falls back
    to all-gathering the full global-batch FFT buffers (measured: ~120 GB per
    device per layer at 1.3B/train_4k). The FFT runs along the *sequence*
    axis, which is unsharded — so mapping over (batch, channel) shards makes
    the op embarrassingly parallel with ZERO collectives."""
    from repro.distributed.sharding import resolve_spec, shard_map_compat
    mesh = ctx.mesh
    if mesh is None:
        return fft_conv(u, h)
    B, L, D = u.shape
    if h.shape[0] != D:
        h = jnp.repeat(h, D // h.shape[0], axis=0)               # (D, L)
    mesh_shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    spec_u = resolve_spec((B, L, D), ("batch", None, "qkv"), ctx.rules,
                          mesh_shape)
    spec_h = resolve_spec((D, L), ("qkv", None), ctx.rules, mesh_shape)
    u = jax.lax.with_sharding_constraint(
        u, jax.sharding.NamedSharding(mesh, spec_u))

    def local(u_blk, h_blk):
        return fft_conv(u_blk, h_blk)

    # unchecked replication: h is replicated along 'data', so its cotangent
    # needs the conservative psum the unchecked transpose inserts.
    return shard_map_compat(local, mesh, (spec_u, spec_h), spec_u)(u, h)


# ---------------------------------------------------------------------------
# MultiHyena block
# ---------------------------------------------------------------------------
def init_hyena_block(key, cfg):
    d = cfg.d_model
    h = cfg.hyena
    kq, kk, kv, ko, kc, kf = jax.random.split(key, 6)
    filter_init = (init_filter_ssm if h.filter_param == "ssm"
                   else init_filter_mlp)
    return {
        "wqkv": dense_init(kq, (d, 3, d), ("embed", None, "qkv"), in_dim=d),
        "wo": dense_init(ko, (d, d), ("qkv", "embed"), in_dim=d),
        "short_conv": init_short_conv(kc, 3 * d, h.short_conv),
        "filter": filter_init(kf, h, h.n_filter_heads),
        # Distilled modal SSM (populated by repro.core.distill; initialized
        # to a stable random system so decode lowers before distillation).
        # Paper order d == real state dim == 2 x (free complex modes): the
        # modal form takes Re[.], so d/2 conjugate-pair representatives are
        # stored (App. B.1) and the state is d/2 complex = d reals.
        "distilled": init_modal_params(kv, h.n_filter_heads, h.distill_order // 2),
    }


def init_modal_params(key, M: int, d: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "log_a": Param(jnp.log(jax.random.uniform(k1, (M, d), minval=0.7, maxval=0.99)), (None, "state")),
        "theta": Param(jax.random.uniform(k2, (M, d), maxval=math.pi), (None, "state")),
        "R_re": Param(jax.random.normal(k3, (M, d)) / d, (None, "state")),
        "R_im": Param(jnp.zeros((M, d)), (None, "state")),
        "h0": Param(jnp.zeros((M,)), (None,)),
    }


def modal_poles_residues(dp) -> Tuple[jnp.ndarray, jnp.ndarray]:
    lam = jnp.exp(dp["log_a"]) * jnp.exp(1j * dp["theta"])
    R = dp["R_re"] + 1j * dp["R_im"]
    return lam, R


def hyena_block(params, x, cfg, *, ctx: ShardCtx = NOCTX,
                filters: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
                return_cache: bool = False, cache_kind: str = "native",
                lengths: Optional[jnp.ndarray] = None,
                filter_len: Optional[int] = None):
    """Full-sequence MultiHyena (train / prefill). x: (B, S, D).

    cache_kind selects what `return_cache` collects:
      * "native" — distilled modal SSM state (O(d) recurrent decode);
      * "conv"   — the k.v product sequence for the Lemma-2.1 cached-conv
                   decode baseline (O(t) per token);
      * "epoch"  — the conv buffers plus the FutureFill epoch state
                   (exact decode at amortized O(sqrt(L) log L) per token).

    `lengths` (B,) marks per-row true prompt lengths for bucketed (right-
    padded) prefill: the collected caches are masked/gathered so padded
    positions never enter the modal state, the conv tail, or the kv buffer.
    The causal conv itself needs no masking — right padding cannot reach
    positions < length.

    `filter_len` materializes the implicit filter at a fixed reference
    length and slices it to S. The implicit filter is a function of
    normalized time, so its values depend on the materialization length —
    serving passes filter_len=max_len so exact-length, bucket-padded, and
    chunked prefill (and the cached-conv decode path) all see identical
    filter values; training leaves it None (materialize at S, as before).
    """
    B, S, D = x.shape
    qkv = jnp.einsum("bsd,dge->bsge", x, params["wqkv"].astype(x.dtype))
    qkv = qkv.reshape(B, S, 3 * D)
    pre_conv = qkv
    qkv = apply_short_conv(params["short_conv"], qkv)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q = ctx.cs(q, ("batch", None, "qkv"))
    if filters is None:
        Lf = S if filter_len is None else max(int(filter_len), S)
        filters = materialize_filters(params["filter"], Lf, cfg.hyena)
        filters = (filters[0][:, :S], filters[1])
    h, h0 = filters                                       # (M, S), (M,)
    kv = ctx.cs(k * v, ("batch", None, "qkv"))
    y = fft_conv_sharded(kv, h, ctx) + \
        kv * jnp.repeat(h0, D // h.shape[0]).astype(x.dtype)
    y = ctx.cs(q * y, ("batch", None, "qkv"))
    out = jnp.einsum("bse,ed->bsd", y, params["wo"].astype(x.dtype))
    if return_cache:
        w = cfg.hyena.short_conv - 1
        if lengths is None:
            conv = pre_conv[:, S - w:, :].astype(jnp.float32)
            kv_c = kv
        else:
            # conv tail = the w positions ending at each row's true length
            conv = conv_tail_gather(pre_conv, w, lengths).astype(jnp.float32)
            kv_c = jnp.where(
                jnp.arange(S)[None, :, None] < lengths[:, None, None], kv, 0)
        if cache_kind == "conv":
            cache = {"conv": conv, "kv": kv_c.astype(jnp.float32)}
        elif cache_kind == "epoch":
            # FutureFill epoch cache: prefill leaves epoch 0 with `fut`
            # empty — the first decode tick's flush bakes the whole prefix
            # in via one FFT (exact either way; see hyena_decode_epoch).
            cache = {"conv": conv, "kv": kv_c.astype(jnp.float32),
                     "fut": jnp.zeros((B, S, D), jnp.float32),
                     "epoch": jnp.zeros((B,), jnp.int32)}
        else:
            # modal SSM prefill (Sec. 3.4, O(dT) matmul variant — MXU friendly)
            xr, xi = modal_prefill_state(params["distilled"], kv_c, cfg.hyena,
                                         lengths=lengths)
            cache = {"conv": conv, "x_re": xr, "x_im": xi}
        return out, cache
    return out


def modal_prefill_state(dp, u, hcfg, lengths=None):
    """State after consuming u (B, T, D): x_L[n] = sum_{t<L} lam_n^{L-1-t} u_t.

    Evaluated as a (d x T) Vandermonde-basis matmul per filter head — the
    O(dT) strategy of Sec. 3.4, which maps onto the MXU. The input is
    time-reversed first (u_rev[j] = u[L-1-j]) so the basis lam^j is shared
    across rows; with per-row `lengths` the reversal is a masked gather from
    each row's true end, which is what makes bucket-padded prefill exact.
    Returns (re, im) each (B, D, d).
    """
    B, T, D = u.shape
    M, d = dp["log_a"].shape
    N = D // M
    expo = jnp.arange(T, dtype=jnp.float32)                      # lam^j
    mag = jnp.exp(dp["log_a"][..., None] * expo)                 # (M, d, T)
    ang = dp["theta"][..., None] * expo
    br = mag * jnp.cos(ang)
    bi = mag * jnp.sin(ang)
    uf = u.astype(jnp.float32)
    if lengths is None:
        u_rev = uf[:, ::-1, :]
    else:
        idx = lengths[:, None] - 1 - jnp.arange(T)[None, :]      # (B, T)
        u_rev = jnp.where(idx[..., None] >= 0,
                          jnp.take_along_axis(uf, jnp.clip(idx, 0)[..., None],
                                              axis=1), 0.0)
    ur = u_rev.reshape(B, T, M, N)
    xr = jnp.einsum("btmi,mdt->bmid", ur, br).reshape(B, D, d)
    xi = jnp.einsum("btmi,mdt->bmid", ur, bi).reshape(B, D, d)
    return xr, xi


# ---------------------------------------------------------------------------
# Decode: distilled modal recurrence (Prop. 3.3) — O(d) per token per channel
# ---------------------------------------------------------------------------
def init_hyena_cache(batch: int, cfg, dtype=jnp.float32) -> Dict:
    d = cfg.d_model
    h = cfg.hyena
    return {
        "conv": jnp.zeros((batch, h.short_conv - 1, 3 * d), dtype),
        # modal state: d/2 conjugate-pair modes stored as re/im = d reals
        # per channel — exactly the paper's order-d memory footprint.
        "x_re": jnp.zeros((batch, d, h.distill_order // 2), dtype),
        "x_im": jnp.zeros((batch, d, h.distill_order // 2), dtype),
    }


def hyena_decode(params, cache, x, cfg, *, ctx: ShardCtx = NOCTX):
    """One-token decode with the distilled SSM. x: (B, 1, D)."""
    B, _, D = x.shape
    h = cfg.hyena
    M, N = h.n_filter_heads, D // h.n_filter_heads
    qkv = jnp.einsum("bsd,dge->bsge", x, params["wqkv"].astype(x.dtype))
    qkv = qkv.reshape(B, 3 * D)
    conv_cache, qkv = short_conv_step(params["short_conv"], cache["conv"], qkv)
    q, k, v = jnp.split(qkv, 3, axis=-1)                   # (B, D) each
    u = (k * v).astype(jnp.float32)                        # (B, D)

    dp = params["distilled"]
    log_a = jnp.repeat(dp["log_a"], N, axis=0)               # (D, d)
    theta = jnp.repeat(dp["theta"], N, axis=0)
    R_re = jnp.repeat(dp["R_re"], N, axis=0)
    R_im = jnp.repeat(dp["R_im"], N, axis=0)
    h0 = jnp.repeat(dp["h0"], N, axis=0)

    # Paper convention (Prop. 3.3): y_t = Re[R . x_t] + h0 u_t, then
    # x_{t+1} = lam x_t + u_t, with x_t holding the state after u_{t-1}.
    # Dispatch through the ops wrapper: fused Pallas kernel on TPU (one HBM
    # pass over the state), jnp reference elsewhere.
    xr, xi = cache["x_re"], cache["x_im"]
    y, nxr, nxi = ssm_decode(xr, xi, u, log_a, theta, R_re, R_im, h0)
    out = (q.astype(jnp.float32) * y).astype(x.dtype)
    new_cache = {"conv": conv_cache, "x_re": nxr, "x_im": nxi}
    return new_cache, jnp.einsum("be,ed->bd", out, params["wo"].astype(x.dtype))[:, None, :]


# ---------------------------------------------------------------------------
# Decode baseline: cached convolution (Lemma 2.1) — O(t) per token
# ---------------------------------------------------------------------------
def init_hyena_conv_cache(batch: int, max_len: int, cfg, dtype=jnp.float32):
    return {
        "conv": jnp.zeros((batch, cfg.hyena.short_conv - 1, 3 * cfg.d_model), dtype),
        "kv": jnp.zeros((batch, max_len, cfg.d_model), dtype),   # past k.v products
    }


def hyena_decode_cached_conv(params, cache, x, pos, cfg, filters,
                             *, ctx: ShardCtx = NOCTX):
    """Naive cached-conv decode: y_t = q_t * sum_j h_{t-j} (kv)_j.

    pos: scalar int32 or a per-slot (B,) vector (continuous batching: each
    resident request decodes at its own position).
    """
    B, _, D = x.shape
    h_full, h0 = filters                                   # (M, Lmax), (M,)
    M = h_full.shape[0]
    qkv = jnp.einsum("bsd,dge->bsge", x, params["wqkv"].astype(x.dtype)).reshape(B, 3 * D)
    conv_cache, qkv = short_conv_step(params["short_conv"], cache["conv"], qkv)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    pos = jnp.asarray(pos, jnp.int32)
    Lmax = cache["kv"].shape[1]
    if pos.ndim == 1:
        widx = jnp.minimum(pos, Lmax - 1)                  # clamp idle slots
        kv_cache = cache["kv"].at[jnp.arange(B), widx].set(
            (k * v).astype(cache["kv"].dtype))
        # h_rev[b, j] = h[pos_b - j] for j <= pos_b else 0
        idx = pos[:, None] - jnp.arange(Lmax)[None, :]     # (B, Lmax)
        hm = jnp.take(h_full, jnp.clip(idx, 0), axis=1)    # (M, B, Lmax)
        hr = jnp.where((idx >= 0)[None], hm, 0.0)
        hr = jnp.repeat(hr, D // M, axis=0)                # (D, B, Lmax)
        y = jnp.einsum("bld,dbl->bd", kv_cache, hr.astype(kv_cache.dtype))
    else:
        kv_cache = jax.lax.dynamic_update_slice_in_dim(
            cache["kv"], (k * v)[:, None, :].astype(cache["kv"].dtype), pos,
            axis=1)
        # h_rev[j] = h[pos - j] for j <= pos else 0
        idx = pos - jnp.arange(Lmax)
        hr = jnp.where((idx >= 0)[None, :],
                       jnp.take(h_full, jnp.clip(idx, 0), axis=1), 0.0)
        hr = jnp.repeat(hr, D // M, axis=0)                # (D, Lmax)
        y = jnp.einsum("bld,dl->bd", kv_cache, hr.astype(kv_cache.dtype))
    y = y.astype(jnp.float32) + jnp.repeat(h0, D // M) * \
        (k * v).astype(jnp.float32)
    # keep the accumulation in f32, emit in the residual-stream dtype (the
    # short-conv cache is f32, so q/k/v promote even under bf16 configs)
    out = (q.astype(jnp.float32) * y).astype(x.dtype)
    new_cache = {"conv": conv_cache, "kv": kv_cache}
    return new_cache, jnp.einsum("be,ed->bd", out, params["wo"].astype(x.dtype))[:, None, :]


# ---------------------------------------------------------------------------
# Decode: epoched convolution (FutureFill / Flash Inference) — exact output
# from the TRUE long filter at amortized O(sqrt(L) log L) per token
# ---------------------------------------------------------------------------
def epoch_tail(max_len: int) -> int:
    """Online-tail length E for the epoched decode: the smallest power of two
    >= sqrt(max_len), clamped to max_len. A flush re-runs the full FFT every
    ~E tokens per slot, so the per-token amortized cost is
    O(E + (L/E) log L) ~ O(sqrt(L) log L) — the FutureFill schedule."""
    target = max(1, math.isqrt(max(max_len - 1, 0)) + 1)
    return min(1 << (target - 1).bit_length(), max_len)


def init_hyena_epoch_cache(batch: int, max_len: int, cfg, dtype=jnp.float32):
    """Epoch cache = the cached-conv buffers plus the FutureFill state:
    `fut` (B, max_len, D) holds the consumed prefix's precomputed
    contribution to every future output position, `epoch` (B,) int32 the
    per-slot count of prefix tokens baked into it."""
    c = init_hyena_conv_cache(batch, max_len, cfg, dtype)
    c["fut"] = jnp.zeros((batch, max_len, cfg.d_model), dtype)
    c["epoch"] = jnp.zeros((batch,), jnp.int32)
    return c


def hyena_decode_epoch(params, cache, x, pos, cfg, filters,
                       *, ctx: ShardCtx = NOCTX):
    """One-token epoched decode (FutureFill): y_t exact from the true long
    filter, amortized O(sqrt(L) log L) per token.

    The causal conv splits at the per-slot epoch boundary e:
    fut[t] = sum_{j<e} h[t-j] (kv)_j is precomputed for EVERY future t by one
    FFT at the last flush, so the step only adds the short online tail
    sum_{j in [e, t]} h[t-j] (kv)_j — at most E = epoch_tail terms. When the
    tail would exceed E the flush re-runs the FFT over the kv buffer (rows
    past t are zero, so the full causal conv IS the prefix contribution to
    every future position) under a lax.cond: one executable, zero
    steady-state compiles, FFT cost amortized over ~E tokens per slot.
    Prefill leaves epoch 0 with fut empty, so a freshly admitted slot's
    first decode tick bakes the whole prompt in — exact either way.
    pos: scalar int32 or per-slot (B,).
    """
    B, _, D = x.shape
    h_full, h0 = filters                                   # (M, Lmax), (M,)
    M = h_full.shape[0]
    Lmax = cache["kv"].shape[1]
    E = epoch_tail(Lmax)
    qkv = jnp.einsum("bsd,dge->bsge", x,
                     params["wqkv"].astype(x.dtype)).reshape(B, 3 * D)
    conv_cache, qkv = short_conv_step(params["short_conv"], cache["conv"], qkv)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    kv_t = (k * v).astype(cache["kv"].dtype)
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.full((B,), pos, jnp.int32)
    widx = jnp.minimum(pos, Lmax - 1)                      # clamp idle slots
    kv_cache = cache["kv"].at[jnp.arange(B), widx].set(kv_t)
    epoch = jnp.asarray(cache["epoch"], jnp.int32)
    flush = (pos + 1 - epoch) > E                          # tail incl. token t

    def do_flush(fut):
        full = fft_conv(kv_cache, h_full).astype(fut.dtype)
        return jnp.where(flush[:, None, None], full, fut)

    fut = jax.lax.cond(jnp.any(flush), do_flush, lambda f: f, cache["fut"])
    new_epoch = jnp.where(flush, pos + 1, epoch)
    # online tail over [epoch', pos]: <= E terms, empty right after a flush
    idx = pos[:, None] - jnp.arange(E)[None, :]            # (B, E)
    keep = (idx >= new_epoch[:, None]) & (idx >= 0)
    kv_g = jnp.take_along_axis(kv_cache, jnp.clip(idx, 0)[..., None], axis=1)
    h_tail = jnp.repeat(h_full[:, :E], D // M, axis=0)     # (D, E)
    y = jnp.einsum("bkd,dk->bd", jnp.where(keep[..., None], kv_g, 0),
                   h_tail.astype(kv_cache.dtype))
    fut_t = jnp.take_along_axis(fut, widx[:, None, None], axis=1)[:, 0, :]
    y = y.astype(jnp.float32) + fut_t.astype(jnp.float32) + \
        jnp.repeat(h0, D // M) * kv_t.astype(jnp.float32)
    out = (q.astype(jnp.float32) * y).astype(x.dtype)
    new_cache = {"conv": conv_cache, "kv": kv_cache, "fut": fut,
                 "epoch": new_epoch}
    return new_cache, jnp.einsum("be,ed->bd", out,
                                 params["wo"].astype(x.dtype))[:, None, :]


# ---------------------------------------------------------------------------
# Multi-token decode on the decode cache (speculative verify / replay)
# ---------------------------------------------------------------------------
def _short_conv_rows(params, tail, u, active_len):
    """Per-row resumable short conv: u (B, C, D'), tail (B, W-1, D').
    Returns (new_tail, y (B, C, D'), ext (B, W-1+C, D')) where row b's new
    tail is the W-1 inputs ending at its own active_len (inputs past it
    never enter the carried state). `ext` is the concatenated input window —
    `conv_tail_gather(ext, W-1, W-1+j)` yields the tail after ANY j <= C
    tokens, which is how the speculative selection-commit rolls a conv tail
    to the accepted position without a replay."""
    from repro.models.layers import conv_bias, conv_tail_gather
    w = params["w"]
    width = w.shape[0]
    C = u.shape[1]
    ext = jnp.concatenate([tail, u], axis=1)          # promotes to f32 tail
    wc = w.astype(ext.dtype)
    y = jnp.zeros_like(ext[:, width - 1:, :])
    for i in range(width):
        y = y + ext[:, i:i + C, :] * wc[i]
    y = conv_bias(params, y)
    if width == 1:
        return tail, y, ext
    new_tail = conv_tail_gather(ext, width - 1, (width - 1) + active_len)
    return new_tail.astype(tail.dtype), y, ext


def hyena_decode_chunk(params, cache, x, active_len, cfg, *,
                       ctx: ShardCtx = NOCTX, return_states: bool = False):
    """Consume up to C tokens per slot with the distilled modal recurrence.
    x: (B, C, D); active_len (B,) — row b's modal state and conv tail advance
    by exactly its first active_len tokens (the rest compute garbage outputs
    the caller ignores).

    The state trajectory is an unrolled C-step recurrence (C is tiny — the
    speculation window) with per-row keep-masking, using the SAME update
    formulas as the one-token `ssm_decode` step (lam precomputed once,
    bit-identical values), so a replay over an accepted prefix is
    bit-identical to having decoded those tokens sequentially. The
    Prop.-3.3 readout y_j = Re[R X_j] + h0 u_j is then evaluated for all
    positions in ONE batched einsum over the stacked states — the verify
    path is op-overhead-bound, so keeping the scan body to the 6 state
    multiplies matters. With return_states=True the per-step trajectory and
    the conv input window are also returned, so a speculative commit can
    SELECT the state after any accepted prefix length instead of replaying
    (states past a row's active_len are frozen — only indices <= active_len
    are ever selected)."""
    B, C, D = x.shape
    h = cfg.hyena
    N = D // h.n_filter_heads
    qkv = jnp.einsum("bsd,dge->bsge", x, params["wqkv"].astype(x.dtype))
    qkv = qkv.reshape(B, C, 3 * D)
    active_len = jnp.asarray(active_len, jnp.int32)
    new_tail, qkv, ext = _short_conv_rows(params["short_conv"], cache["conv"],
                                          qkv, active_len)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    u = (k * v).astype(jnp.float32)                       # (B, C, D)
    valid = jnp.arange(C)[None, :] < active_len[:, None]  # (B, C)

    dp = params["distilled"]
    log_a = jnp.repeat(dp["log_a"], N, axis=0)            # (D, d)
    theta = jnp.repeat(dp["theta"], N, axis=0)
    R_re = jnp.repeat(dp["R_re"], N, axis=0)
    R_im = jnp.repeat(dp["R_im"], N, axis=0)
    h0 = jnp.repeat(dp["h0"], N, axis=0)
    lr = jnp.exp(log_a) * jnp.cos(theta)                  # as in ssm_decode
    li = jnp.exp(log_a) * jnp.sin(theta)

    def body(carry, inp):
        xr, xi = carry
        u_t, keep = inp                                   # (B, D), (B,)
        nxr = lr[None] * xr - li[None] * xi + u_t[..., None]
        nxi = lr[None] * xi + li[None] * xr
        keep = keep[:, None, None]
        nxr = jnp.where(keep, nxr, xr)
        nxi = jnp.where(keep, nxi, xi)
        return (nxr, nxi), (xr, xi)       # emit the state BEFORE the token

    (nxr, nxi), (pre_re, pre_im) = jax.lax.scan(
        body, (cache["x_re"], cache["x_im"]),
        (jnp.moveaxis(u, 1, 0), jnp.moveaxis(valid, 1, 0)), unroll=C)
    # batched Prop.-3.3 readout over all C positions at once
    y = jnp.einsum("cbed,ed->bce", pre_re, R_re) \
        - jnp.einsum("cbed,ed->bce", pre_im, R_im) + h0 * u
    out = (q.astype(jnp.float32) * y).astype(x.dtype)
    new_cache = {"conv": new_tail, "x_re": nxr, "x_im": nxi}
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    if return_states:
        # trajectory AFTER each token j (j = 1..C): positions 1..C-1 come
        # from the emitted pre-token states, position C from the final carry
        xs_re = jnp.concatenate([jnp.moveaxis(pre_re[1:], 0, 1),
                                 nxr[:, None]], axis=1)   # (B, C, D, d)
        xs_im = jnp.concatenate([jnp.moveaxis(pre_im[1:], 0, 1),
                                 nxi[:, None]], axis=1)
        aux = {"xs_re": xs_re, "xs_im": xs_im, "ext": ext}
        return new_cache, out, aux
    return new_cache, out


def hyena_decode_cached_conv_chunk(params, cache, x, pos, active_len, cfg,
                                   filters, *, ctx: ShardCtx = NOCTX):
    """Cached-conv (Lemma 2.1) multi-token decode: write up to C new k.v
    products per slot at positions [pos_b, pos_b + active_len_b) and emit the
    exact causal convolution with the TRUE long filter at every chunk
    position. x: (B, C, D); pos/active_len: (B,)."""
    B, C, D = x.shape
    h_full, h0 = filters                                  # (M, Lmax'), (M,)
    M = h_full.shape[0]
    qkv = jnp.einsum("bsd,dge->bsge", x,
                     params["wqkv"].astype(x.dtype)).reshape(B, C, 3 * D)
    pos = jnp.asarray(pos, jnp.int32)
    active_len = jnp.asarray(active_len, jnp.int32)
    new_tail, qkv, _ = _short_conv_rows(params["short_conv"],
                                        cache["conv"], qkv, active_len)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    kvc = (k * v).astype(cache["kv"].dtype)               # (B, C, D)
    Lmax = cache["kv"].shape[1]
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    widx = jnp.clip(positions, 0, Lmax - 1)
    valid = jnp.arange(C)[None, :] < active_len[:, None]
    b = jnp.arange(B)[:, None]
    cur = jnp.take_along_axis(cache["kv"],
                              jnp.broadcast_to(widx[..., None], (B, C, D)),
                              axis=1)
    kv_cache = cache["kv"].at[b, widx].set(
        jnp.where(valid[..., None], kvc, cur))
    # h_rev[b, c, j] = h[pos_b + c - j] for j <= pos_b + c else 0
    idx = positions[:, :, None] - jnp.arange(Lmax)[None, None, :]  # (B,C,L)
    hm = jnp.take(h_full, jnp.clip(idx, 0), axis=1)       # (M, B, C, Lmax)
    hr = jnp.where((idx >= 0)[None], hm, 0.0)
    hr = jnp.repeat(hr, D // M, axis=0)                   # (D, B, C, Lmax)
    y = jnp.einsum("bld,dbcl->bcd", kv_cache, hr.astype(kv_cache.dtype))
    y = y.astype(jnp.float32) + jnp.repeat(h0, D // M) * kvc.astype(jnp.float32)
    out = (q.astype(jnp.float32) * y).astype(x.dtype)
    new_cache = {"conv": new_tail, "kv": kv_cache}
    return new_cache, jnp.einsum("bse,ed->bsd", out,
                                 params["wo"].astype(x.dtype))


def hyena_decode_epoch_chunk(params, cache, x, pos, active_len, cfg,
                             filters, *, ctx: ShardCtx = NOCTX):
    """Epoched multi-token decode (speculative verify / replay): write up to
    C new k.v products per slot and emit the exact causal conv at every chunk
    position as fut[t] + an online tail of at most E + C terms — the at-rest
    tail is <= E by the flush invariant and the chunk adds <= C, so a widened
    static window covers every mid-chunk position without flushing.

    Two lax.cond flushes bracket the chunk: an ENTRY flush for slots whose
    at-rest tail exceeds E (a freshly admitted slot arrives with epoch 0 —
    prefill defers its flush to the first decode; the entry kv rows past pos
    are zero, so the full causal FFT is the prefix contribution), and an END
    flush restoring the <= E invariant for the next tick. `fut`/`epoch` are
    rewritten wholesale by flushes, which is why they are deliberately NOT
    in model._SEQ_KEYS: a speculative snapshot/rollback restores them whole
    while `kv` rolls back row-indexed."""
    B, C, D = x.shape
    h_full, h0 = filters                                  # (M, Lmax), (M,)
    M = h_full.shape[0]
    Lmax = cache["kv"].shape[1]
    E = epoch_tail(Lmax)
    W = min(E + C, Lmax)
    qkv = jnp.einsum("bsd,dge->bsge", x,
                     params["wqkv"].astype(x.dtype)).reshape(B, C, 3 * D)
    pos = jnp.asarray(pos, jnp.int32)
    active_len = jnp.asarray(active_len, jnp.int32)
    new_tail, qkv, _ = _short_conv_rows(params["short_conv"],
                                        cache["conv"], qkv, active_len)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    kvc = (k * v).astype(cache["kv"].dtype)               # (B, C, D)
    epoch = jnp.asarray(cache["epoch"], jnp.int32)
    entry = (pos - epoch) > E

    def do_entry(fut):
        full = fft_conv(cache["kv"], h_full).astype(fut.dtype)
        return jnp.where(entry[:, None, None], full, fut)

    fut = jax.lax.cond(jnp.any(entry), do_entry, lambda f: f, cache["fut"])
    epoch = jnp.where(entry, pos, epoch)
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    widx = jnp.clip(positions, 0, Lmax - 1)
    valid = jnp.arange(C)[None, :] < active_len[:, None]
    b = jnp.arange(B)[:, None]
    cur = jnp.take_along_axis(cache["kv"],
                              jnp.broadcast_to(widx[..., None], (B, C, D)),
                              axis=1)
    kv_cache = cache["kv"].at[b, widx].set(
        jnp.where(valid[..., None], kvc, cur))
    # per-position online tail over [epoch, pos_b + c]: <= E + C terms
    idx = positions[:, :, None] - jnp.arange(W)[None, None, :]   # (B, C, W)
    keep = (idx >= epoch[:, None, None]) & (idx >= 0)
    kv_g = jnp.take_along_axis(
        kv_cache, jnp.clip(idx, 0).reshape(B, C * W)[..., None],
        axis=1).reshape(B, C, W, D)
    h_tail = jnp.repeat(h_full[:, :W], D // M, axis=0)           # (D, W)
    y = jnp.einsum("bckd,dk->bcd", jnp.where(keep[..., None], kv_g, 0),
                   h_tail.astype(kv_cache.dtype))
    fut_c = jnp.take_along_axis(fut,
                                jnp.broadcast_to(widx[..., None], (B, C, D)),
                                axis=1)
    y = y.astype(jnp.float32) + fut_c.astype(jnp.float32) + \
        jnp.repeat(h0, D // M) * kvc.astype(jnp.float32)
    out = (q.astype(jnp.float32) * y).astype(x.dtype)
    # end-of-chunk flush keeps the at-rest tail <= E for the next tick
    new_pos = pos + active_len
    flush = (new_pos - epoch) > E

    def do_flush(fut):
        full = fft_conv(kv_cache, h_full).astype(fut.dtype)
        return jnp.where(flush[:, None, None], full, fut)

    fut = jax.lax.cond(jnp.any(flush), do_flush, lambda f: f, fut)
    new_epoch = jnp.where(flush, new_pos, epoch)
    new_cache = {"conv": new_tail, "kv": kv_cache, "fut": fut,
                 "epoch": new_epoch}
    return new_cache, jnp.einsum("bse,ed->bsd", out,
                                 params["wo"].astype(x.dtype))


# ---------------------------------------------------------------------------
# Chunked (resumable) prefill: one fixed-size chunk of the prompt at a time
# ---------------------------------------------------------------------------
def hyena_prefill_chunk(params, cache, x, start, chunk_len, cfg, filters,
                        *, ctx: ShardCtx = NOCTX, cache_kind: str = "native"):
    """Consume one prompt chunk x (B, C, D) starting at absolute position
    `start` (traced scalar). The cache carries the short-conv tail AND the
    k.v product history buffer (B, Lbuf, D): the chunk's layer output is the
    exact causal convolution of the full history with the TRUE long filter
    (one fft over the zero-padded buffer — a single executable for any
    prompt length), so chunked prefill matches one-shot prefill, not the
    distilled approximation. For the "native" kind the distilled modal state
    is additionally advanced per chunk with the Sec.-3.4 update
    x <- lam^cl x + sum_{i<cl} lam^{cl-1-i} u_i (the per-chunk Vandermonde
    form of core/prefill.py). `chunk_len` <= C marks the real positions of a
    padded final chunk; positions past it write zeros and leave all state
    untouched.
    """
    B, C, D = x.shape
    h_full, h0 = filters                                   # (M, Lbuf'), (M,)
    M = h_full.shape[0]
    qkv = jnp.einsum("bsd,dge->bsge", x, params["wqkv"].astype(x.dtype))
    qkv = qkv.reshape(B, C, 3 * D)
    new_tail, qkv = short_conv_chunk(params["short_conv"], cache["conv"], qkv,
                                     chunk_len)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    valid = (jnp.arange(C) < chunk_len)[None, :, None]
    kvc = jnp.where(valid, (k * v), 0).astype(cache["kv"].dtype)
    kv_buf = jax.lax.dynamic_update_slice_in_dim(cache["kv"], kvc, start,
                                                 axis=1)
    y = jax.lax.dynamic_slice_in_dim(fft_conv(kv_buf, h_full), start, C,
                                     axis=1)
    y = y + kvc * jnp.repeat(h0, D // M)
    out = (q.astype(jnp.float32) * y).astype(x.dtype)
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    new_cache = {"conv": new_tail.astype(cache["conv"].dtype), "kv": kv_buf}
    if cache_kind != "conv":
        dp = params["distilled"]
        N = D // dp["log_a"].shape[0]
        cl = jnp.asarray(chunk_len, jnp.float32)
        # decay the incoming state by lam^cl ...
        scale = jnp.exp(dp["log_a"] * cl)                  # (M, d)
        lr = jnp.repeat(scale * jnp.cos(dp["theta"] * cl), N, axis=0)
        li = jnp.repeat(scale * jnp.sin(dp["theta"] * cl), N, axis=0)
        # ... and add the chunk's own Vandermonde contribution
        vr, vi = modal_prefill_state(dp, kvc, cfg.hyena,
                                     lengths=jnp.full((B,), chunk_len,
                                                      jnp.int32))
        xr, xi = cache["x_re"], cache["x_im"]
        new_cache["x_re"] = lr * xr - li * xi + vr
        new_cache["x_im"] = lr * xi + li * xr + vi
    return new_cache, out


# ---------------------------------------------------------------------------
# Literal Sec.-4 outer-product operator (for Theorem 4.1 validation)
# ---------------------------------------------------------------------------
def outer_product_op(q, k, v, h, M: int):
    """q,k,v: (B, L, D); h: (M, L). Returns (B, L, D).

    y^m_t = (h^m * (k^m (x) v^m))_t q^m_t  — O(L log L * M * N^2) via FFT.
    Only intended for small widths (tests / associative recall).
    """
    B, L, D = q.shape
    N = D // M
    qh = q.reshape(B, L, M, N)
    kh = k.reshape(B, L, M, N)
    vh = v.reshape(B, L, M, N)
    z = jnp.einsum("blmi,blmj->blmij", kh, vh).reshape(B, L, M * N * N)
    hz = fft_conv(z, jnp.repeat(h, N * N, axis=0)).reshape(B, L, M, N, N)
    y = jnp.einsum("blmij,blmj->blmi", hz, qh)
    return y.reshape(B, L, D)
