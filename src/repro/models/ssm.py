"""SSM blocks: Mamba-2 (SSD, arXiv:2405.21060) and RG-LRU (RecurrentGemma).

Both provide a full-sequence mode (chunked-matmul SSD / associative scan) and
an O(1)-state decode step, which is what makes the long_500k cell lowerable.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import Param
from repro.kernels.mamba2_decode.ops import mamba2_state_step
from repro.models.layers import (
    NOCTX, ShardCtx, apply_short_conv, conv_tail_gather, dense_init,
    init_short_conv, short_conv_chunk, short_conv_step,
)


# ===========================================================================
# Mamba-2 (SSD)
# ===========================================================================
def init_mamba2_block(key, cfg):
    d = cfg.d_model
    s = cfg.ssm
    di = s.expand * d
    H = di // s.head_dim
    G = s.n_groups
    conv_dim = di + 2 * G * s.d_state
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return {
        # fused input projection: [z(di), x(di), B(G*N), C(G*N), dt(H)]
        "in_proj": dense_init(k1, (d, 2 * di + 2 * G * s.d_state + H),
                              ("embed", "mlp"), in_dim=d),
        "conv": init_short_conv(k2, conv_dim, s.d_conv, bias=s.conv_bias),
        "A_log": Param(jnp.log(jnp.linspace(1.0, 16.0, H)), ("heads",)),
        "D": Param(jnp.ones((H,)), ("heads",)),
        "dt_bias": Param(jnp.log(jnp.exp(jnp.linspace(1e-3, 0.1, H)) - 1.0 + 1e-9),
                         ("heads",)),
        "norm_scale": Param(jnp.ones((di,)), ("mlp",)),
        "out_proj": dense_init(k3, (di, d), ("mlp", "embed"), in_dim=di),
    }


def _split_mamba_proj(proj, cfg):
    s = cfg.ssm
    di = s.expand * cfg.d_model
    H = di // s.head_dim
    G, N = s.n_groups, s.d_state
    z, xBC, dt = jnp.split(proj, [di, 2 * di + 2 * G * N], axis=-1)
    return z, xBC, dt, di, H, G, N


def gated_rms_norm(y, z, scale, eps: float, dtype):
    """Mamba-2's gated RMSNorm: y * silu(z), normalised in float32 over the
    inner width and scaled, in `dtype`."""
    y = y * jax.nn.silu(z)
    var = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1, keepdims=True)
    return (y.astype(jnp.float32) * jax.lax.rsqrt(var + eps) *
            scale).astype(dtype)


def _segsum(a):
    """a: (..., Q) log-decays -> (..., Q, Q) lower-tri cumulative sums."""
    Q = a.shape[-1]
    cs = jnp.cumsum(a, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = np.tril(np.ones((Q, Q), dtype=bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(x, a_log, B, C, chunk: int, initial_state=None):
    """Chunked SSD (Mamba-2 Listing 1, JAX port).

    x: (b, L, H, P) pre-scaled by dt; a_log: (b, L, H) = dt*A (negative);
    B, C: (b, L, G, N). Returns y (b, L, H, P) and final state (b, H, P, N).
    `initial_state` (b, H, P, N) resumes from a previous segment (chunked
    prefill); omitted, the recurrence starts from zero as before.
    """
    b, L, H, P = x.shape
    G, N = B.shape[2], B.shape[3]
    Q = min(chunk, L)
    nc = L // Q
    assert L % Q == 0, (L, Q)
    xr = x.reshape(b, nc, Q, H, P)
    ar = a_log.reshape(b, nc, Q, H).transpose(0, 3, 1, 2)       # (b,H,nc,Q)
    Br = B.reshape(b, nc, Q, G, N)
    Cr = C.reshape(b, nc, Q, G, N)
    rep = H // G
    Brh = jnp.repeat(Br, rep, axis=3)                            # (b,nc,Q,H,N)
    Crh = jnp.repeat(Cr, rep, axis=3)

    # 1. intra-chunk (diagonal blocks)
    Lmat = jnp.exp(_segsum(ar))                                  # (b,H,nc,Q,Q)
    Ydiag = jnp.einsum("bcqhn,bckhn,bhcqk,bckhp->bcqhp", Crh, Brh, Lmat, xr)

    # 2. chunk states
    a_cum = jnp.cumsum(ar, axis=-1)                              # (b,H,nc,Q)
    a_tot = a_cum[..., -1]                                       # (b,H,nc)
    decay_to_end = jnp.exp(a_tot[..., None] - a_cum)             # (b,H,nc,Q)
    states = jnp.einsum("bckhn,bhck,bckhp->bchpn", Brh, decay_to_end, xr)

    # 3. inter-chunk recurrence on states (scan over chunks)
    def scan_fn(carry, inp):
        st, atot = inp                                           # (b,H,P,N), (b,H)
        new = carry * jnp.exp(atot)[..., None, None] + st
        return new, carry                                        # emit state BEFORE chunk

    from repro import flags
    init = (jnp.zeros((b, H, P, N), x.dtype) if initial_state is None
            else initial_state.astype(x.dtype))
    final, prev_states = jax.lax.scan(
        scan_fn, init,
        (states.transpose(1, 0, 2, 3, 4), a_tot.transpose(2, 0, 1)),
        unroll=flags.scan_unroll(nc))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)           # (b,nc,H,P,N)

    # 4. off-diagonal contribution
    decay_in = jnp.exp(a_cum)                                    # (b,H,nc,Q)
    Yoff = jnp.einsum("bcqhn,bchpn,bhcq->bcqhp", Crh, prev_states, decay_in)
    y = (Ydiag + Yoff).reshape(b, L, H, P)
    return y, final


def mamba2_block(params, x, cfg, *, ctx: ShardCtx = NOCTX, return_state=False,
                 lengths=None):
    """Full-sequence Mamba-2 block. x: (B, S, D).

    `lengths` (B,) marks true prompt lengths for bucketed prefill: padded
    positions get dt = 0, i.e. an identity transition (decay 1, input 0), so
    the final state is exactly the state at each row's true length.
    """
    Bsz, S, D = x.shape
    s = cfg.ssm
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(x.dtype))
    z, xBC, dt, di, H, G, N = _split_mamba_proj(proj, cfg)
    pre_conv = xBC
    xBC = jax.nn.silu(apply_short_conv(params["conv"], xBC))
    xs, B_, C_ = jnp.split(xBC, [di, di + G * N], axis=-1)
    B_ = B_.reshape(Bsz, S, G, N)
    C_ = C_.reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])   # (B,S,H)
    if lengths is not None:
        dt = jnp.where(jnp.arange(S)[None, :, None] < lengths[:, None, None],
                       dt, 0.0)
    A = -jnp.exp(params["A_log"])                                      # (H,)
    xh = xs.reshape(Bsz, S, H, s.head_dim).astype(jnp.float32)
    y, state = ssd_chunked(xh * dt[..., None], dt * A, B_.astype(jnp.float32),
                           C_.astype(jnp.float32), s.chunk)
    y = y + params["D"][None, None, :, None] * xh
    y = y.reshape(Bsz, S, di).astype(x.dtype)
    y = gated_rms_norm(y, z, params["norm_scale"], cfg.norm_eps, x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(x.dtype))
    if return_state:
        w = s.d_conv - 1
        cache = {"conv": conv_tail_gather(pre_conv, w, lengths).astype(jnp.float32),
                 "ssm": state.astype(jnp.float32)}
        return out, cache
    return out


def init_mamba2_cache(batch: int, cfg, dtype=jnp.float32) -> Dict:
    s = cfg.ssm
    di = s.expand * cfg.d_model
    H = di // s.head_dim
    conv_dim = di + 2 * s.n_groups * s.d_state
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, H, s.head_dim, s.d_state), dtype),
    }


def mamba2_decode(params, cache, x, cfg, *, ctx: ShardCtx = NOCTX):
    """One-token decode. x: (B, 1, D); O(1) state."""
    Bsz, _, D = x.shape
    s = cfg.ssm
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(x.dtype))[:, 0]
    z, xBC, dt, di, H, G, N = _split_mamba_proj(proj, cfg)
    conv_cache, xBC = short_conv_step(params["conv"], cache["conv"], xBC)
    xBC = jax.nn.silu(xBC)
    xs, B_, C_ = jnp.split(xBC, [di, di + G * N], axis=-1)
    B_ = B_.reshape(Bsz, G, N).astype(jnp.float32)
    C_ = C_.reshape(Bsz, G, N).astype(jnp.float32)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])  # (B,H)
    A = -jnp.exp(params["A_log"])
    a = jnp.exp(dt * A)                                                # (B,H)
    xh = xs.reshape(Bsz, H, s.head_dim).astype(jnp.float32)
    y, h = mamba2_state_step(cache["ssm"], xh, dt, a, B_, C_, params["D"])
    y = y.reshape(Bsz, di).astype(x.dtype)
    y = gated_rms_norm(y, z, params["norm_scale"], cfg.norm_eps, x.dtype)
    out = jnp.einsum("be,ed->bd", y, params["out_proj"].astype(x.dtype))
    return {"conv": conv_cache, "ssm": h}, out[:, None, :]


def mamba2_decode_chunk(params, cache, x, active_len, cfg, *,
                        ctx: ShardCtx = NOCTX):
    """Multi-token decode on the decode cache (speculative verify / replay).
    x: (B, C, D); active_len (B,) — positions past a row's active_len get
    dt = 0 (identity transition, zero input) so its conv tail and SSM state
    advance by exactly active_len tokens. Runs the chunk path through
    `ssd_chunked(initial_state=cache["ssm"])`."""
    from repro.models.hyena import _short_conv_rows
    Bsz, C, D = x.shape
    s = cfg.ssm
    active_len = jnp.asarray(active_len, jnp.int32)
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(x.dtype))
    z, xBC, dt, di, H, G, N = _split_mamba_proj(proj, cfg)
    new_tail, xBC, _ = _short_conv_rows(params["conv"], cache["conv"], xBC,
                                        active_len)
    xBC = jax.nn.silu(xBC)
    xs, B_, C_ = jnp.split(xBC, [di, di + G * N], axis=-1)
    B_ = B_.reshape(Bsz, C, G, N)
    C_ = C_.reshape(Bsz, C, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    dt = jnp.where(jnp.arange(C)[None, :, None] < active_len[:, None, None],
                   dt, 0.0)
    A = -jnp.exp(params["A_log"])
    xh = xs.reshape(Bsz, C, H, s.head_dim).astype(jnp.float32)
    y, state = ssd_chunked(xh * dt[..., None], dt * A, B_.astype(jnp.float32),
                           C_.astype(jnp.float32), C,
                           initial_state=cache["ssm"])
    y = y + params["D"][None, None, :, None] * xh
    y = y.reshape(Bsz, C, di).astype(x.dtype)
    y = gated_rms_norm(y, z, params["norm_scale"], cfg.norm_eps, x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(x.dtype))
    return {"conv": new_tail.astype(jnp.float32),
            "ssm": state.astype(jnp.float32)}, out


def rglru_decode_chunk(params, cache, x, active_len, cfg, *,
                       ctx: ShardCtx = NOCTX):
    """Multi-token RG-LRU decode on the decode cache. Positions past a row's
    active_len become identity transitions (a = 1, input 0), so h[:, -1] is
    the state after exactly active_len tokens."""
    from repro.models.hyena import _short_conv_rows
    C = x.shape[1]
    active_len = jnp.asarray(active_len, jnp.int32)
    xb = jnp.einsum("bsd,de->bse", x, params["wx"].astype(x.dtype))
    yb = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, params["wy"].astype(x.dtype)))
    new_tail, xc, _ = _short_conv_rows(params["conv"], cache["conv"], xb,
                                       active_len)
    log_a, gated = _rglru_gates(params, xc)
    valid = (jnp.arange(C)[None, :, None] < active_len[:, None, None])
    log_a = jnp.where(valid, log_a, 0.0)
    gated = jnp.where(valid, gated, 0.0)
    a = jnp.exp(log_a)
    a_cum, h = jax.lax.associative_scan(_rglru_combine, (a, gated), axis=1)
    h = h + a_cum * cache["h"][:, None, :]
    out = h.astype(x.dtype) * yb
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return {"conv": new_tail.astype(jnp.float32),
            "h": h[:, -1, :].astype(jnp.float32)}, out


def mamba2_prefill_chunk(params, cache, x, chunk_len, cfg, *,
                         ctx: ShardCtx = NOCTX):
    """Consume one prompt chunk x (B, C, D) resuming from cache{conv, ssm}.
    Positions >= chunk_len are padding (identity transitions)."""
    Bsz, C, D = x.shape
    s = cfg.ssm
    proj = jnp.einsum("bsd,de->bse", x, params["in_proj"].astype(x.dtype))
    z, xBC, dt, di, H, G, N = _split_mamba_proj(proj, cfg)
    new_tail, xBC = short_conv_chunk(params["conv"], cache["conv"], xBC,
                                     chunk_len)
    xBC = jax.nn.silu(xBC)
    xs, B_, C_ = jnp.split(xBC, [di, di + G * N], axis=-1)
    B_ = B_.reshape(Bsz, C, G, N)
    C_ = C_.reshape(Bsz, C, G, N)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    dt = jnp.where(jnp.arange(C)[None, :, None] < chunk_len, dt, 0.0)
    A = -jnp.exp(params["A_log"])
    xh = xs.reshape(Bsz, C, H, s.head_dim).astype(jnp.float32)
    y, state = ssd_chunked(xh * dt[..., None], dt * A, B_.astype(jnp.float32),
                           C_.astype(jnp.float32), s.chunk,
                           initial_state=cache["ssm"])
    y = y + params["D"][None, None, :, None] * xh
    y = y.reshape(Bsz, C, di).astype(x.dtype)
    y = gated_rms_norm(y, z, params["norm_scale"], cfg.norm_eps, x.dtype)
    out = jnp.einsum("bse,ed->bsd", y, params["out_proj"].astype(x.dtype))
    return {"conv": new_tail.astype(jnp.float32),
            "ssm": state.astype(jnp.float32)}, out


# ===========================================================================
# RG-LRU (RecurrentGemma / Griffin)
# ===========================================================================
_RG_C = 8.0


def init_rglru_block(key, cfg):
    d = cfg.d_model
    r = cfg.rglru
    di = r.expand * d
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    return {
        "wx": dense_init(k1, (d, di), ("embed", "mlp"), in_dim=d),
        "wy": dense_init(k2, (d, di), ("embed", "mlp"), in_dim=d),
        "conv": init_short_conv(k3, di, r.d_conv),
        "wa": dense_init(k4, (di, di), ("mlp", "mlp"), in_dim=di),
        "wi": dense_init(k5, (di, di), ("mlp", "mlp"), in_dim=di),
        # Lambda init so that a = sigmoid(lam)^c is in [0.9, 0.999]
        "lam": Param(jnp.linspace(2.0, 6.0, di), ("mlp",)),
        "wo": dense_init(k6, (di, d), ("mlp", "embed"), in_dim=di),
    }


def _rglru_gates(params, xc):
    """Returns (log_a, gated_input): log_a (B,S,di) <= 0."""
    r_gate = jax.nn.sigmoid(jnp.einsum("...e,ef->...f", xc, params["wa"].astype(xc.dtype)))
    i_gate = jax.nn.sigmoid(jnp.einsum("...e,ef->...f", xc, params["wi"].astype(xc.dtype)))
    log_a = -_RG_C * r_gate.astype(jnp.float32) * jax.nn.softplus(params["lam"])
    a2 = jnp.exp(2.0 * log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a2, 1e-9)) * (i_gate * xc).astype(jnp.float32)
    return log_a, gated


def _rglru_combine(c1, c2):
    a1, b1 = c1
    a2, b2 = c2
    return a1 * a2, a2 * b1 + b2


def rglru_block(params, x, cfg, *, ctx: ShardCtx = NOCTX, return_state=False,
                lengths=None):
    """Full-sequence RG-LRU block via associative scan. x: (B,S,D).

    With `lengths` (B,), padded positions become identity transitions
    (a = 1, input 0) so the final state is the state at the true length.
    """
    xb = jnp.einsum("bsd,de->bse", x, params["wx"].astype(x.dtype))
    yb = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, params["wy"].astype(x.dtype)))
    xc = apply_short_conv(params["conv"], xb)
    log_a, gated = _rglru_gates(params, xc)
    if lengths is not None:
        valid = (jnp.arange(x.shape[1])[None, :, None] <
                 lengths[:, None, None])
        log_a = jnp.where(valid, log_a, 0.0)
        gated = jnp.where(valid, gated, 0.0)
    a = jnp.exp(log_a)

    _, h = jax.lax.associative_scan(_rglru_combine, (a, gated), axis=1)
    out = h.astype(x.dtype) * yb
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    if return_state:
        w = cfg.rglru.d_conv - 1
        cache = {"conv": conv_tail_gather(xb, w, lengths).astype(jnp.float32),
                 "h": h[:, -1, :].astype(jnp.float32)}
        return out, cache
    return out


def init_rglru_cache(batch: int, cfg, dtype=jnp.float32) -> Dict:
    r = cfg.rglru
    di = r.expand * cfg.d_model
    return {
        "conv": jnp.zeros((batch, r.d_conv - 1, di), dtype),
        "h": jnp.zeros((batch, di), dtype),
    }


def rglru_decode(params, cache, x, cfg, *, ctx: ShardCtx = NOCTX):
    xb = jnp.einsum("bsd,de->bse", x, params["wx"].astype(x.dtype))[:, 0]
    yb = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, params["wy"].astype(x.dtype)))[:, 0]
    conv_cache, xc = short_conv_step(params["conv"], cache["conv"], xb)
    log_a, gated = _rglru_gates(params, xc)
    h = jnp.exp(log_a) * cache["h"] + gated
    out = h.astype(x.dtype) * yb
    out = jnp.einsum("be,ed->bd", out, params["wo"].astype(x.dtype))
    return {"conv": conv_cache, "h": h}, out[:, None, :]


def rglru_prefill_chunk(params, cache, x, chunk_len, cfg, *,
                        ctx: ShardCtx = NOCTX):
    """Consume one prompt chunk x (B, C, D) resuming from cache{conv, h}.
    Positions >= chunk_len are padding (identity transitions)."""
    C = x.shape[1]
    xb = jnp.einsum("bsd,de->bse", x, params["wx"].astype(x.dtype))
    yb = jax.nn.gelu(jnp.einsum("bsd,de->bse", x, params["wy"].astype(x.dtype)))
    new_tail, xc = short_conv_chunk(params["conv"], cache["conv"], xb,
                                    chunk_len)
    log_a, gated = _rglru_gates(params, xc)
    valid = (jnp.arange(C) < chunk_len)[None, :, None]
    log_a = jnp.where(valid, log_a, 0.0)
    gated = jnp.where(valid, gated, 0.0)
    a = jnp.exp(log_a)
    a_cum, h = jax.lax.associative_scan(_rglru_combine, (a, gated), axis=1)
    h = h + a_cum * cache["h"][:, None, :]          # fold in the carried state
    out = h.astype(x.dtype) * yb
    out = jnp.einsum("bse,ed->bsd", out, params["wo"].astype(x.dtype))
    return {"conv": new_tail.astype(jnp.float32),
            "h": h[:, -1, :].astype(jnp.float32)}, out
