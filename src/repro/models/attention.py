"""GQA attention: full/windowed causal for train & prefill, cached decode.

The quadratic path is a plain einsum formulation XLA fuses well; a Pallas
flash-attention kernel (repro.kernels.flash_attention) can be swapped in via
`use_flash=True` on real TPUs (validated in interpret mode in tests).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import Param
from repro.models.layers import NOCTX, ShardCtx, apply_rope, dense_init


def init_attention(key, d: int, n_heads: int, n_kv: int, hd: int):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": dense_init(kq, (d, n_heads, hd), ("embed", "heads", None), in_dim=d),
        "wk": dense_init(kk, (d, n_kv, hd), ("embed", "kv_heads", None), in_dim=d),
        "wv": dense_init(kv, (d, n_kv, hd), ("embed", "kv_heads", None), in_dim=d),
        "wo": dense_init(ko, (n_heads, hd, d), ("heads", None, "embed"),
                         in_dim=n_heads * hd),
    }


def _rope(x, positions, cfg):
    """RoPE as the config asks; none at all for a NoPE model."""
    if cfg.nope:
        return x
    return apply_rope(x, positions, cfg.rope_theta,
                      cfg.m_rope_sections if cfg.m_rope else None)


def _gqa_scores(q, k, scale=None):
    """q: (B,S,Hq,hd), k: (B,T,Hkv,hd) -> scores (B,Hkv,G,S,T), times
    `scale` (None: divided by sqrt(hd))."""
    B, S, Hq, hd = q.shape
    Hkv = k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, S, Hkv, G, hd)
    s = jnp.einsum("bskgh,btkh->bkgst", qg, k)
    return s / np.sqrt(hd) if scale is None else s * scale


def _gqa_out(probs, v):
    """probs: (B,Hkv,G,S,T), v: (B,T,Hkv,hd) -> (B,S,Hq,hd)."""
    B, Hkv, G, S, T = probs.shape
    out = jnp.einsum("bkgst,btkh->bskgh", probs, v)
    return out.reshape(B, S, Hkv * G, out.shape[-1])


def causal_mask(S: int, T: int, offset: int = 0, window: int = 0):
    """(S, T) boolean mask. offset = index of query 0 within the key axis."""
    qpos = jnp.arange(S)[:, None] + offset
    kpos = jnp.arange(T)[None, :]
    m = kpos <= qpos
    if window > 0:
        m = m & (kpos > qpos - window)
    return m


def mha(q, k, v, *, causal=True, offset=0, window=0, ctx: ShardCtx = NOCTX,
        cross=False, scale=None):
    scores = _gqa_scores(q, k, scale).astype(jnp.float32)
    if causal and not cross:
        m = causal_mask(q.shape[1], k.shape[1], offset, window)
        scores = jnp.where(m[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return _gqa_out(probs, v)


# ---------------------------------------------------------------------------
# Chunked (flash-style) attention: O(S * block) memory instead of O(S^2).
#
# Pure-JAX online-softmax over kv blocks with a scalar-predicate lax.cond that
# skips fully-masked blocks at runtime (the causal upper triangle / outside
# the local window). This is the portable path; the Pallas kernel in
# repro.kernels.flash_attention is the TPU-tuned variant of the same
# algorithm.
# ---------------------------------------------------------------------------
def chunked_mha(q, k, v, *, causal=True, offset=0, window=0, block=1024,
                scale=None):
    """q: (B,S,Hq,hd); k,v: (B,T,Hkv,hd). Returns (B,S,Hq,hd). `scale` is
    the softmax scale (None: 1/sqrt(hd))."""
    from repro import flags
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if flags.DRYRUN_UNROLL:
        # python-loop blocks: exact causal FLOPs, fully visible to
        # cost_analysis (scan bodies are otherwise counted once). Block size
        # balances causal over-compute ((nq+1)/nq) against HLO size.
        blk = int(np.clip(S // 4, 1024, 4096))
        return _chunked_mha_unrolled(q, k, v, causal=causal, offset=offset,
                                     window=window, block=blk, scale=scale)
    qb = min(block, S)
    kb = min(block, T)
    assert S % qb == 0 and T % kb == 0, (S, T, block)
    nq, nk = S // qb, T // kb
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    qr = q.reshape(B, nq, qb, Hkv, G, hd)
    kr = k.reshape(B, nk, kb, Hkv, hd)
    vr = v.reshape(B, nk, kb, Hkv, hd)

    def q_block(args):
        qi, qblk = args                                  # (B, qb, Hkv, G, hd)
        m0 = jnp.full((B, Hkv, G, qb), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, Hkv, G, qb), jnp.float32)
        a0 = jnp.zeros((B, Hkv, G, qb, hd), jnp.float32)

        def kv_step(carry, j):
            def compute(carry):
                m, l, acc = carry
                kblk = kr[:, j]
                vblk = vr[:, j]
                s = jnp.einsum("bqkgh,btkh->bkgqt", qblk, kblk).astype(
                    jnp.float32) * scale
                qpos = offset + qi * qb + jnp.arange(qb)
                kpos = j * kb + jnp.arange(kb)
                valid = jnp.ones((qb, kb), bool)
                if causal:
                    valid = valid & (kpos[None, :] <= qpos[:, None])
                if window > 0:
                    valid = valid & (kpos[None, :] > qpos[:, None] - window)
                s = jnp.where(valid[None, None, None], s, -jnp.inf)
                mj = jnp.maximum(m, jnp.max(s, axis=-1))
                # guard fully-masked rows
                mj_safe = jnp.where(jnp.isfinite(mj), mj, 0.0)
                p = jnp.exp(s - mj_safe[..., None])
                p = jnp.where(valid[None, None, None], p, 0.0)
                corr = jnp.where(jnp.isfinite(m), jnp.exp(m - mj_safe), 0.0)
                l = l * corr + jnp.sum(p, axis=-1)
                acc = acc * corr[..., None] + jnp.einsum(
                    "bkgqt,btkh->bkgqh", p.astype(q.dtype), vblk).astype(jnp.float32)
                return mj, l, acc

            lo = offset + qi * qb                        # first query position
            hi = offset + qi * qb + qb - 1               # last query position
            needed = jnp.ones((), bool)
            if causal:
                needed = needed & (j * kb <= hi)
            if window > 0:
                needed = needed & ((j + 1) * kb - 1 > lo - window)
            return jax.lax.cond(needed, compute, lambda c: c, carry), None

        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(nk))
        out = acc / jnp.maximum(l[..., None], 1e-30)
        # (B, Hkv, G, qb, hd) -> (B, qb, Hq, hd)
        return jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, qb, Hq, hd)

    outs = jax.lax.map(q_block, (jnp.arange(nq), jnp.moveaxis(qr, 1, 0)))
    return jnp.moveaxis(outs, 0, 1).reshape(B, S, Hq, hd).astype(q.dtype)


def _chunked_mha_unrolled(q, k, v, *, causal=True, offset=0, window=0,
                          block=4096, scale=None):
    """Python-loop flash attention: only causally-needed (i, j) block pairs are
    emitted, so HLO FLOPs match a real blocked causal kernel."""
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qb = min(block, S)
    kb = min(block, T)
    nq, nk = S // qb, T // kb
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    qr = q.reshape(B, nq, qb, Hkv, G, hd)
    kr = k.reshape(B, nk, kb, Hkv, hd)
    vr = v.reshape(B, nk, kb, Hkv, hd)
    outs = []
    for i in range(nq):
        lo = offset + i * qb
        hi = lo + qb - 1
        m = jnp.full((B, Hkv, G, qb), -jnp.inf, jnp.float32)
        l = jnp.zeros((B, Hkv, G, qb), jnp.float32)
        acc = jnp.zeros((B, Hkv, G, qb, hd), jnp.float32)
        for j in range(nk):
            if causal and j * kb > hi:
                continue                      # strictly above the diagonal
            if window > 0 and (j + 1) * kb - 1 <= lo - window:
                continue                      # entirely left of the window
            s = jnp.einsum("bqkgh,btkh->bkgqt", qr[:, i], kr[:, j]).astype(
                jnp.float32) * scale
            qpos = lo + jnp.arange(qb)
            kpos = j * kb + jnp.arange(kb)
            valid = jnp.ones((qb, kb), bool)
            if causal:
                valid = valid & (kpos[None, :] <= qpos[:, None])
            if window > 0:
                valid = valid & (kpos[None, :] > qpos[:, None] - window)
            s = jnp.where(valid[None, None, None], s, -jnp.inf)
            mj = jnp.maximum(m, jnp.max(s, axis=-1))
            mj_safe = jnp.where(jnp.isfinite(mj), mj, 0.0)
            p = jnp.exp(s - mj_safe[..., None])
            p = jnp.where(valid[None, None, None], p, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - mj_safe), 0.0)
            l = l * corr + jnp.sum(p, axis=-1)
            acc = acc * corr[..., None] + jnp.einsum(
                "bkgqt,btkh->bkgqh", p.astype(q.dtype), vr[:, j]).astype(jnp.float32)
            m = mj
        out = acc / jnp.maximum(l[..., None], 1e-30)
        outs.append(jnp.transpose(out, (0, 3, 1, 2, 4)).reshape(B, qb, Hq, hd))
    return jnp.concatenate(outs, axis=1).astype(q.dtype)


def attention_block(params, x, positions, cfg, *, window=0, ctx: ShardCtx = NOCTX,
                    cross_kv=None, causal=True, return_kv=False,
                    kv_valid=None):
    """Full-sequence attention (train / prefill). x: (B,S,D).

    kv_valid (B, S) marks each row's real (non-padded) positions for
    bucketed prefill: the k/v returned for the decode cache are zeroed at
    padded positions. The attention itself needs no extra mask — with right
    padding, causality already keeps padded keys away from real queries.
    """
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    if cross_kv is None:
        k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(x.dtype))
        v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(x.dtype))
        q = _rope(q, positions, cfg)
        k = _rope(k, positions, cfg)
    else:
        k, v = cross_kv
    # TP sharding of attention FLOPs: shard heads when they divide the model
    # axis, otherwise fall back to context parallelism (shard q's sequence
    # axis; k/v stay replicated over the model axis and every device computes
    # its own q-rows — works for any head count, e.g. 24-head llama on TP=16).
    model_sz = 1
    if ctx.mesh is not None:
        sizes = dict(zip(ctx.mesh.axis_names, ctx.mesh.devices.shape))
        model_sz = sizes.get("model", 1)
    if q.shape[2] % max(model_sz, 1) == 0:
        q = ctx.cs(q, ("batch", None, "heads", None))
    else:
        q = ctx.cs(q, ("batch", "qseq", "heads", None))
    k = ctx.cs(k, ("batch", None, "kv_heads", None))
    v = ctx.cs(v, ("batch", None, "kv_heads", None))
    is_causal = causal and cross_kv is None
    scale = cfg.attention_multiplier
    if q.shape[1] >= 4096 and q.shape[1] % 1024 == 0 and k.shape[1] % 1024 == 0:
        o = chunked_mha(q, k, v, causal=is_causal, window=window, scale=scale)
    else:
        o = mha(q, k, v, causal=is_causal, window=window, ctx=ctx,
                cross=cross_kv is not None, scale=scale)
    y = jnp.einsum("bsnh,nhd->bsd", o, params["wo"].astype(x.dtype))
    if return_kv:
        if kv_valid is not None:
            k = jnp.where(kv_valid[..., None, None], k, 0)
            v = jnp.where(kv_valid[..., None, None], v, 0)
        return y, (k, v)
    return y


def compute_kv(params, x, positions, cfg):
    """Project k, v only (cross-attention cache construction)."""
    k = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(x.dtype))
    return k, v


# ---------------------------------------------------------------------------
# Chunked (resumable) prefill: one fixed-size chunk of the prompt at a time
# ---------------------------------------------------------------------------
def attention_prefill_chunk(params, cache, x, positions, start, chunk_len,
                            cfg, *, window=0, ctx: ShardCtx = NOCTX):
    """Consume one prompt chunk x (B, C, D) starting at absolute position
    `start` (traced scalar). cache k/v are full-length LINEAR buffers — even
    for windowed layers, which are re-laid-out into ring form by
    `finalize_prefill_cache`. Positions of the chunk at index >= chunk_len
    are padding: their k/v are written as zeros (and excluded from every
    real query by causality). Returns (cache, y (B, C, D))."""
    B, C, _ = x.shape
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    k_new = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(x.dtype))
    v_new = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(x.dtype))
    q = _rope(q, positions, cfg)
    k_new = _rope(k_new, positions, cfg)
    valid = (jnp.arange(C) < chunk_len)[None, :, None, None]
    k_new = jnp.where(valid, k_new, 0).astype(cache["k"].dtype)
    v_new = jnp.where(valid, v_new, 0).astype(cache["v"].dtype)
    k = jax.lax.dynamic_update_slice_in_dim(cache["k"], k_new, start, axis=1)
    v = jax.lax.dynamic_update_slice_in_dim(cache["v"], v_new, start, axis=1)
    # chunk queries against the whole buffer: unfilled keys sit strictly in
    # the causal future of every chunk query, so kpos <= qpos masks them
    y = mha(q, k.astype(q.dtype), v.astype(q.dtype), causal=True,
            offset=start, window=window, ctx=ctx,
            scale=cfg.attention_multiplier)
    y = jnp.einsum("bsnh,nhd->bsd", y, params["wo"].astype(x.dtype))
    return {"k": k, "v": v}, y


# ---------------------------------------------------------------------------
# Multi-token decode on the decode cache (speculative verify / replay)
# ---------------------------------------------------------------------------
def attention_decode_chunk(params, cache, x, pos, active_len, cfg, *,
                           window=0, ctx: ShardCtx = NOCTX):
    """Consume up to C tokens per slot against the DECODE cache (linear or
    ring layout). x: (B, C, D); pos: (B,) per-slot positions; active_len:
    (B,) — row b consumes only its first active_len tokens: positions at
    index >= active_len leave the k/v buffers (and ring slot_pos) untouched,
    which is what lets a speculative verify be replayed with a shorter
    accepted prefix. Returns (cache, y (B, C, D)) with logits-bearing
    outputs at every position (invalid positions produce garbage that the
    caller masks)."""
    B, C, _ = x.shape
    pos = jnp.asarray(pos, jnp.int32)
    active_len = jnp.asarray(active_len, jnp.int32)
    positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]  # (B,C)
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    k_new = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(x.dtype))
    v_new = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(x.dtype))
    q = _rope(q, positions, cfg)
    k_new = _rope(k_new, positions, cfg)
    ring = "slot_pos" in cache
    size = cache["k"].shape[1]
    # Attention READS the pre-write cache plus the chunk's own keys as a
    # separate segment: scattering first would let a later chunk position's
    # ring write evict a key still inside an earlier position's window
    # (ring size == window), silently truncating that query's context.
    T = cache["k"].shape[1]
    Hkv = cache["k"].shape[2]
    G = q.shape[2] // Hkv
    qg = q.reshape(B, C, Hkv, G, q.shape[-1])
    scale = (1.0 / np.sqrt(q.shape[-1]) if cfg.attention_multiplier is None
             else cfg.attention_multiplier)
    s_old = jnp.einsum("bckgh,btkh->bkgct", qg,
                       cache["k"].astype(q.dtype)).astype(jnp.float32) * scale
    # past-segment mask: only positions strictly BEFORE this chunk (also
    # drops stale rows an evicted occupant left at indices >= pos)
    if ring:
        sp = cache["slot_pos"]                                      # (B, eff)
        m_old = (sp[:, None, :] >= 0) & (sp[:, None, :] < pos[:, None, None])
        if window > 0:
            m_old = m_old & (sp[:, None, :] > positions[:, :, None] - window)
    else:
        kpos = jnp.arange(T, dtype=jnp.int32)
        m_old = kpos[None, None, :] < pos[:, None, None]            # (B,C,T)
        if window > 0:
            m_old = m_old & (kpos[None, None, :] >
                             positions[:, :, None] - window)
    s_old = jnp.where(m_old[:, None, None, :, :], s_old, -1e30)
    # in-chunk segment: key i visible to query c iff i <= c (and in-window).
    # Round-trip through the cache dtype first: the sequential decode path
    # reads these keys back from the (bf16) cache, and greedy identity with
    # it requires matching that precision.
    k_chunk = k_new.astype(cache["k"].dtype)
    v_chunk = v_new.astype(cache["v"].dtype)
    s_new = jnp.einsum("bckgh,bikh->bkgci", qg,
                       k_chunk.astype(q.dtype)).astype(jnp.float32) * scale
    ii = jnp.arange(C, dtype=jnp.int32)
    m_new = ii[None, :] <= ii[:, None]                              # (C, C)
    if window > 0:
        m_new = m_new & (ii[None, :] > ii[:, None] - window)
    s_new = jnp.where(m_new[None, None, None], s_new, -1e30)
    scores = jnp.concatenate([s_old, s_new], axis=-1)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    vv = jnp.concatenate([cache["v"].astype(q.dtype),
                          v_chunk.astype(q.dtype)], axis=1)
    o = jnp.einsum("bkgct,btkh->bckgh", probs, vv)
    o = o.reshape(B, C, Hkv * G, o.shape[-1])
    y = jnp.einsum("bsnh,nhd->bsd", o, params["wo"].astype(x.dtype))

    # per-row write indices; idle rows past the buffer end clamp (linear) or
    # wrap (ring) — both are masked out at read time and fully rewritten
    widx = positions % size if ring else jnp.clip(positions, 0, size - 1)
    valid = jnp.arange(C)[None, :] < active_len[:, None]               # (B,C)
    b = jnp.arange(B)[:, None]

    def scatter(buf, new):
        tgt = (B, C) + buf.shape[2:]
        idx = jnp.broadcast_to(widx.reshape((B, C) + (1,) * (buf.ndim - 2)),
                               tgt)
        cur = jnp.take_along_axis(buf, idx, axis=1)
        sel = jnp.where(valid.reshape((B, C) + (1,) * (buf.ndim - 2)),
                        new.astype(buf.dtype), cur)
        return buf.at[b, widx].set(sel)

    new_cache = {"k": scatter(cache["k"], k_new),
                 "v": scatter(cache["v"], v_new)}
    if ring:
        new_cache["slot_pos"] = scatter(cache["slot_pos"], positions)
    return new_cache, y


# ---------------------------------------------------------------------------
# Decode with KV cache
# ---------------------------------------------------------------------------
def init_kv_cache(batch: int, max_len: int, n_kv: int, hd: int, dtype=jnp.bfloat16):
    return {
        "k": jnp.zeros((batch, max_len, n_kv, hd), dtype),
        "v": jnp.zeros((batch, max_len, n_kv, hd), dtype),
    }


def attention_decode(params, cache, x, pos, cfg, *, window=0,
                     ctx: ShardCtx = NOCTX, cross_kv=None):
    """One-token decode. x: (B,1,D); pos: scalar int32 (current index) or a
    per-slot (B,) vector — the continuous-batching engine runs every request
    at its own position within one batched step.

    Two cache layouts:
      * linear  — cache length == max_len, written at `pos`, masked by index.
      * ring    — cache carries "slot_pos" (B, eff) (absolute position per
                  ring slot); used for windowed layers so a 500k-context
                  hybrid keeps an O(window) cache. Written at pos % size,
                  masked by slot_pos.
    """
    B = x.shape[0]
    pos = jnp.asarray(pos, jnp.int32)
    per_slot = pos.ndim == 1
    q = jnp.einsum("bsd,dnh->bsnh", x, params["wq"].astype(x.dtype))
    positions = pos[:, None] if per_slot else jnp.full((B, 1), pos, jnp.int32)
    ring = cross_kv is None and "slot_pos" in cache
    if cross_kv is None:
        k_new = jnp.einsum("bsd,dnh->bsnh", x, params["wk"].astype(x.dtype))
        v_new = jnp.einsum("bsd,dnh->bsnh", x, params["wv"].astype(x.dtype))
        q = _rope(q, positions, cfg)
        k_new = _rope(k_new, positions, cfg)
        size = cache["k"].shape[1]
        if per_slot:
            # per-slot write index: scatter one (k, v) row per batch element.
            # Inactive slots may sit past max_len; clamp — they are masked at
            # the scheduler level and fully overwritten on (re)admission.
            widx = pos % size if ring else jnp.minimum(pos, size - 1)
            b = jnp.arange(B)
            k = cache["k"].at[b, widx].set(k_new[:, 0].astype(cache["k"].dtype))
            v = cache["v"].at[b, widx].set(v_new[:, 0].astype(cache["v"].dtype))
            new_cache = {"k": k, "v": v}
            if ring:
                new_cache["slot_pos"] = cache["slot_pos"].at[b, widx].set(pos)
        else:
            widx = pos % size if ring else pos
            k = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k_new.astype(cache["k"].dtype), widx, axis=1)
            v = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v_new.astype(cache["v"].dtype), widx, axis=1)
            new_cache = {"k": k, "v": v}
            if ring:
                new_cache["slot_pos"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["slot_pos"],
                    jnp.broadcast_to(pos, (B, 1)).astype(jnp.int32), widx,
                    axis=1)
    else:
        k, v = cross_kv
        new_cache = {}
    T = k.shape[1]
    scores = _gqa_scores(q, k.astype(q.dtype), cfg.attention_multiplier
                         ).astype(jnp.float32)                     # (B,Hkv,G,1,T)
    if cross_kv is None:
        pos_b = pos[:, None] if per_slot else pos          # (B,1) | scalar
        if ring:
            sp = new_cache["slot_pos"]                     # (B, eff)
            valid = (sp >= 0) & (sp <= pos_b)
            if window > 0:
                valid = valid & (sp > pos_b - window)
        else:
            kpos = jnp.arange(T)[None, :]
            valid = jnp.broadcast_to(kpos <= pos_b, (B, T))
            if window > 0:
                valid = valid & (kpos > pos_b - window)
        scores = jnp.where(valid[:, None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    o = _gqa_out(probs, v.astype(q.dtype))
    y = jnp.einsum("bsnh,nhd->bsd", o, params["wo"].astype(x.dtype))
    return new_cache, y
