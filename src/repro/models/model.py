"""Top-level model: init / forward / train_loss / prefill / decode_step.

Every architecture in the pool is an instance of this assembly:
  embed -> [scan over pattern groups of blocks] -> remainder blocks -> norm -> logits
with optional encoder (whisper) and modality-frontend stubs (qwen2-vl audio).

Layer stacking: parameters of one pattern period ("group") are initialized per
group and stacked on a leading axis, then consumed by jax.lax.scan — keeping
HLO size O(pattern) instead of O(n_layers), which matters when compiling
80-layer models for 512 devices.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import (ATTN, HYENA, LOCAL_ATTN, MAMBA2, MLP_MOE,
                                RGLRU, ModelConfig)
from repro.distributed.sharding import Param
from repro.models import attention as attn_mod
from repro.models import hyena as hyena_mod
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (NOCTX, ShardCtx, apply_mlp, apply_norm,
                                 embed_tokens, init_embed, init_mlp, init_norm,
                                 unembed)

is_param = lambda x: isinstance(x, Param)


def stack_groups(groups):
    """Stack a list of Param trees along a new leading (layer) axis."""
    def stack(*ps):
        return Param(jnp.stack([p.value for p in ps]), (None,) + tuple(ps[0].axes))
    return jax.tree.map(stack, *groups, is_leaf=is_param)


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_block(key, kind: str, cfg: ModelConfig, cross: bool = False):
    ks = jax.random.split(key, 6)
    p: Dict[str, Any] = {"norm1": init_norm(cfg.norm, cfg.d_model)}
    if kind in (ATTN, LOCAL_ATTN):
        p["mix"] = attn_mod.init_attention(ks[0], cfg.d_model, cfg.n_heads,
                                           cfg.n_kv_heads, cfg.hd)
    elif kind == HYENA:
        p["mix"] = hyena_mod.init_hyena_block(ks[0], cfg)
    elif kind == MAMBA2:
        p["mix"] = ssm_mod.init_mamba2_block(ks[0], cfg)
    elif kind == RGLRU:
        p["mix"] = ssm_mod.init_rglru_block(ks[0], cfg)
    else:
        raise ValueError(kind)
    if cross:
        p["cross_norm"] = init_norm(cfg.norm, cfg.d_model)
        p["cross"] = attn_mod.init_attention(ks[1], cfg.d_model, cfg.n_heads,
                                             cfg.n_kv_heads, cfg.hd)
    if cfg.d_ff > 0:
        p["norm2"] = init_norm(cfg.norm, cfg.d_model)
        if cfg.mlp_kind == MLP_MOE:
            p["mlp"] = moe_mod.init_moe(ks[2], cfg.d_model, cfg.d_ff, cfg.moe)
        else:
            p["mlp"] = init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.act)
    return p


def _init_group(key, cfg: ModelConfig, cross: bool):
    ks = jax.random.split(key, len(cfg.pattern))
    return {f"l{i}": _init_block(ks[i], kind, cfg, cross)
            for i, kind in enumerate(cfg.pattern)}


def layer_layout(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_groups scanned, n_remainder unstacked layers)."""
    period = len(cfg.pattern)
    return cfg.n_layers // period, cfg.n_layers % period


def init_params(key, cfg: ModelConfig):
    """Returns a Param tree (values + logical axes)."""
    n_groups, n_rem = layer_layout(cfg)
    keys = jax.random.split(key, n_groups + n_rem + 4)
    cross = cfg.enc_dec
    groups = [_init_group(keys[i], cfg, cross) for i in range(n_groups)]
    params: Dict[str, Any] = {
        "embed": init_embed(keys[-1], cfg.vocab, cfg.d_model, cfg.tie_embeddings,
                            max_seq=max(cfg.max_seq, 1),
                            learned_pos=cfg.learned_pos),
        "final_norm": init_norm(cfg.norm, cfg.d_model),
        "groups": stack_groups(groups),
    }
    if n_rem:
        params["rem"] = [
            _init_block(keys[n_groups + i], cfg.blocks[n_groups * len(cfg.pattern) + i],
                        cfg, cross)
            for i in range(n_rem)
        ]
    if cfg.enc_dec:
        ekeys = jax.random.split(keys[-2], cfg.n_enc_layers)
        enc = [_init_block(ekeys[i], ATTN, cfg, cross=False)
               for i in range(cfg.n_enc_layers)]
        params["encoder"] = stack_groups(enc)
        params["enc_norm"] = init_norm(cfg.norm, cfg.d_model)
        params["enc_pos"] = Param(
            jax.random.normal(keys[-3], (cfg.frontend_len, cfg.d_model)) * 0.02,
            (None, "embed"))
    return params


# ---------------------------------------------------------------------------
# Forward (full sequence)
# ---------------------------------------------------------------------------
def _residual(x, y, cfg: ModelConfig):
    """x + y, the branch scaled by the config's residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def _apply_block(bp, kind: str, x, positions, cfg: ModelConfig, ctx: ShardCtx,
                 *, enc_out=None, moe_impl: str, collect_cache: bool = False,
                 cross_kv_cache=None, cache_kind: str = "native",
                 lengths=None, filter_len=None):
    """One block (mix + mlp). Returns (x, aux_loss, cache_or_None).

    `lengths` (B,) marks true per-row prompt lengths for bucketed (right-
    padded) prefill; it only affects what the collected caches contain —
    padded positions never reach the SSM states, conv tails, or KV caches.
    `filter_len` pins the Hyena filter materialization length (serving).
    """
    h = apply_norm(bp["norm1"], x, cfg.norm, cfg.norm_eps)
    cache = None
    window = cfg.window if kind == LOCAL_ATTN else 0
    kv_valid = (None if lengths is None else
                jnp.arange(x.shape[1])[None, :] < lengths[:, None])
    if kind in (ATTN, LOCAL_ATTN):
        if collect_cache:
            y, (k, v) = attn_mod.attention_block(
                bp["mix"], h, positions, cfg, window=window, ctx=ctx,
                return_kv=True, kv_valid=kv_valid)
            cache = {"k": k, "v": v}
        else:
            y = attn_mod.attention_block(bp["mix"], h, positions, cfg,
                                         window=window, ctx=ctx)
    elif kind == HYENA:
        if collect_cache:
            y, cache = hyena_mod.hyena_block(bp["mix"], h, cfg, ctx=ctx,
                                             return_cache=True,
                                             cache_kind=cache_kind,
                                             lengths=lengths,
                                             filter_len=filter_len)
        else:
            y = hyena_mod.hyena_block(bp["mix"], h, cfg, ctx=ctx,
                                      filter_len=filter_len)
    elif kind == MAMBA2:
        if collect_cache:
            y, cache = ssm_mod.mamba2_block(bp["mix"], h, cfg, ctx=ctx,
                                            return_state=True, lengths=lengths)
        else:
            y = ssm_mod.mamba2_block(bp["mix"], h, cfg, ctx=ctx)
    elif kind == RGLRU:
        if collect_cache:
            y, cache = ssm_mod.rglru_block(bp["mix"], h, cfg, ctx=ctx,
                                           return_state=True, lengths=lengths)
        else:
            y = ssm_mod.rglru_block(bp["mix"], h, cfg, ctx=ctx)
    else:
        raise ValueError(kind)
    x = ctx.cs(_residual(x, y, cfg), ("batch", None, "act_embed"))
    if "cross" in bp:
        h = apply_norm(bp["cross_norm"], x, cfg.norm, cfg.norm_eps)
        if cross_kv_cache is not None:
            kv = cross_kv_cache
        else:
            assert enc_out is not None
            kv = attn_mod.compute_kv(bp["cross"], enc_out, None, cfg)
        y = attn_mod.attention_block(bp["cross"], h, positions, cfg, ctx=ctx,
                                     cross_kv=kv)
        x = x + y
        if collect_cache and cache is not None:
            cache["cross_k"], cache["cross_v"] = kv
        elif collect_cache:
            cache = {"cross_k": kv[0], "cross_v": kv[1]}
    aux = jnp.zeros((), jnp.float32)
    if cfg.d_ff > 0:
        h = apply_norm(bp["norm2"], x, cfg.norm, cfg.norm_eps)
        if cfg.mlp_kind == MLP_MOE:
            y, aux = moe_mod.moe_block(bp["mlp"], h, cfg.moe, impl=moe_impl, ctx=ctx)
        else:
            y = apply_mlp(bp["mlp"], h, cfg.act, ctx=ctx)
        x = ctx.cs(_residual(x, y, cfg), ("batch", None, "act_embed"))
    return x, aux, cache


def forward(params, tokens, cfg: ModelConfig, *, ctx: ShardCtx = NOCTX,
            frontend: Optional[jnp.ndarray] = None, moe_impl: str = "dropless",
            remat: Optional[str] = "none", collect_cache: bool = False,
            cache_kind: str = "native", lengths=None,
            filter_len: Optional[int] = None):
    """Full-sequence forward. tokens: (B, S) int32.

    Returns logits (B, S', vocab) and, with collect_cache, the per-layer
    decode caches (for prefill). For VLM, `frontend` embeddings are prepended
    (S' includes them). For enc-dec, `frontend` feeds the encoder.
    cache_kind: "native" (recurrent/kv states) or "conv" (Hyena layers cache
    the k.v product sequence for the Lemma-2.1 cached-conv baseline).
    `lengths` (B,) supports bucketed prefill: rows are right-padded to S and
    collected caches are masked to each row's true length.
    """
    if lengths is not None and frontend is not None:
        raise ValueError("lengths (bucketed prefill) is incompatible with "
                         "frontend inputs")
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = embed_tokens(params["embed"], tokens, ctx=ctx, dtype=dtype,
                     multiplier=cfg.embedding_multiplier)
    enc_out = None
    if cfg.enc_dec and frontend is not None:
        enc_out = encode_stack(params, frontend.astype(dtype), cfg, ctx)
    elif frontend is not None:                       # VLM: prepend patch embeds
        x = jnp.concatenate([frontend.astype(dtype), x], axis=1)
    if cfg.learned_pos:                        # learned absolute positions
        x = x + params["embed"]["pos"][None, :x.shape[1], :].astype(dtype)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None, :],
                                 (x.shape[0], x.shape[1]))

    n_groups, n_rem = layer_layout(cfg)

    def group_body(carry, gp):
        x, aux = carry
        caches = {}
        for i, kind in enumerate(cfg.pattern):
            x, a, c = _apply_block(gp[f"l{i}"], kind, x, positions, cfg, ctx,
                                   enc_out=enc_out, moe_impl=moe_impl,
                                   collect_cache=collect_cache,
                                   cache_kind=cache_kind, lengths=lengths,
                                   filter_len=filter_len)
            aux = aux + a
            if collect_cache:
                caches[f"l{i}"] = c
        return (x, aux), (caches if collect_cache else None)

    body = group_body
    if remat and remat != "none":
        policy = {
            "full": jax.checkpoint_policies.nothing_saveable,
            "dots": jax.checkpoint_policies.checkpoint_dots,
            "dots_no_batch": jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims,
        }[remat]
        body = jax.checkpoint(group_body, policy=policy)

    from repro import flags
    n_g = jax.tree.leaves(params["groups"])[0].shape[0]
    (x, aux), scan_caches = jax.lax.scan(body, (x, jnp.zeros((), jnp.float32)),
                                         params["groups"],
                                         unroll=flags.scan_unroll(n_g))
    rem_caches = []
    for i in range(n_rem):
        kind = cfg.blocks[n_groups * len(cfg.pattern) + i]
        x, a, c = _apply_block(params["rem"][i], kind, x, positions, cfg, ctx,
                               enc_out=enc_out, moe_impl=moe_impl,
                               collect_cache=collect_cache,
                               cache_kind=cache_kind, lengths=lengths,
                               filter_len=filter_len)
        aux = aux + a
        rem_caches.append(c)
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings,
                     softcap=cfg.logit_softcap, ctx=ctx,
                     scaling=cfg.logits_scaling)
    if collect_cache:
        return logits, aux, (scan_caches, rem_caches)
    return logits, aux


def encode_stack(params, frontend_emb, cfg: ModelConfig, ctx: ShardCtx):
    x = frontend_emb + params["enc_pos"][None, :frontend_emb.shape[1], :].astype(
        frontend_emb.dtype)
    positions = jnp.broadcast_to(jnp.arange(x.shape[1])[None, :],
                                 (x.shape[0], x.shape[1]))

    def body(carry, lp):
        h = apply_norm(lp["norm1"], carry, cfg.norm, cfg.norm_eps)
        y = attn_mod.attention_block(lp["mix"], h, positions, cfg, ctx=ctx,
                                     causal=False)
        carry = carry + y
        h = apply_norm(lp["norm2"], carry, cfg.norm, cfg.norm_eps)
        carry = carry + apply_mlp(lp["mlp"], h, cfg.act, ctx=ctx)
        return carry, None

    from repro import flags
    n_e = jax.tree.leaves(params["encoder"])[0].shape[0]
    x, _ = jax.lax.scan(body, x, params["encoder"], unroll=flags.scan_unroll(n_e))
    return apply_norm(params["enc_norm"], x, cfg.norm, cfg.norm_eps)


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------
def train_loss(params, batch, cfg: ModelConfig, *, ctx: ShardCtx = NOCTX,
               moe_impl: str = "dropless", remat: str = "none"):
    """batch: {tokens (B,S), [frontend]}. Next-token cross-entropy."""
    tokens = batch["tokens"]
    logits, aux = forward(params, tokens[:, :-1], cfg, ctx=ctx,
                          frontend=batch.get("frontend"), moe_impl=moe_impl,
                          remat=remat)
    targets = tokens[:, 1:]
    if logits.shape[1] != targets.shape[1]:          # VLM prepended frontend
        logits = logits[:, -targets.shape[1]:, :]
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = jnp.mean(logz - gold)
    return nll + aux, {"nll": nll, "aux": aux}


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------
def _init_block_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                      cross: bool, cache_kind: str = "native"):
    c: Dict[str, Any] = {}
    if kind in (ATTN, LOCAL_ATTN):
        eff = max_len if kind == ATTN or cfg.window <= 0 else min(max_len, cfg.window)
        kv = attn_mod.init_kv_cache(batch, eff, cfg.n_kv_heads, cfg.hd)
        c["k"] = Param(kv["k"], ("batch", "kv_seq", "kv_heads", None))
        c["v"] = Param(kv["v"], ("batch", "kv_seq", "kv_heads", None))
        if eff < max_len:                       # ring buffer for windowed layers
            c["slot_pos"] = Param(jnp.full((batch, eff), -1, jnp.int32),
                                  ("batch", None))
    elif kind == HYENA and cache_kind == "conv":
        hc = hyena_mod.init_hyena_conv_cache(batch, max_len, cfg)
        c["conv"] = Param(hc["conv"], ("batch", None, "qkv"))
        c["kv"] = Param(hc["kv"], ("batch", "kv_seq", "qkv"))
    elif kind == HYENA and cache_kind == "epoch":
        hc = hyena_mod.init_hyena_epoch_cache(batch, max_len, cfg)
        c["conv"] = Param(hc["conv"], ("batch", None, "qkv"))
        c["kv"] = Param(hc["kv"], ("batch", "kv_seq", "qkv"))
        c["fut"] = Param(hc["fut"], ("batch", "kv_seq", "qkv"))
        c["epoch"] = Param(hc["epoch"], ("batch",))
    elif kind == HYENA:
        hc = hyena_mod.init_hyena_cache(batch, cfg)
        c["conv"] = Param(hc["conv"], ("batch", None, "qkv"))
        c["x_re"] = Param(hc["x_re"], ("batch", "qkv", "state"))
        c["x_im"] = Param(hc["x_im"], ("batch", "qkv", "state"))
    elif kind == MAMBA2:
        mc = ssm_mod.init_mamba2_cache(batch, cfg)
        c["conv"] = Param(mc["conv"], ("batch", None, "mlp"))
        c["ssm"] = Param(mc["ssm"], ("batch", "heads", None, "state"))
    elif kind == RGLRU:
        rc = ssm_mod.init_rglru_cache(batch, cfg)
        c["conv"] = Param(rc["conv"], ("batch", None, "mlp"))
        c["h"] = Param(rc["h"], ("batch", "mlp"))
    if cross:
        F = cfg.frontend_len
        c["cross_k"] = Param(jnp.zeros((batch, F, cfg.n_kv_heads, cfg.hd),
                                       jnp.bfloat16),
                             ("batch", "kv_seq", "kv_heads", None))
        c["cross_v"] = Param(jnp.zeros((batch, F, cfg.n_kv_heads, cfg.hd),
                                       jnp.bfloat16),
                             ("batch", "kv_seq", "kv_heads", None))
    return c


def init_cache(cfg: ModelConfig, batch: int, max_len: int, *,
               cache_kind: str = "native", per_slot: bool = False):
    """Param-tree of decode caches (leading group axis on scanned layers).

    per_slot=True gives each batch row its own position counter (B,) — the
    layout the continuous-batching engine uses, where every slot holds an
    independent request at its own decode position.
    """
    n_groups, n_rem = layer_layout(cfg)
    group = {f"l{i}": _init_block_cache(kind, cfg, batch, max_len, cfg.enc_dec,
                                        cache_kind)
             for i, kind in enumerate(cfg.pattern)}
    stacked = jax.tree.map(
        lambda p: Param(jnp.broadcast_to(p.value, (n_groups,) + p.value.shape),
                        (None,) + tuple(p.axes)),
        group, is_leaf=is_param)
    pos = (Param(jnp.zeros((batch,), jnp.int32), ("batch",)) if per_slot
           else Param(jnp.zeros((), jnp.int32), ()))
    cache: Dict[str, Any] = {"groups": stacked, "pos": pos}
    if n_rem:
        cache["rem"] = [
            _init_block_cache(cfg.blocks[n_groups * len(cfg.pattern) + i], cfg,
                              batch, max_len, cfg.enc_dec, cache_kind)
            for i in range(n_rem)
        ]
    return cache


def _decode_block(bp, bc, kind: str, x, pos, cfg: ModelConfig, ctx: ShardCtx,
                  conv_filters=None):
    h = apply_norm(bp["norm1"], x, cfg.norm, cfg.norm_eps)
    window = cfg.window if kind == LOCAL_ATTN else 0
    if kind in (ATTN, LOCAL_ATTN):
        kv = {k: bc[k] for k in ("k", "v", "slot_pos") if k in bc}
        kv, y = attn_mod.attention_decode(bp["mix"], kv, h, pos, cfg,
                                          window=window, ctx=ctx)
        bc = dict(bc, **kv)
    elif kind == HYENA:
        if "fut" in bc:           # FutureFill epoched exact decode
            sub = {k: bc[k] for k in ("conv", "kv", "fut", "epoch")}
            if conv_filters is None:   # fallback: re-materialize every step
                conv_filters = hyena_mod.materialize_filters(
                    bp["mix"]["filter"], bc["kv"].shape[1], cfg.hyena)
            sub, y = hyena_mod.hyena_decode_epoch(
                bp["mix"], sub, h, pos, cfg, conv_filters, ctx=ctx)
        elif "kv" in bc:          # Lemma-2.1 cached-conv baseline (O(t)/token)
            sub = {k: bc[k] for k in ("conv", "kv")}
            if conv_filters is None:   # fallback: re-materialize every step
                conv_filters = hyena_mod.materialize_filters(
                    bp["mix"]["filter"], bc["kv"].shape[1], cfg.hyena)
            sub, y = hyena_mod.hyena_decode_cached_conv(
                bp["mix"], sub, h, pos, cfg, conv_filters, ctx=ctx)
        else:                     # distilled modal recurrence (O(d)/token)
            sub = {k: bc[k] for k in ("conv", "x_re", "x_im")}
            sub, y = hyena_mod.hyena_decode(bp["mix"], sub, h, cfg, ctx=ctx)
        bc = dict(bc, **sub)
    elif kind == MAMBA2:
        sub = {k: bc[k] for k in ("conv", "ssm")}
        sub, y = ssm_mod.mamba2_decode(bp["mix"], sub, h, cfg, ctx=ctx)
        bc = dict(bc, **sub)
    elif kind == RGLRU:
        sub = {k: bc[k] for k in ("conv", "h")}
        sub, y = ssm_mod.rglru_decode(bp["mix"], sub, h, cfg, ctx=ctx)
        bc = dict(bc, **sub)
    else:
        raise ValueError(kind)
    x = _residual(x, y, cfg)
    if "cross" in bp:
        h = apply_norm(bp["cross_norm"], x, cfg.norm, cfg.norm_eps)
        y = attn_mod.attention_block(bp["cross"], h,
                                     jnp.zeros((x.shape[0], 1), jnp.int32), cfg,
                                     ctx=ctx, cross_kv=(bc["cross_k"], bc["cross_v"]))
        x = x + y
    if cfg.d_ff > 0:
        h = apply_norm(bp["norm2"], x, cfg.norm, cfg.norm_eps)
        if cfg.mlp_kind == MLP_MOE:
            y, _ = moe_mod.moe_block(bp["mlp"], h, cfg.moe, ctx=ctx)
        else:
            y = apply_mlp(bp["mlp"], h, cfg.act, ctx=ctx)
        x = _residual(x, y, cfg)
    return bc, x


def decode_step(params, cache, tokens, cfg: ModelConfig, *, ctx: ShardCtx = NOCTX,
                conv_filters=None):
    """One decode step. tokens: (B, 1) int32. Returns (cache, logits).

    cache["pos"] is either a scalar (uniform batch: every row at the same
    position) or a (B,) vector (continuous batching: one position per slot).
    conv_filters (from `materialize_conv_filters`) supplies pre-materialized
    long filters for cached-conv Hyena layers; without it each decode step
    re-runs the filter MLP (hot-loop waste — engines always pass it).
    """
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    pos = jnp.asarray(cache["pos"], jnp.int32)
    x = embed_tokens(params["embed"], tokens, ctx=ctx, dtype=dtype,
                     multiplier=cfg.embedding_multiplier)
    if cfg.learned_pos:
        pe = params["embed"]["pos"]
        if pos.ndim == 1:
            x = x + jnp.take(pe, jnp.clip(pos, 0, pe.shape[0] - 1),
                             axis=0)[:, None, :].astype(dtype)
        else:
            x = x + jax.lax.dynamic_slice_in_dim(
                pe, pos, 1, axis=0)[None].astype(dtype)[:, 0:1]
    n_groups, n_rem = layer_layout(cfg)

    def body(x, gp_gc):
        gp, gc = gp_gc[0], gp_gc[1]
        gf = gp_gc[2] if len(gp_gc) > 2 else {}
        for i, kind in enumerate(cfg.pattern):
            gc[f"l{i}"], x = _decode_block(gp[f"l{i}"], gc[f"l{i}"], kind, x,
                                           pos, cfg, ctx,
                                           conv_filters=gf.get(f"l{i}"))
        return x, gc

    from repro import flags
    n_g = jax.tree.leaves(params["groups"])[0].shape[0]
    xs = (params["groups"], cache["groups"])
    if conv_filters is not None:
        xs = xs + (conv_filters["groups"],)
    x, new_group_caches = jax.lax.scan(body, x, xs,
                                       unroll=flags.decode_unroll(n_g))
    new_cache = {"groups": new_group_caches, "pos": pos + 1}
    if n_rem:
        rem_filters = (conv_filters or {}).get("rem", {})
        rem = []
        for i in range(n_rem):
            kind = cfg.blocks[n_groups * len(cfg.pattern) + i]
            bc, x = _decode_block(params["rem"][i], cache["rem"][i], kind, x,
                                  pos, cfg, ctx,
                                  conv_filters=rem_filters.get(i))
            rem.append(bc)
        new_cache["rem"] = rem
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings,
                     softcap=cfg.logit_softcap, ctx=ctx,
                     scaling=cfg.logits_scaling)
    return new_cache, logits


# ---------------------------------------------------------------------------
# Multi-token decode on the decode cache (speculative verify / replay)
#
# decode_chunk consumes up to C tokens per slot in ONE executable, returning
# logits at every position — the verify half of self-speculative decoding.
# Per-row `active_len` masks the advance: row b's states, conv tails, kv/ring
# buffers and position move by exactly active_len[b] tokens, positions past
# that are identity. Together with snapshot_cache_slots/restore_cache_slots
# this gives the rollback protocol: snapshot -> verify C tokens -> accept n
# -> restore -> replay with active_len = n.
# ---------------------------------------------------------------------------
def _decode_chunk_block(bp, bc, kind: str, x, pos, active_len,
                        cfg: ModelConfig, ctx: ShardCtx, conv_filters=None,
                        collect_states: bool = False):
    h = apply_norm(bp["norm1"], x, cfg.norm, cfg.norm_eps)
    window = cfg.window if kind == LOCAL_ATTN else 0
    aux = {}
    if kind in (ATTN, LOCAL_ATTN):
        kv = {k: bc[k] for k in ("k", "v", "slot_pos") if k in bc}
        kv, y = attn_mod.attention_decode_chunk(bp["mix"], kv, h, pos,
                                                active_len, cfg,
                                                window=window, ctx=ctx)
        bc = dict(bc, **kv)
    elif kind == HYENA:
        if "fut" in bc:           # FutureFill epoched exact decode
            sub = {k: bc[k] for k in ("conv", "kv", "fut", "epoch")}
            if conv_filters is None:
                conv_filters = hyena_mod.materialize_filters(
                    bp["mix"]["filter"], bc["kv"].shape[1], cfg.hyena)
            sub, y = hyena_mod.hyena_decode_epoch_chunk(
                bp["mix"], sub, h, pos, active_len, cfg, conv_filters,
                ctx=ctx)
        elif "kv" in bc:          # Lemma-2.1 cached-conv baseline
            sub = {k: bc[k] for k in ("conv", "kv")}
            if conv_filters is None:
                conv_filters = hyena_mod.materialize_filters(
                    bp["mix"]["filter"], bc["kv"].shape[1], cfg.hyena)
            sub, y = hyena_mod.hyena_decode_cached_conv_chunk(
                bp["mix"], sub, h, pos, active_len, cfg, conv_filters,
                ctx=ctx)
        else:                     # distilled modal recurrence
            sub = {k: bc[k] for k in ("conv", "x_re", "x_im")}
            if collect_states:
                sub, y, aux = hyena_mod.hyena_decode_chunk(
                    bp["mix"], sub, h, active_len, cfg, ctx=ctx,
                    return_states=True)
            else:
                sub, y = hyena_mod.hyena_decode_chunk(bp["mix"], sub, h,
                                                      active_len, cfg,
                                                      ctx=ctx)
        bc = dict(bc, **sub)
    elif kind == MAMBA2:
        sub = {k: bc[k] for k in ("conv", "ssm")}
        sub, y = ssm_mod.mamba2_decode_chunk(bp["mix"], sub, h, active_len,
                                             cfg, ctx=ctx)
        bc = dict(bc, **sub)
    elif kind == RGLRU:
        sub = {k: bc[k] for k in ("conv", "h")}
        sub, y = ssm_mod.rglru_decode_chunk(bp["mix"], sub, h, active_len,
                                            cfg, ctx=ctx)
        bc = dict(bc, **sub)
    else:
        raise ValueError(kind)
    x = _residual(x, y, cfg)
    if cfg.d_ff > 0:
        h = apply_norm(bp["norm2"], x, cfg.norm, cfg.norm_eps)
        if cfg.mlp_kind == MLP_MOE:
            y, _ = moe_mod.moe_block(bp["mlp"], h, cfg.moe, ctx=ctx)
        else:
            y = apply_mlp(bp["mlp"], h, cfg.act, ctx=ctx)
        x = _residual(x, y, cfg)
    if collect_states:
        return bc, x, aux
    return bc, x


def supports_state_select(cfg: ModelConfig, cache_kind: str = "native") -> bool:
    """True when decode_chunk(collect_states=True) can provide an O(1)
    selection-commit for this arch: every block is a distilled (native)
    Hyena layer, whose per-position modal states + conv windows identify the
    committed state at ANY accepted prefix length without a replay pass."""
    return (cfg.hyena is not None and cache_kind == "native"
            and not cfg.enc_dec and cfg.frontend == "none"
            and all(b == HYENA for b in cfg.blocks))


def decode_chunk(params, cache, tokens, cfg: ModelConfig, *, active_len,
                 ctx: ShardCtx = NOCTX, conv_filters=None,
                 need_logits: bool = True, collect_states: bool = False):
    """Multi-token decode step. tokens: (B, C) int32; cache must be a
    per-slot pool (pos (B,)); active_len (B,) in [0, C]. Returns
    (cache, logits (B, C, V)) — logits at EVERY chunk position (the
    speculative verifier needs them all; positions past a row's active_len
    yield garbage the caller masks). cache["pos"] advances by active_len.
    need_logits=False skips the final norm + unembed (the speculative
    commit replay only needs the state advance) and returns (cache, None).
    collect_states=True (requires `supports_state_select`) additionally
    returns a per-layer aux of per-position states for
    `commit_cache_from_states`: (cache, logits, aux)."""
    if cfg.enc_dec or cfg.frontend != "none":
        raise ValueError("decode_chunk does not support enc-dec/frontend "
                         "architectures")
    B, C = tokens.shape
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    pos = jnp.asarray(cache["pos"], jnp.int32)
    if pos.ndim != 1:
        raise ValueError("decode_chunk requires a per-slot cache "
                         "(init_cache(per_slot=True))")
    if collect_states and not supports_state_select(cfg):
        raise ValueError("collect_states requires a pure distilled-Hyena "
                         "arch (see supports_state_select)")
    active_len = jnp.asarray(active_len, jnp.int32)
    x = embed_tokens(params["embed"], tokens, ctx=ctx, dtype=dtype,
                     multiplier=cfg.embedding_multiplier)
    if cfg.learned_pos:                    # learned absolute positions
        pe = params["embed"]["pos"]
        positions = pos[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
        x = x + jnp.take(pe, jnp.clip(positions, 0, pe.shape[0] - 1),
                         axis=0).astype(dtype)
    n_groups, n_rem = layer_layout(cfg)

    def body(x, gp_gc):
        gp, gc = gp_gc[0], gp_gc[1]
        gf = gp_gc[2] if len(gp_gc) > 2 else {}
        auxes = {}
        for i, kind in enumerate(cfg.pattern):
            out = _decode_chunk_block(gp[f"l{i}"], gc[f"l{i}"], kind, x, pos,
                                      active_len, cfg, ctx,
                                      conv_filters=gf.get(f"l{i}"),
                                      collect_states=collect_states)
            if collect_states:
                gc[f"l{i}"], x, auxes[f"l{i}"] = out
            else:
                gc[f"l{i}"], x = out
        return x, (gc, auxes)

    from repro import flags
    n_g = jax.tree.leaves(params["groups"])[0].shape[0]
    xs = (params["groups"], cache["groups"])
    if conv_filters is not None:
        xs = xs + (conv_filters["groups"],)
    x, (new_group_caches, group_aux) = jax.lax.scan(
        body, x, xs, unroll=flags.decode_unroll(n_g))
    new_cache = {"groups": new_group_caches, "pos": pos + active_len}
    aux = {"groups": group_aux, "pos": pos}
    if n_rem:
        rem_filters = (conv_filters or {}).get("rem", {})
        rem = []
        rem_aux = []
        for i in range(n_rem):
            kind = cfg.blocks[n_groups * len(cfg.pattern) + i]
            out = _decode_chunk_block(params["rem"][i], cache["rem"][i],
                                      kind, x, pos, active_len, cfg, ctx,
                                      conv_filters=rem_filters.get(i),
                                      collect_states=collect_states)
            if collect_states:
                bc, x, a = out
                rem_aux.append(a)
            else:
                bc, x = out
            rem.append(bc)
        new_cache["rem"] = rem
        aux["rem"] = rem_aux
    if not need_logits:
        return new_cache, None
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings,
                     softcap=cfg.logit_softcap, ctx=ctx,
                     scaling=cfg.logits_scaling)
    if collect_states:
        return new_cache, logits, aux
    return new_cache, logits


def commit_cache_from_states(aux, n_emit, cfg: ModelConfig):
    """Build the committed decode cache directly from a
    decode_chunk(collect_states=True) aux: per slot, select the modal state
    after exactly n_emit tokens and gather the conv tail ending there — an
    O(1) rollback-to-accepted-prefix with NO replay pass. Only valid for
    `supports_state_select` archs (pure distilled Hyena)."""
    from repro.models.layers import conv_tail_gather
    n_emit = jnp.asarray(n_emit, jnp.int32)
    w = cfg.hyena.short_conv - 1

    def sel_states(xs, seq_axis: int):
        # xs (..., B, C, D, d): state after j+1 tokens at index j
        idx = jnp.broadcast_to(
            (n_emit - 1).reshape((1,) * (seq_axis - 1) + (-1, 1, 1, 1)),
            xs.shape[:seq_axis] + (1,) + xs.shape[seq_axis + 1:])
        return jnp.take_along_axis(xs, idx, axis=seq_axis)[
            (slice(None),) * seq_axis + (0,)]

    def fix(a, seq_axis: int):
        ext = a["ext"]                           # (..., B, W-1+C, 3D)
        if seq_axis == 2:                        # leading group axis
            tail = jax.vmap(lambda e: conv_tail_gather(e, w, w + n_emit))(ext)
        else:
            tail = conv_tail_gather(ext, w, w + n_emit)
        return {"conv": tail,
                "x_re": sel_states(a["xs_re"], seq_axis),
                "x_im": sel_states(a["xs_im"], seq_axis)}

    out = {"groups": {lk: fix(lv, seq_axis=2)
                      for lk, lv in aux["groups"].items()},
           "pos": jnp.asarray(aux["pos"], jnp.int32) + n_emit}
    if aux.get("rem"):
        out["rem"] = [fix(a, seq_axis=1) for a in aux["rem"]]
    return out


# ---------------------------------------------------------------------------
# Snapshot / restore: the rollback half of speculative decoding
# ---------------------------------------------------------------------------
def _chunk_write_idx(pos, horizon: int, size: int, ring: bool):
    """(B, horizon) buffer indices a horizon-token advance writes per slot —
    the same index math attention_decode_chunk / the cached-conv chunk use."""
    offs = pos[:, None] + jnp.arange(horizon, dtype=jnp.int32)[None, :]
    return offs % size if ring else jnp.clip(offs, 0, size - 1)


def _gather_rows(leaf, idx, seq_axis: int):
    """Gather rows idx (B, C) along seq_axis; batch axis is seq_axis - 1."""
    B, C = idx.shape
    shape = [1] * leaf.ndim
    shape[seq_axis - 1] = B
    shape[seq_axis] = C
    tgt = leaf.shape[:seq_axis] + (C,) + leaf.shape[seq_axis + 1:]
    return jnp.take_along_axis(leaf, jnp.broadcast_to(idx.reshape(shape), tgt),
                               axis=seq_axis)


def _scatter_rows(leaf, idx, rows, seq_axis: int):
    b = jnp.arange(idx.shape[0])[:, None]                 # (B, 1) vs (B, C)
    rows = rows.astype(leaf.dtype)
    if seq_axis == 1:
        return leaf.at[b, idx].set(rows)
    assert seq_axis == 2, seq_axis
    return leaf.at[:, b, idx].set(rows)


_SEQ_KEYS = ("k", "v", "kv", "slot_pos")


def snapshot_cache_slots(cache, cfg: ModelConfig, horizon: int):
    """Capture everything a <= horizon-token advance (decode_step calls or
    one decode_chunk) can mutate, per slot: recurrent states and conv tails
    in full (they are O(1) per slot), plus the `horizon` rows of every
    sequence buffer (attention k/v linear or ring — slot_pos included — and
    cached-conv k.v products) at the write indices derived from the CURRENT
    cache["pos"]. restore_cache_slots with this snapshot is a bit-exact
    rollback to the snapshot point."""
    pos = jnp.asarray(cache["pos"], jnp.int32)
    if pos.ndim != 1:
        raise ValueError("snapshot_cache_slots requires a per-slot cache")

    def snap_block(c, seq_axis: int):
        out = {}
        ring = "slot_pos" in c
        for k, v in c.items():
            if k in ("cross_k", "cross_v"):
                continue                        # decode never mutates these
            if k in _SEQ_KEYS:
                idx = _chunk_write_idx(pos, horizon, v.shape[seq_axis], ring)
                out[k] = _gather_rows(v, idx, seq_axis)
            else:                               # conv / x_re / x_im / ssm / h
                out[k] = v
        return out

    snap = {"pos": pos,
            "groups": {lk: snap_block(lv, seq_axis=2)
                       for lk, lv in cache["groups"].items()}}
    if "rem" in cache:
        snap["rem"] = [snap_block(rc, seq_axis=1) for rc in cache["rem"]]
    return snap


def restore_cache_slots(cache, snap, cfg: ModelConfig):
    """Bit-exact rollback of a per-slot cache to a snapshot taken by
    snapshot_cache_slots: scatter the saved sequence-buffer rows back (ring
    slot_pos positions included), swap the saved recurrent states / conv
    tails in wholesale, and reset pos to the snapshot position."""
    pos = jnp.asarray(snap["pos"], jnp.int32)

    def rest_block(c, s, seq_axis: int):
        out = dict(c)
        ring = "slot_pos" in c
        for k, v in s.items():
            if k in _SEQ_KEYS:
                idx = _chunk_write_idx(pos, v.shape[seq_axis],
                                       c[k].shape[seq_axis], ring)
                out[k] = _scatter_rows(c[k], idx, v, seq_axis)
            else:
                out[k] = v
        return out

    out = {"groups": {lk: rest_block(lv, snap["groups"][lk], seq_axis=2)
                      for lk, lv in cache["groups"].items()},
           "pos": pos}
    if "rem" in cache:
        out["rem"] = [rest_block(rc, snap["rem"][i], seq_axis=1)
                      for i, rc in enumerate(cache["rem"])]
    return out


# ---------------------------------------------------------------------------
# Prefill: full-sequence pass that fills the decode caches
# ---------------------------------------------------------------------------
def _ring_from_linear(leaf, seq_axis: int, eff: int, lens):
    """Re-layout a linear (..., T, ...) buffer into ring-slot order.

    Ring slot j of row b holds the absolute position p ≡ j (mod eff) from the
    window [len_b - eff, len_b); slots whose position is negative (prompt
    shorter than the ring) are zeroed and marked -1 in slot_pos. The batch
    axis is seq_axis - 1; lens is (B,). Returns (ring, slot_pos (B, eff)).
    """
    B = lens.shape[0]
    j = jnp.arange(eff)
    base = lens[:, None] - eff                           # (B, 1), may be < 0
    p = base + ((j[None, :] - base) % eff)               # (B, eff)
    valid = p >= 0
    sp = jnp.where(valid, p, -1).astype(jnp.int32)
    idx = jnp.clip(p, 0, leaf.shape[seq_axis] - 1)
    shape = [1] * leaf.ndim
    shape[seq_axis - 1] = B
    shape[seq_axis] = eff
    tgt = leaf.shape[:seq_axis] + (eff,) + leaf.shape[seq_axis + 1:]
    ring = jnp.take_along_axis(leaf, jnp.broadcast_to(idx.reshape(shape), tgt),
                               axis=seq_axis)
    ring = jnp.where(jnp.broadcast_to(valid.reshape(shape), tgt), ring, 0)
    return ring, sp


def prefill(params, tokens, cfg: ModelConfig, max_len: int, *,
            ctx: ShardCtx = NOCTX, frontend=None, moe_impl: str = "dropless",
            cache_kind: str = "native", lengths=None):
    """Process prompt, return (cache, last_logits).

    Attention k/v from the forward pass are padded into max_len cache buffers;
    recurrent blocks produce O(1) states directly (Sec. 3.4 fast pre-filling).
    With cache_kind="conv", Hyena layers cache the k.v product sequence for
    the Lemma-2.1 cached-conv decode baseline instead of the modal state.

    `lengths` (B,) enables bucketed batch prefill: rows are right-padded to a
    shared bucket length T, caches are masked to each row's true length, the
    cache position becomes a per-row (B,) vector, and last_logits is taken at
    each row's own last real position. One executable then serves every
    prompt length in the bucket.
    """
    B, T = tokens.shape
    logits, _, (scan_caches, rem_caches) = forward(
        params, tokens, cfg, ctx=ctx, frontend=frontend, moe_impl=moe_impl,
        collect_cache=True, remat="none", cache_kind=cache_kind,
        lengths=lengths, filter_len=max_len)
    if frontend is not None and not cfg.enc_dec:
        T = T + frontend.shape[1]              # VLM: patches occupy kv positions
    lens = (jnp.full((B,), T, jnp.int32) if lengths is None
            else jnp.asarray(lengths, jnp.int32))

    def fix_cache(c, kind: str, seq_axis: int):
        eff = max_len
        if kind == LOCAL_ATTN and 0 < cfg.window < max_len:
            eff = cfg.window
        out = {}
        for k, v in c.items():
            if k in ("k", "v"):
                if eff < max_len:
                    ring, sp = _ring_from_linear(v.astype(jnp.bfloat16),
                                                 seq_axis, eff, lens)
                    out[k] = ring
                    # slot_pos is per batch row: (B, eff) / (n_groups, B, eff)
                    out["slot_pos"] = jnp.broadcast_to(
                        sp, v.shape[:seq_axis - 1] + (B, eff))
                else:
                    pad = [(0, 0)] * v.ndim
                    pad[seq_axis] = (0, max_len - v.shape[seq_axis])
                    out[k] = jnp.pad(v.astype(jnp.bfloat16), pad)
            elif k in ("kv", "fut"):   # hyena conv/epoch sequence buffers
                pad = [(0, 0)] * v.ndim
                pad[seq_axis] = (0, max_len - v.shape[seq_axis])
                out[k] = jnp.pad(v, pad)
            elif k in ("cross_k", "cross_v"):
                out[k] = v.astype(jnp.bfloat16)
            elif k != "slot_pos":
                out[k] = v
        return out

    groups = {lk: fix_cache(lv, cfg.pattern[int(lk[1:])], seq_axis=2)
              for lk, lv in scan_caches.items()}
    pos = jnp.asarray(T, jnp.int32) if lengths is None else lens
    cache = {"groups": groups, "pos": pos}
    n_groups, n_rem = layer_layout(cfg)
    if n_rem:
        cache["rem"] = [
            fix_cache(rc, cfg.blocks[n_groups * len(cfg.pattern) + i], seq_axis=1)
            for i, rc in enumerate(rem_caches)
        ]
    if lengths is None:
        return cache, logits[:, -1, :]
    last = jnp.take_along_axis(logits, (lens - 1)[:, None, None], axis=1)
    return cache, last[:, 0, :]


def materialize_conv_filters(params, cfg: ModelConfig, max_len: int):
    """Pre-materialize every Hyena layer's long filters at max_len for the
    cached-conv decode path. One-time engine-setup cost; pass the result to
    `decode_step(conv_filters=...)` so the hot loop doesn't re-run the
    filter MLP each token. Layout mirrors the cache: {"groups": {l_i:
    (h (G,M,L), h0 (G,M))}, "rem": {i: (h, h0)}}."""
    hcfg = cfg.hyena
    n_groups, n_rem = layer_layout(cfg)
    out: Dict[str, Any] = {"groups": {}}
    for i, kind in enumerate(cfg.pattern):
        if kind == HYENA:
            out["groups"][f"l{i}"] = jax.vmap(
                lambda fp: hyena_mod.materialize_filters(fp, max_len, hcfg))(
                    params["groups"][f"l{i}"]["mix"]["filter"])
    rem = {}
    for i in range(n_rem):
        if cfg.blocks[n_groups * len(cfg.pattern) + i] == HYENA:
            rem[i] = hyena_mod.materialize_filters(
                params["rem"][i]["mix"]["filter"], max_len, hcfg)
    if rem:
        out["rem"] = rem
    return out


# ---------------------------------------------------------------------------
# Chunked (resumable) prefill: consume a prompt in fixed-size chunks
#
# One chunk-shaped executable covers arbitrarily long prompts, so a serving
# engine can interleave long-prompt admission with decode ticks (FutureFill /
# Flash-Inference-style blocked prompt processing). The scratch cache differs
# from the decode cache in two ways: Hyena layers carry the k.v product
# history so cross-chunk contributions use the TRUE long filter (exact — not
# the distilled approximation; the modal state is advanced alongside), and
# windowed attention keeps a full linear buffer (ring layout is produced at
# finalize). Buffers are rounded up to a whole number of chunks so the final
# (padded) chunk's writes never clamp.
# ---------------------------------------------------------------------------
def _prefill_buf_len(max_len: int, chunk: int) -> int:
    return ((max_len + chunk - 1) // chunk) * chunk


def _init_block_prefill_cache(kind: str, cfg: ModelConfig, batch: int,
                              buf_len: int, cache_kind: str):
    c: Dict[str, Any] = {}
    if kind in (ATTN, LOCAL_ATTN):
        # f32 scratch: the decode cache is bf16, but chunked prefill re-reads
        # past keys for in-chunk attention — downcast only at finalize
        c["k"] = Param(jnp.zeros((batch, buf_len, cfg.n_kv_heads, cfg.hd),
                                 jnp.float32),
                       ("batch", "kv_seq", "kv_heads", None))
        c["v"] = Param(jnp.zeros((batch, buf_len, cfg.n_kv_heads, cfg.hd),
                                 jnp.float32),
                       ("batch", "kv_seq", "kv_heads", None))
    elif kind == HYENA:
        hc = hyena_mod.init_hyena_conv_cache(batch, buf_len, cfg)
        c["conv"] = Param(hc["conv"], ("batch", None, "qkv"))
        c["kv"] = Param(hc["kv"], ("batch", "kv_seq", "qkv"))
        if cache_kind == "native":
            nc = hyena_mod.init_hyena_cache(batch, cfg)
            c["x_re"] = Param(nc["x_re"], ("batch", "qkv", "state"))
            c["x_im"] = Param(nc["x_im"], ("batch", "qkv", "state"))
    elif kind == MAMBA2:
        mc = ssm_mod.init_mamba2_cache(batch, cfg)
        c["conv"] = Param(mc["conv"], ("batch", None, "mlp"))
        c["ssm"] = Param(mc["ssm"], ("batch", "heads", None, "state"))
    elif kind == RGLRU:
        rc = ssm_mod.init_rglru_cache(batch, cfg)
        c["conv"] = Param(rc["conv"], ("batch", None, "mlp"))
        c["h"] = Param(rc["h"], ("batch", "mlp"))
    else:
        raise ValueError(kind)
    return c


def init_prefill_cache(cfg: ModelConfig, batch: int, max_len: int, *,
                       chunk: int, cache_kind: str = "native"):
    """Param-tree of chunked-prefill scratch state (see section comment)."""
    if cfg.enc_dec or cfg.frontend != "none":
        raise ValueError("chunked prefill does not support enc-dec/frontend "
                         "architectures")
    buf_len = _prefill_buf_len(max_len, chunk)
    n_groups, n_rem = layer_layout(cfg)
    group = {f"l{i}": _init_block_prefill_cache(kind, cfg, batch, buf_len,
                                                cache_kind)
             for i, kind in enumerate(cfg.pattern)}
    stacked = jax.tree.map(
        lambda p: Param(jnp.broadcast_to(p.value, (n_groups,) + p.value.shape),
                        (None,) + tuple(p.axes)),
        group, is_leaf=is_param)
    cache: Dict[str, Any] = {"groups": stacked}
    if n_rem:
        cache["rem"] = [
            _init_block_prefill_cache(
                cfg.blocks[n_groups * len(cfg.pattern) + i], cfg, batch,
                buf_len, cache_kind)
            for i in range(n_rem)
        ]
    return cache


def _prefill_chunk_block(bp, bc, kind: str, x, positions, start, chunk_len,
                         cfg: ModelConfig, max_len: int, ctx: ShardCtx, *,
                         conv_filters=None, cache_kind: str = "native"):
    """One block over one prompt chunk. Mirrors _decode_block's structure."""
    h = apply_norm(bp["norm1"], x, cfg.norm, cfg.norm_eps)
    window = cfg.window if kind == LOCAL_ATTN else 0
    if kind in (ATTN, LOCAL_ATTN):
        sub = {k: bc[k] for k in ("k", "v")}
        sub, y = attn_mod.attention_prefill_chunk(
            bp["mix"], sub, h, positions, start, chunk_len, cfg,
            window=window, ctx=ctx)
    elif kind == HYENA:
        keys = ("conv", "kv") if "x_re" not in bc else ("conv", "kv", "x_re",
                                                        "x_im")
        sub = {k: bc[k] for k in keys}
        if conv_filters is None:       # fallback: re-materialize every chunk
            # at max_len, NOT the buffer length — the implicit filter's
            # values depend on the materialization length, and every other
            # serving path pins it to max_len (filter_len)
            conv_filters = hyena_mod.materialize_filters(
                bp["mix"]["filter"], max_len, cfg.hyena)
        sub, y = hyena_mod.hyena_prefill_chunk(
            bp["mix"], sub, h, start, chunk_len, cfg, conv_filters, ctx=ctx,
            cache_kind="conv" if "x_re" not in bc else "native")
    elif kind == MAMBA2:
        sub = {k: bc[k] for k in ("conv", "ssm")}
        sub, y = ssm_mod.mamba2_prefill_chunk(bp["mix"], sub, h, chunk_len,
                                              cfg, ctx=ctx)
    elif kind == RGLRU:
        sub = {k: bc[k] for k in ("conv", "h")}
        sub, y = ssm_mod.rglru_prefill_chunk(bp["mix"], sub, h, chunk_len,
                                             cfg, ctx=ctx)
    else:
        raise ValueError(kind)
    bc = dict(bc, **sub)
    x = _residual(x, y, cfg)
    if cfg.d_ff > 0:
        h = apply_norm(bp["norm2"], x, cfg.norm, cfg.norm_eps)
        if cfg.mlp_kind == MLP_MOE:
            y, _ = moe_mod.moe_block(bp["mlp"], h, cfg.moe, ctx=ctx)
        else:
            y = apply_mlp(bp["mlp"], h, cfg.act, ctx=ctx)
        x = _residual(x, y, cfg)
    return bc, x


def prefill_from_cache(params, cache, tokens, start_pos, cfg: ModelConfig,
                       max_len: int, *, chunk_len=None, ctx: ShardCtx = NOCTX,
                       conv_filters=None, cache_kind: str = "native"):
    """Resumable prefill: consume the prompt slice tokens (B, C) occupying
    absolute positions [start_pos, start_pos + chunk_len).

    `cache` comes from `init_prefill_cache` (first chunk) or a previous call;
    `chunk_len` (traced scalar, default C) marks the real positions of a
    padded final chunk — one chunk-shaped executable serves every prompt
    length. `conv_filters` (materialize_conv_filters at the buffer length or
    longer) avoids re-running the Hyena filter MLP per chunk. Returns
    (cache, last_logits (B, V)) with logits taken at the chunk's last real
    position; hand the finished cache to `finalize_prefill_cache`.
    """
    B, C = tokens.shape
    if chunk_len is None:
        chunk_len = C
    chunk_len = jnp.asarray(chunk_len, jnp.int32)
    start = jnp.asarray(start_pos, jnp.int32)
    dtype = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    x = embed_tokens(params["embed"], tokens, ctx=ctx, dtype=dtype,
                     multiplier=cfg.embedding_multiplier)
    if cfg.learned_pos:                    # learned absolute positions
        pe = params["embed"]["pos"]
        x = x + jax.lax.dynamic_slice_in_dim(pe, start, C,
                                             axis=0)[None].astype(dtype)
    positions = jnp.broadcast_to(start + jnp.arange(C)[None, :], (B, C))
    n_groups, n_rem = layer_layout(cfg)

    def body(x, gp_gc):
        gp, gc = gp_gc[0], gp_gc[1]
        gf = gp_gc[2] if len(gp_gc) > 2 else {}
        for i, kind in enumerate(cfg.pattern):
            gc[f"l{i}"], x = _prefill_chunk_block(
                gp[f"l{i}"], gc[f"l{i}"], kind, x, positions, start,
                chunk_len, cfg, max_len, ctx, conv_filters=gf.get(f"l{i}"),
                cache_kind=cache_kind)
        return x, gc

    from repro import flags
    n_g = jax.tree.leaves(params["groups"])[0].shape[0]
    xs = (params["groups"], cache["groups"])
    if conv_filters is not None:
        xs = xs + (conv_filters["groups"],)
    x, new_group_caches = jax.lax.scan(body, x, xs,
                                       unroll=flags.scan_unroll(n_g))
    new_cache = {"groups": new_group_caches}
    if n_rem:
        rem_filters = (conv_filters or {}).get("rem", {})
        rem = []
        for i in range(n_rem):
            kind = cfg.blocks[n_groups * len(cfg.pattern) + i]
            bc, x = _prefill_chunk_block(
                params["rem"][i], cache["rem"][i], kind, x, positions, start,
                chunk_len, cfg, max_len, ctx, conv_filters=rem_filters.get(i),
                cache_kind=cache_kind)
            rem.append(bc)
        new_cache["rem"] = rem
    x = jax.lax.dynamic_slice_in_dim(x, chunk_len - 1, 1, axis=1)
    x = apply_norm(params["final_norm"], x, cfg.norm, cfg.norm_eps)
    logits = unembed(params["embed"], x, cfg.tie_embeddings,
                     softcap=cfg.logit_softcap, ctx=ctx,
                     scaling=cfg.logits_scaling)
    return new_cache, logits[:, 0, :]


def finalize_prefill_cache(cache, length, cfg: ModelConfig, max_len: int, *,
                           cache_kind: str = "native"):
    """Convert finished chunked-prefill scratch into a decode cache: drop the
    Hyena k.v history for the distilled kind, trim buffers to max_len,
    downcast attention k/v to bf16, re-layout windowed attention into ring
    form, and set pos = `length` (the prompt length, traced scalar)."""
    length = jnp.asarray(length, jnp.int32)

    def trim(v, seq_axis: int, to_len: int):
        return jax.lax.slice_in_dim(v, 0, to_len, axis=seq_axis)

    def fix(c, kind: str, seq_axis: int):
        B = jax.tree.leaves(c)[0].shape[seq_axis - 1]
        lens = jnp.full((B,), length, jnp.int32)
        out = dict(c)
        if kind in (ATTN, LOCAL_ATTN):
            eff = max_len
            if kind == LOCAL_ATTN and 0 < cfg.window < max_len:
                eff = cfg.window
            if eff < max_len:
                ring_k, sp = _ring_from_linear(c["k"].astype(jnp.bfloat16),
                                               seq_axis, eff, lens)
                ring_v, _ = _ring_from_linear(c["v"].astype(jnp.bfloat16),
                                              seq_axis, eff, lens)
                out = {"k": ring_k, "v": ring_v,
                       "slot_pos": jnp.broadcast_to(
                           sp, c["k"].shape[:seq_axis - 1] + (B, eff))}
            else:
                out = {"k": trim(c["k"], seq_axis, max_len).astype(jnp.bfloat16),
                       "v": trim(c["v"], seq_axis, max_len).astype(jnp.bfloat16)}
        elif kind == HYENA:
            if "x_re" in c:                       # distilled: drop kv history
                out = {"conv": c["conv"], "x_re": c["x_re"], "x_im": c["x_im"]}
            else:
                out = {"conv": c["conv"],
                       "kv": trim(c["kv"], seq_axis, max_len)}
                if cache_kind == "epoch":
                    # fresh FutureFill state: epoch 0 / fut empty — the first
                    # decode tick's flush bakes the prefix in (exact either
                    # way; see hyena_decode_epoch)
                    out["fut"] = jnp.zeros_like(out["kv"])
                    out["epoch"] = jnp.zeros(
                        c["kv"].shape[:seq_axis - 1] + (B,), jnp.int32)
        return out

    groups = {lk: fix(lv, cfg.pattern[int(lk[1:])], seq_axis=2)
              for lk, lv in cache["groups"].items()}
    n_groups, n_rem = layer_layout(cfg)
    out = {"groups": groups, "pos": length}
    if n_rem:
        out["rem"] = [
            fix(rc, cfg.blocks[n_groups * len(cfg.pattern) + i], seq_axis=1)
            for i, rc in enumerate(cache["rem"])
        ]
    return out


# ---------------------------------------------------------------------------
# Slot-indexed cache helpers (continuous-batching serving engine)
#
# A pooled cache (init_cache(..., per_slot=True)) holds one request per batch
# row ("slot"). Admission scatters a freshly prefilled batch=1 cache into a
# free slot; eviction just frees the slot — its stale state is fully
# overwritten on readmission (reset_cache_slot exists for explicit hygiene).
# ---------------------------------------------------------------------------
def _slot_update(axis: int, slot):
    def f(pool_leaf, single_leaf):
        return jax.lax.dynamic_update_slice_in_dim(
            pool_leaf, single_leaf.astype(pool_leaf.dtype), slot, axis=axis)
    return f


def write_cache_slot(pool, single, slot):
    """Scatter a batch=1 cache (from `prefill`) into row `slot` of a pooled
    per-slot cache. Group leaves carry a leading layer axis, so their batch
    axis is 1; remainder leaves and `pos` use axis 0. jit-friendly (traced
    `slot`)."""
    slot = jnp.asarray(slot, jnp.int32)
    out = {"groups": jax.tree.map(_slot_update(1, slot), pool["groups"],
                                  single["groups"]),
           "pos": pool["pos"].at[slot].set(
               jnp.asarray(single["pos"], jnp.int32))}
    if "rem" in pool:
        out["rem"] = jax.tree.map(_slot_update(0, slot), pool["rem"],
                                  single["rem"])
    return out


def write_cache_slots(pool, multi, slots):
    """Scatter a batch=K prefilled cache (from `prefill(..., lengths=...)`)
    into rows `slots` (K,) of a pooled per-slot cache in ONE call — the
    bucketed batch-admission path. Rows whose slot index falls outside
    [0, n_slots) are dummy padding (the engine pads an admission batch to a
    fixed size by pointing dummies at slot index n_slots) and must not touch
    the pool. That drop is an EXPLICIT mask, not out-of-bounds scatter
    semantics: under a sharded pool each partition sees shifted local
    indices, so `.at[...].set(mode="drop")` would drop or clamp different
    rows per shard. A scatter-max marker records per pool row the index of
    the last valid admission row targeting it (-1 = untouched; dummy rows
    contribute -1 so they can never override a valid update), and each leaf
    takes a masked gather against it. jit-friendly (traced `slots`);
    `multi["pos"]` must be a (K,) vector."""
    slots = jnp.asarray(slots, jnp.int32)
    K = slots.shape[0]
    B = pool["pos"].shape[0]
    valid = (slots >= 0) & (slots < B)
    src = jnp.where(valid, jnp.arange(K, dtype=jnp.int32), -1)
    marker = jnp.full((B,), -1, jnp.int32).at[
        jnp.where(valid, slots, 0)].max(src)
    take_idx = jnp.maximum(marker, 0)
    keep = marker >= 0

    def upd(axis: int):
        def f(pool_leaf, multi_leaf):
            vals = jnp.take(multi_leaf.astype(pool_leaf.dtype), take_idx,
                            axis=axis)
            mask = keep.reshape((1,) * axis + (B,)
                                + (1,) * (pool_leaf.ndim - axis - 1))
            return jnp.where(mask, vals, pool_leaf)
        return f

    out = {"groups": jax.tree.map(upd(1), pool["groups"], multi["groups"]),
           "pos": jnp.where(keep,
                            jnp.take(jnp.asarray(multi["pos"], jnp.int32),
                                     take_idx),
                            pool["pos"])}
    if "rem" in pool:
        out["rem"] = jax.tree.map(upd(0), pool["rem"], multi["rem"])
    return out


def gather_cache_rows(cache, rows):
    """Gather batch rows `rows` (R,) int32 from a pooled per-slot cache (or a
    `decode_chunk(collect_states=True)` aux, which shares the same axis
    conventions: group leaves carry a leading layer axis so their batch axis
    is 1; remainder leaves and `pos` use axis 0). Rows may repeat — the tree
    speculative verifier replicates each slot once per draft branch
    (`rows = repeat(arange(B), branch)`) and later selects the winning
    branch per slot (`rows = arange(B) * branch + winner`). jit-friendly
    (traced `rows`)."""
    rows = jnp.asarray(rows, jnp.int32)
    out = {"groups": jax.tree.map(lambda x: jnp.take(x, rows, axis=1),
                                  cache["groups"]),
           "pos": jnp.take(jnp.asarray(cache["pos"], jnp.int32), rows,
                           axis=0)}
    if cache.get("rem"):
        out["rem"] = jax.tree.map(lambda x: jnp.take(x, rows, axis=0),
                                  cache["rem"])
    return out


# ---------------------------------------------------------------------------
# State-integrity guards (resilient serving)
# ---------------------------------------------------------------------------
_STATE_NORM_KEYS = ("x_re", "x_im")      # modal state: pole bound applies


def modal_state_bound(params, cfg: ModelConfig, *, margin: float = 1e3):
    """Host-side bound on the distilled modal-state magnitude.

    Prop. 3.3's recurrence x_{t+1} = λ x_t + R u_t with stable poles
    (|λ| < 1) keeps |x| ≤ max|Ru| / (1 - max|λ|); `margin` stands in for
    the data-dependent max|Ru| term, so the bound only trips on genuine
    divergence (corrupted state / unstable pole), never on healthy
    activations. Returns inf when the arch has no distilled Hyena params
    (finiteness-only guard). Pure host computation — call once at engine
    init, not per tick.
    """
    if cfg.hyena is None:
        return float("inf")
    max_log_a = None

    def walk(node):
        nonlocal max_log_a
        if isinstance(node, dict):
            dp = node.get("distilled")
            if isinstance(dp, dict) and "log_a" in dp:
                la = dp["log_a"]
                la = getattr(la, "value", la)
                m = float(jnp.max(la))
                max_log_a = m if max_log_a is None else max(max_log_a, m)
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    if max_log_a is None:
        return float("inf")
    max_pole = math.exp(max_log_a)
    if max_pole >= 1.0 - 1e-6:             # nominally unstable pole: only a
        return margin * 1e3                # runaway state should trip this
    return margin / (1.0 - max_pole)


def slot_health(cache, logits, bound):
    """Per-slot state-integrity bitvector (B,) bool: True = healthy.

    O(B·state) reductions over the SMALL per-slot leaves only — recurrent
    states and conv tails — plus per-row finiteness of this tick's logits.
    The large sequence buffers (_SEQ_KEYS: attention k/v rings, cached-conv
    kv) are deliberately skipped: a NaN/Inf row there poisons the attention
    softmax / conv sum and therefore surfaces in that slot's logits row, so
    the logits check covers them without O(max_len) reductions. The modal
    state (x_re/x_im) is additionally checked against `bound`
    (modal_state_bound); pass inf to disable the norm check. Operates on a
    raw (unzipped) per-slot cache; fuse into the dispatch jit so the
    bitvector rides back with the sampled tokens.
    """
    # ONE reduction, not one per leaf: on CPU every extra XLA op pays a
    # parallel-loop dispatch that dwarfs the actual FLOPs (a per-leaf
    # formulation costs ~40% of a decode step; this form is ~3%). Leaves
    # that only need finiteness are scaled by 0 (finite -> 0, Inf/NaN ->
    # NaN); modal-state leaves by 1/bound (so the pole bound becomes <= 1,
    # and bound=inf degrades to finiteness: x/inf is 0 finite, NaN for
    # Inf/NaN). One concat + max per slot; NaN propagates through max and
    # fails the <= compare.
    B = logits.shape[0]
    parts = [logits.astype(jnp.float32).reshape(B, -1) * 0.0]

    def add_block(c, batch_axis: int):
        for k, v in c.items():
            if k in _SEQ_KEYS or k in ("cross_k", "cross_v", "fut"):
                # `fut` is an O(max_len) buffer like kv: corruption reaches
                # the slot's logits row additively, so the logits check
                # covers it without an O(max_len) reduction here
                continue
            if not jnp.issubdtype(v.dtype, jnp.inexact):
                continue
            vf = jnp.moveaxis(v, batch_axis, 0).reshape(B, -1)
            vf = vf.astype(jnp.float32)
            scale = (1.0 / bound) if k in _STATE_NORM_KEYS else 0.0
            parts.append(vf * scale)

    for lv in cache["groups"].values():
        add_block(lv, batch_axis=1)
    for rc in cache.get("rem") or []:
        add_block(rc, batch_axis=0)
    m = jnp.max(jnp.abs(jnp.concatenate(parts, axis=1)), axis=1)
    return m <= 1.0


def state_bytes(cache) -> Dict[str, int]:
    """Resident bytes of a pooled cache by kind of state. `kv`: the buffers
    that grow with max_len (attention k/v and ring positions, the cached-
    conv k.v history and its FutureFill buffer, cross-attention k/v);
    `recurrent`: the fixed-size per-slot states (SSM and modal states, conv
    tails, epoch counters). The position vector is neither."""
    out = {"recurrent": 0, "kv": 0}
    for c in list(cache["groups"].values()) + list(cache.get("rem") or []):
        for k, v in c.items():
            kind = ("kv" if k in _SEQ_KEYS + ("fut", "cross_k", "cross_v")
                    else "recurrent")
            out[kind] += int(v.size) * v.dtype.itemsize
    return out


def reset_cache_slot(pool, slot):
    """Zero row `slot` of a pooled cache (ring slot_pos rows to -1, pos 0)."""
    from jax.tree_util import DictKey, tree_map_with_path
    slot = jnp.asarray(slot, jnp.int32)

    def rz(axis: int):
        def f(path, leaf):
            is_sp = any(isinstance(k, DictKey) and k.key == "slot_pos"
                        for k in path)
            row = jnp.full(leaf.shape[:axis] + (1,) + leaf.shape[axis + 1:],
                           -1 if is_sp else 0, leaf.dtype)
            return jax.lax.dynamic_update_slice_in_dim(leaf, row, slot,
                                                       axis=axis)
        return f

    out = {"groups": tree_map_with_path(rz(1), pool["groups"]),
           "pos": pool["pos"].at[slot].set(0)}
    if "rem" in pool:
        out["rem"] = tree_map_with_path(rz(0), pool["rem"])
    return out
