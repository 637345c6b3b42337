"""Pallas TPU kernel: blocked causal GQA flash attention.

Grid: (B * Hq, nq, nk) — the kv axis is the innermost (sequential on TPU) so
the online-softmax running statistics (m, l, acc) can live in VMEM scratch
across kv iterations. Block shapes are MXU-aligned: (qb, hd) x (kb, hd) with
qb, kb multiples of 128 and hd in {64, 128, 256}.

GQA is handled in the index maps: head h of q reads kv head h // G — no
repeat/materialization of k/v.

Causal skip: programs with block_j * kb > block_i * qb + qb - 1 write nothing
and skip the matmuls under pl.when (the grid still visits them; on TPU the
dominant cost — the MXU work — is gated off).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            qb: int, kb: int, causal: bool, window: int, scale: float):
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, -jnp.inf)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    lo = qi * qb
    hi = lo + qb - 1
    needed = jnp.asarray(True)
    if causal:
        needed = needed & (kj * kb <= hi)
    if window > 0:
        needed = needed & ((kj + 1) * kb - 1 > lo - window)

    @pl.when(needed)
    def _compute():
        q = q_ref[0].astype(jnp.float32)                   # (qb, hd)
        k = k_ref[0].astype(jnp.float32)                   # (kb, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale
        qpos = lo + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
        kpos = kj * kb + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 1)
        valid = jnp.ones((qb, kb), jnp.bool_)
        if causal:
            valid = valid & (kpos <= qpos)
        if window > 0:
            valid = valid & (kpos > qpos - window)
        s = jnp.where(valid, s, -jnp.inf)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(valid, jnp.exp(s - m_safe[:, None]), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_scr[...] = m_new

    @pl.when(kj == nk - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...][:, None], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "window", "qb", "kb", "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           qb: int = 128, kb: int = 128,
                           interpret: bool = True):
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qb = min(qb, S)
    kb = min(kb, T)
    assert S % qb == 0 and T % kb == 0
    nq, nk = S // qb, T // kb
    grid = (B * Hq, nq, nk)
    scale = 1.0 / np.sqrt(hd)

    # layouts: fold (B, H) into the grid; blocks are (1, qb|kb, hd)
    qt = jnp.moveaxis(q, 2, 1).reshape(B * Hq, S, hd)
    kt = jnp.moveaxis(k, 2, 1).reshape(B * Hkv, T, hd)
    vt = jnp.moveaxis(v, 2, 1).reshape(B * Hkv, T, hd)

    def q_map(bh, qi, kj):
        return (bh, qi, 0)

    def kv_map(bh, qi, kj):
        b = bh // Hq
        h = bh % Hq
        return (b * Hkv + h // G, kj, 0)

    scratch_shapes = [pltpu.VMEM((qb,), jnp.float32),
                      pltpu.VMEM((qb,), jnp.float32),
                      pltpu.VMEM((qb, hd), jnp.float32)]

    out = pl.pallas_call(
        functools.partial(_kernel, qb=qb, kb=kb, causal=causal, window=window,
                          scale=scale),
        grid=grid,
        in_specs=[pl.BlockSpec((1, qb, hd), q_map),
                  pl.BlockSpec((1, kb, hd), kv_map),
                  pl.BlockSpec((1, kb, hd), kv_map)],
        out_specs=pl.BlockSpec((1, qb, hd), q_map),
        out_shape=jax.ShapeDtypeStruct((B * Hq, S, hd), q.dtype),
        interpret=interpret,
        scratch_shapes=scratch_shapes,
    )(qt, kt, vt)
    return jnp.moveaxis(out.reshape(B, Hq, S, hd), 1, 2)
