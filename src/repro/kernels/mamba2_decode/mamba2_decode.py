"""Pallas TPU kernel: the Mamba-2 decode step's state update and readout.

At pool scale the decode step of a Mamba-2 layer is bound by its state:
each slot holds an (H, P, N) float32 state that every token reads and
writes. The jnp step repeats B and C over the heads, writes h' and reads it
back for the readout. This kernel makes one pass, per (slot, head):

    h' = exp(dt A) h + (dt x) B^T ;   y = h' C + D x

with h (P, N), x (P,), B and C (N,) of the head's group, and the scalars
dt, exp(dt A) and D. Each state byte is read once and written once, in
place (the new state aliases the old); B and C are read once per group
rather than repeated over its H/G heads.

Grid: (B // bb, H // hb). A state block (bb, hb, P, N) has the state size
N (128 in the published Mamba-2 models) on the lanes and the head dim P on
the sublanes, so the state's tiles are lane-dense. x and y blocks
(bb, hb, P) keep P on the lanes; dt and the decay travel as (B, H, 1), D
as (H, 1), and B, C as (B, G, 1, N), each block indexed by its group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# bytes of one state block: 1 MiB keeps the double-buffered state in and
# out (4 blocks) far inside the scoped VMEM
_MAX_BLOCK_BYTES = 1 << 20
_MAX_SLOT_BLOCK = 8


def _kernel(h_ref, x_ref, dt_ref, decay_ref, b_ref, c_ref, d_ref, y_ref,
            hn_ref):
    x = x_ref[...]                                  # (bb, hb, P)
    xdt = (x * dt_ref[...])[..., None]              # (bb, hb, P, 1)
    hn = h_ref[...] * decay_ref[...][..., None] + xdt * b_ref[...]
    hn_ref[...] = hn
    y = jnp.sum(hn * c_ref[...], axis=-1)           # (bb, hb, P)
    y_ref[...] = y + d_ref[...][None] * x


def _largest_divisor(n: int, cap: int, step: int = 1) -> int:
    """Largest divisor of n that is <= cap and a multiple of `step`, or 0."""
    for k in range(min(n, cap), 0, -1):
        if n % k == 0 and k % step == 0:
            return k
    return 0


def _decode_blocks(B: int, H: int, G: int, P: int, N: int) -> tuple:
    """Default (slot, head) block: the most heads of one group that are a
    multiple of 8 (or all H) within the block budget, then up to 8 slots
    that divide B and stay within it."""
    head = P * N * 4
    cap = max(_MAX_BLOCK_BYTES // head, 1)
    hb = _largest_divisor(H // G, cap, step=8) or (H if G == 1 else H // G)
    bb = _largest_divisor(B, max(min(_MAX_SLOT_BLOCK,
                                     _MAX_BLOCK_BYTES // (hb * head)), 1))
    return bb, hb


@functools.partial(jax.jit, static_argnames=("bb", "hb", "interpret"))
def mamba2_decode_pallas(h, x, dt, decay, B, C, D, *, bb: int = None,
                         hb: int = None, interpret: bool = True):
    """h: (B, H, P, N); x: (B, H, P); dt, decay: (B, H); B, C: (B, G, N);
    D: (H,). Returns (y (B, H, P), h') in float32."""
    Bs, H, P, N = h.shape
    G = B.shape[1]
    dbb, dhb = _decode_blocks(Bs, H, G, P, N)
    bb = dbb if bb is None else bb
    hb = dhb if hb is None else hb
    if Bs % bb or (H // G) % hb or (hb % 8 and hb != H):
        raise ValueError(f"mamba2_decode_pallas: block (bb={bb}, hb={hb}) "
                         f"must divide (B={Bs}, H/G={H // G}), with hb a "
                         f"multiple of 8 or all of H={H}")
    per_group = H // G // hb                       # head blocks per group
    state = pl.BlockSpec((bb, hb, P, N), lambda bi, hi: (bi, hi, 0, 0))
    vec = pl.BlockSpec((bb, hb, P), lambda bi, hi: (bi, hi, 0))
    scal = pl.BlockSpec((bb, hb, 1), lambda bi, hi: (bi, hi, 0))
    group = pl.BlockSpec((bb, 1, 1, N),
                         lambda bi, hi: (bi, hi // per_group, 0, 0))
    head = pl.BlockSpec((hb, 1), lambda bi, hi: (hi, 0))
    f32 = jnp.float32
    y, hn = pl.pallas_call(
        _kernel,
        grid=(Bs // bb, H // hb),
        in_specs=[state, vec, scal, scal, group, group, head],
        out_specs=[vec, state],
        out_shape=[jax.ShapeDtypeStruct((Bs, H, P), f32),
                   jax.ShapeDtypeStruct((Bs, H, P, N), f32)],
        input_output_aliases={0: 1},
        interpret=interpret,
    )(h.astype(f32), x.astype(f32), dt.astype(f32)[..., None],
      decay.astype(f32)[..., None], B.astype(f32)[:, :, None, :],
      C.astype(f32)[:, :, None, :], D.astype(f32)[:, None])
    return y, hn
