"""Pallas TPU kernel: fused modal-SSM decode step.

The auto-regressive decode step is memory-bound: per token it must stream the
(B, C, d) complex state in and out of HBM once. Unfused XLA emits separate
kernels for the output reduction, the two state-update products and the
add, re-reading the state several times. This kernel performs

    y = Re[R . x] + h0 u ;  x' = lam x + u

in a single pass: one read of (x_re, x_im), one write of (x_re', x_im'), one
read of u and the (C, d) parameters (broadcast across batch blocks).

Grid: (B // bb, C // cb). State tiles (bb, cb, d) live in VMEM; d is the lane
axis (modal orders are small, <= 128), channels the sublane axis. The
per-channel vectors (u, y, h0) carry a trailing unit axis so every block
shares that (channels on sublanes) layout: the lane-axis reduction for y and
the lane broadcast of u need no relayout, and a channel block only has to be
a multiple of 8 (or all of C) — 864- and 2048-wide models tile alike.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# (slot, channel) rows per block: each row is one (d,)-lane vector, so this
# bounds a state block at 512 KiB of VMEM for d <= 128 and keeps the
# double-buffered working set (4 state, 2 vector, 5 parameter blocks) small.
_MAX_ROWS = 1024
_MAX_SLOT_BLOCK = 8


def _kernel(x_re_ref, x_im_ref, u_ref, log_a_ref, theta_ref, R_re_ref,
            R_im_ref, h0_ref, y_ref, nx_re_ref, nx_im_ref):
    xr = x_re_ref[...]                          # (bb, cb, d)
    xi = x_im_ref[...]
    u = u_ref[...]                              # (bb, cb, 1)
    lr = jnp.exp(log_a_ref[...]) * jnp.cos(theta_ref[...])   # (cb, d)
    li = jnp.exp(log_a_ref[...]) * jnp.sin(theta_ref[...])
    # output first (paper convention: y_t from x_t), then the update
    y = jnp.sum(xr * R_re_ref[...][None] - xi * R_im_ref[...][None], axis=-1,
                keepdims=True)
    y_ref[...] = y + h0_ref[...][None] * u
    nx_re_ref[...] = lr[None] * xr - li[None] * xi + u
    nx_im_ref[...] = lr[None] * xi + li[None] * xr


def _largest_divisor(n: int, cap: int, step: int = 1) -> int:
    """Largest divisor of n that is <= cap and a multiple of `step`, or 0."""
    for k in range(min(n, cap), 0, -1):
        if n % k == 0 and k % step == 0:
            return k
    return 0


def _decode_blocks(B: int, C: int) -> tuple:
    """Default (slot, channel) block for a (B, C, d) state: up to 8 slots
    that divide B, then the widest channel block that divides C, is a
    multiple of 8 and keeps bb * cb <= _MAX_ROWS (all of C when C has no
    such divisor, e.g. C not a multiple of 8)."""
    bb = _largest_divisor(B, _MAX_SLOT_BLOCK)
    cb = _largest_divisor(C, max(_MAX_ROWS // bb, 8), step=8) or C
    return bb, cb


@functools.partial(jax.jit, static_argnames=("bb", "cb", "interpret"))
def ssm_decode_pallas(x_re, x_im, u, log_a, theta, R_re, R_im, h0, *,
                      bb: int = None, cb: int = None, interpret: bool = True):
    B, C, d = x_re.shape
    dbb, dcb = _decode_blocks(B, C)
    bb = dbb if bb is None else bb
    cb = dcb if cb is None else cb
    if B % bb or C % cb or (cb % 8 and cb != C):
        raise ValueError(f"ssm_decode_pallas: block (bb={bb}, cb={cb}) must "
                         f"divide (B={B}, C={C}), with cb a multiple of 8 "
                         f"or all of C")
    grid = (B // bb, C // cb)
    state_spec = pl.BlockSpec((bb, cb, d), lambda bi, ci: (bi, ci, 0))
    vec_spec = pl.BlockSpec((bb, cb, 1), lambda bi, ci: (bi, ci, 0))
    param_spec = pl.BlockSpec((cb, d), lambda bi, ci: (ci, 0))
    h0_spec = pl.BlockSpec((cb, 1), lambda bi, ci: (ci, 0))
    f32 = jnp.float32
    y, nxr, nxi = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[state_spec, state_spec, vec_spec, param_spec, param_spec,
                  param_spec, param_spec, h0_spec],
        out_specs=[vec_spec, state_spec, state_spec],
        out_shape=[jax.ShapeDtypeStruct((B, C, 1), f32),
                   jax.ShapeDtypeStruct((B, C, d), f32),
                   jax.ShapeDtypeStruct((B, C, d), f32)],
        interpret=interpret,
    )(x_re.astype(f32), x_im.astype(f32), u.astype(f32)[..., None],
      log_a.astype(f32), theta.astype(f32), R_re.astype(f32),
      R_im.astype(f32), h0.astype(f32)[:, None])
    return y[..., 0], nxr, nxi
