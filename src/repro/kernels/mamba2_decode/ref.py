"""Pure-jnp oracle: the Mamba-2 decode step's state update and readout.

h' = exp(dt A) h + (dt x) B^T ;  y = h' C + D x   per (slot, head), with
B and C shared by the H/G heads of a group.
"""
import jax.numpy as jnp


def mamba2_decode_ref(h, x, dt, decay, B, C, D):
    """h: (B, H, P, N); x: (B, H, P); dt, decay: (B, H); B, C: (B, G, N);
    D: (H,). Returns (y (B, H, P), h')."""
    rep = h.shape[1] // B.shape[1]
    Bh = jnp.repeat(B, rep, axis=1)                                   # (B,H,N)
    Ch = jnp.repeat(C, rep, axis=1)
    h = h * decay[..., None, None] + \
        jnp.einsum("bhn,bhp->bhpn", Bh, x * dt[..., None])
    y = jnp.einsum("bhpn,bhn->bhp", h, Ch) + D[None, :, None] * x
    return y, h
