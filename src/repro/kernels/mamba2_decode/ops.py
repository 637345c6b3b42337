"""Public wrapper: the Mamba-2 decode step's state update and readout.

On a TPU the decode always runs the compiled Pallas kernel; a shape it
cannot tile raises there instead of falling back to the reference. Other
backends run the jnp reference (the kernel itself is tested there in
interpret mode).
"""
from __future__ import annotations

import jax

from repro.kernels.mamba2_decode.mamba2_decode import mamba2_decode_pallas
from repro.kernels.mamba2_decode.ref import mamba2_decode_ref


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def mamba2_state_step(h, x, dt, decay, B, C, D):
    """(y, h'): see `ref.mamba2_decode_ref` for the shapes."""
    if _on_tpu():
        return mamba2_decode_pallas(h, x, dt, decay, B, C, D,
                                    interpret=False)
    return mamba2_decode_ref(h, x, dt, decay, B, C, D)
