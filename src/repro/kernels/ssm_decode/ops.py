"""Public wrapper: fused modal-SSM decode step.

On a TPU the decode always runs the compiled Pallas kernel; a shape it
cannot tile raises there instead of falling back to the reference. Other
backends run the jnp reference (the kernel itself is tested there in
interpret mode).
"""
from __future__ import annotations

import jax

from repro.kernels.ssm_decode.ref import ssm_decode_ref
from repro.kernels.ssm_decode.ssm_decode import ssm_decode_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def ssm_decode(x_re, x_im, u, log_a, theta, R_re, R_im, h0):
    if _on_tpu():
        return ssm_decode_pallas(x_re, x_im, u, log_a, theta, R_re, R_im, h0,
                                 interpret=False)
    return ssm_decode_ref(x_re, x_im, u, log_a, theta, R_re, R_im, h0)
