"""Hankel spectrum analysis (paper Sec. 3.3).

The McMillan degree of a filter equals the rank of its (infinite) Hankel
operator (Ho-Kalman, Thm. 3.1); the decay of the singular values of the
L x L principal sub-matrix S_L predicts the achievable distillation error at
a given order (AAK, Thm. 3.2: inf_{rank d} ||S_L - S_hat||_2 = sigma_{d+1}).
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp


def hankel_matrix(h: jnp.ndarray) -> jnp.ndarray:
    """S_L from a filter h (..., L): S[i, j] = h[i + j + 1] (Markov params).

    Index 0 of h is the passthrough term and does not enter the Hankel
    operator. Output: (..., m, m) with m = (L - 1 + 1) // 2 so every entry is
    defined from available samples.
    """
    m = h.shape[-1] // 2
    # row i is the window h[i+1 : i+1+m]: a batched dynamic slice, so the
    # program holds no (m, m) index constant
    rows = jax.vmap(lambda i: jax.lax.dynamic_slice_in_dim(h, i + 1, m, -1))
    return jnp.moveaxis(rows(jnp.arange(m)), 0, -2)


def hankel_singular_values(h: jnp.ndarray) -> jnp.ndarray:
    """Singular values of S_L, descending. h: (..., L) -> (..., m)."""
    S = hankel_matrix(h).astype(jnp.float32)
    return jnp.linalg.svd(S, compute_uv=False)


def suggest_order(sv: jnp.ndarray, tol: float = 1e-3) -> jnp.ndarray:
    """Smallest d with sigma_{d+1} / sigma_1 < tol (rule of thumb, Sec. 3.3)."""
    rel = sv / jnp.clip(sv[..., :1], 1e-30)
    return jnp.sum(rel >= tol, axis=-1)


def aak_lower_bound(sv: jnp.ndarray, d: int) -> jnp.ndarray:
    """AAK: no order-d system gets Hankel error below sigma_{d+1} (Thm. 3.2)."""
    return sv[..., d]
