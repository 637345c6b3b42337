"""Gradient-based modal interpolation (paper Sec. 3.2, App. B, D.2).

Fits the modal form to target filters by unconstrained AdamW on the l2 (time
domain) or H2 (frequency domain; equal by Parseval, kept for faithfulness)
discrepancy. Initialization is either random (paper) or Kung/Ho-Kalman —
SVD of the Hankel matrix, shift-invariance for the poles, then a *linear*
least-squares solve for the residues (the "two linear problems" view of
Prony's method the paper cites; used here as a warm start that cuts the
number of gradient steps by ~10x, see EXPERIMENTS.md).
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.hankel import hankel_matrix
from repro.core.modal import ModalSSM, eval_filter, init_modal
from repro.optim.adamw import adamw_init, adamw_update, cosine_schedule


# ---------------------------------------------------------------------------
# Kung / Ho-Kalman initialization
# ---------------------------------------------------------------------------
def kung_poles(h: jnp.ndarray, d: int) -> jnp.ndarray:
    """Estimate d modal poles from a filter h (..., L) via Hankel-SVD
    shift-invariance (App. E.3.2 steps 1-2 / Kung's method).

    The modal form takes Re[sum R lam^t], so one pole per conjugate pair
    suffices: we extract 2d eigenvalues from the order-2d balanced factor,
    keep ONE representative per conjugate pair (Im >= 0; eigenvalues of the
    real shift matrix come in conjugate pairs, so folding |theta| would
    duplicate each pole and crowd out the weak true modes), and rank by the
    h-inf influence |R| / |1 - |lam|| after a linear residue fit.
    """
    S = hankel_matrix(h).astype(jnp.float32)
    m = S.shape[-1]
    dd = min(2 * d, m - 1)
    U, s = top_singular_pairs(S, dd)
    Od = U * jnp.sqrt(s[..., None, :] + 1e-30)
    O1 = Od[..., :-1, :]
    O2 = Od[..., 1:, :]
    A = jnp.linalg.pinv(O1) @ O2                           # (..., 2d, 2d)
    lam = host_eigvals(A)
    mag = jnp.clip(jnp.abs(lam), 1e-4, 1.2)
    ang = jnp.angle(lam)
    # jitter the phases so coincident true poles don't make the LSQ singular
    jitter = jnp.linspace(0.0, 1e-4, dd)
    lam = mag * jnp.exp(1j * (ang + jitter))
    upper = ang >= -1e-6            # one per conjugate pair; real poles kept
    # lower-half duplicates are swapped for negligible decoy poles so the
    # residue solve attributes each pair's energy to its single representative
    decoy = 1e-3 * jnp.exp(1j * jnp.linspace(0.1, 3.0, dd))
    lam = jnp.where(upper, lam, decoy)
    R = fit_residues(lam, h)
    infl = jnp.abs(R) / jnp.clip(jnp.abs(1.0 - jnp.abs(lam)), 1e-6)
    infl = jnp.where(upper, infl, -1.0)
    idx = jnp.argsort(-infl, axis=-1)[..., :d]
    return jnp.take_along_axis(lam, idx, axis=-1)


# block subspace iteration of top_singular_pairs: extra columns beyond the
# k wanted, and power steps
_OVERSAMPLE, _POWER_ITERS = 16, 8


def top_singular_pairs(S: jnp.ndarray, k: int
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Leading k left singular vectors (..., m, k) and values (..., k) of
    the square Hankel matrices S (..., m, m), by orthonormalized subspace
    iteration on a k + _OVERSAMPLE block, then a dense SVD of the small
    projected block.

    Kung's method only reads the top 2d singular triplets. A dense SVD of
    the whole (m, m) matrix costs O(m^3), and the TPU's dense SVD/eigh
    lowering takes minutes to compile from m ~ 1024 on; the block iteration
    costs O(m^2 (k + _OVERSAMPLE)) per step and compiles in seconds. The
    block has at most m columns, so a Hankel of rank <= k + _OVERSAMPLE (an
    exact low-order filter) is recovered exactly."""
    m = S.shape[-1]
    p = min(m, k + _OVERSAMPLE)
    omega = jax.random.normal(jax.random.PRNGKey(0), (m, p), S.dtype)
    Q, _ = jnp.linalg.qr(S @ omega)

    def power(Q, _):
        # S is symmetric (S[i, j] = h[i + j + 1]), so S @ S^T is S @ S
        Q, _ = jnp.linalg.qr(S @ (S @ Q))
        return Q, None

    Q, _ = jax.lax.scan(power, Q, None, length=_POWER_ITERS)
    Ub, s, _ = jnp.linalg.svd(jnp.swapaxes(Q, -1, -2) @ S,
                              full_matrices=False)
    return (Q @ Ub)[..., :k], s[..., :k]


def host_eigvals(A: jnp.ndarray) -> jnp.ndarray:
    """Eigenvalues of real square matrices A (..., n, n) as complex64,
    computed on the host by LAPACK through a callback: XLA has no
    nonsymmetric eigensolver for the TPU. These are the tiny (2d x 2d) shift
    matrices of Kung's method, set-up work once per distillation, so the
    round trip costs nothing measurable."""
    def eig(a):
        return np.linalg.eigvals(np.asarray(a)).astype(np.complex64)

    out = jax.ShapeDtypeStruct(A.shape[:-1], jnp.complex64)
    return jax.pure_callback(eig, out, A, vmap_method="broadcast_all")


def fit_residues(lam: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """Given poles, residues solve a LINEAR least-squares problem.

    Re[V R] ~= h[1:], where V[t, n] = lam_n^t (t = 0..L-2). Solved via the
    real-stacked normal equations. lam: (..., d); h: (..., L)."""
    L = h.shape[-1]
    t = jnp.arange(L - 1, dtype=jnp.float32)
    logl = jnp.log(jnp.clip(jnp.abs(lam), 1e-8))
    ang = jnp.angle(lam)
    mag = jnp.exp(logl[..., None, :] * t[:, None])         # (..., L-1, d)
    Vr = mag * jnp.cos(ang[..., None, :] * t[:, None])
    Vi = -mag * jnp.sin(ang[..., None, :] * t[:, None])
    # design matrix for x = [R_re; R_im]: h ~ Vr R_re + Vi R_im
    X = jnp.concatenate([Vr, Vi], axis=-1)                 # (..., L-1, 2d)
    XtX = jnp.einsum("...ti,...tj->...ij", X, X)
    Xty = jnp.einsum("...ti,...t->...i", X, h[..., 1:])
    d2 = X.shape[-1]
    # scale-aware ridge keeps the system SPD even with (near-)duplicate poles
    scale = jnp.trace(XtX, axis1=-2, axis2=-1)[..., None, None] / d2
    sol = jnp.linalg.solve(XtX + 1e-6 * scale * jnp.eye(d2),
                           Xty[..., None])[..., 0]
    d = lam.shape[-1]
    return sol[..., :d] + 1j * sol[..., d:]


def kung_init(h: jnp.ndarray, d: int) -> ModalSSM:
    lam = kung_poles(h, d)
    R = fit_residues(lam, h)
    return ModalSSM(
        log_a=jnp.log(jnp.clip(jnp.abs(lam), 1e-8)).astype(jnp.float32),
        theta=jnp.angle(lam).astype(jnp.float32),
        R_re=jnp.real(R).astype(jnp.float32),
        R_im=jnp.imag(R).astype(jnp.float32),
        h0=h[..., 0].astype(jnp.float32),
    )


# ---------------------------------------------------------------------------
# Distillation losses
# ---------------------------------------------------------------------------
def l2_loss(ssm: ModalSSM, h: jnp.ndarray) -> jnp.ndarray:
    """Time-domain squared-l2 interpolation error (per filter, summed)."""
    hh = eval_filter(ssm, h.shape[-1])
    return jnp.sum(jnp.square(hh[..., 1:] - h[..., 1:]))


def h2_loss(ssm: ModalSSM, h: jnp.ndarray) -> jnp.ndarray:
    """H2 (DFT-domain) error — equals l2 by Parseval; kept for Sec. 3.1."""
    hh = eval_filter(ssm, h.shape[-1])
    F1 = jnp.fft.rfft(hh, axis=-1)
    F2 = jnp.fft.rfft(h, axis=-1)
    return jnp.sum(jnp.abs(F1 - F2) ** 2) / h.shape[-1]


# ---------------------------------------------------------------------------
# Distillation driver
# ---------------------------------------------------------------------------
@functools.partial(jax.jit, static_argnames=("d", "steps", "objective", "init"))
def distill_filters(h: jnp.ndarray, d: int, *, steps: int = 3000,
                    lr: float = 3e-3, objective: str = "l2",
                    init: str = "kung", key: Optional[jnp.ndarray] = None
                    ) -> Tuple[ModalSSM, jnp.ndarray]:
    """Distill filters h (..., L) into order-d modal SSMs.

    Returns (ssm, per-step loss trace). AdamW + cosine decay (paper D.2 uses
    AdamW 3e-4 with cosine annealing; we default to Kung warm start + a
    shorter schedule, which reaches the same error earlier).
    """
    h = h.astype(jnp.float32)
    if key is None:
        key = jax.random.PRNGKey(0)
    if init == "kung":
        ssm = kung_init(h, d)
    else:
        ssm = init_modal(key, h.shape[:-1], d)
        ssm = ssm._replace(h0=h[..., 0].astype(jnp.float32))
    loss_fn = l2_loss if objective == "l2" else h2_loss

    fit = {"log_a": ssm.log_a, "theta": ssm.theta,
           "R_re": ssm.R_re, "R_im": ssm.R_im}
    opt = adamw_init(fit)
    sched = cosine_schedule(lr, warmup=max(steps // 50, 1), total=steps,
                            final_frac=1e-3)

    def total_loss(f):
        return loss_fn(ModalSSM(f["log_a"], f["theta"], f["R_re"], f["R_im"],
                                ssm.h0), h)

    def step(carry, i):
        f, o = carry
        loss, g = jax.value_and_grad(total_loss)(f)
        f, o, _ = adamw_update(g, o, f, lr=sched(i), weight_decay=0.0,
                               max_norm=None)
        return (f, o), loss

    (fit, _), trace = jax.lax.scan(step, (fit, opt), jnp.arange(steps))
    out = ModalSSM(fit["log_a"], fit["theta"], fit["R_re"], fit["R_im"], ssm.h0)
    return out, trace


@functools.partial(jax.jit, static_argnames=("hcfg", "L", "d", "steps",
                                             "objective", "init"))
def _distill_layer(filter_params, hcfg, L: int, d: int, steps: int,
                   objective: str, init: str):
    """Distill one layer's filters: returns (distilled params, per-filter
    relative l2 error at length L)."""
    from repro.models.hyena import materialize_filters
    h, bias = materialize_filters(filter_params, L, hcfg)
    ssm, _ = distill_filters(h, d, steps=steps, objective=objective,
                             init=init)
    dp = {"log_a": ssm.log_a, "theta": ssm.theta,
          "R_re": ssm.R_re, "R_im": ssm.R_im, "h0": ssm.h0 + bias}
    err = jnp.sqrt(jnp.sum((eval_filter(ssm, L) - h) ** 2, -1) /
                   jnp.sum(h * h, -1).clip(1e-30))
    return dp, err


def distill_model(params, cfg, *, d: Optional[int] = None, steps: int = 3000,
                  objective: str = "l2", init: str = "kung", L: Optional[int] = None):
    """Distill every Hyena filter of a model in-place (returns new params).

    Materializes each layer's filters at length L (default cfg.max_seq capped
    at 8192 — pre-trained filters decay to ~0 well before that, App. D), fits
    modal SSMs, and writes them into params[...]["distilled"] in the layout
    hyena_decode expects. The passthrough absorbs the explicit Hyena bias:
    h0_total = h[0] + bias (both act as delta terms in the block).

    Layers are distilled one at a time through one compiled per-layer fit,
    so peak memory is one layer's Hankel batch, not the whole model's.
    """
    from repro.configs.base import HYENA

    hcfg = cfg.hyena
    # `d` is the paper's order (real state dim); the modal form stores d/2
    # conjugate-pair representatives (App. B.1).
    d = (d or hcfg.distill_order) // 2
    L = L or min(cfg.max_seq, 8192)

    new_params = jax.tree.map(lambda x: x, params)   # shallow copy
    errs = {}
    for i, kind in enumerate(cfg.pattern):
        if kind != HYENA:
            continue
        filt = params["groups"][f"l{i}"]["mix"]["filter"]
        n_stack = jax.tree.leaves(filt)[0].shape[0]
        outs = [_distill_layer(jax.tree.map(lambda x, g=g: x[g], filt), hcfg,
                               L, d, steps, objective, init)
                for g in range(n_stack)]
        dp, err = jax.tree.map(lambda *xs: jnp.stack(xs), *outs)
        new_params["groups"][f"l{i}"]["mix"]["distilled"] = dp
        errs[f"l{i}"] = err
    return new_params, errs


def distillation_certificate(params, cfg, L: Optional[int] = None) -> Dict:
    """Measured per-layer distillation-error certificate for a distilled
    model: materialize every Hyena layer's TRUE filters and the distilled
    modal reconstruction at horizon L and record the worst-case gap.

    Per layer, ``l1`` = sum over positions of the max-over-filter error —
    the error any single conv output can accumulate over an L-token
    generation through that layer; ``max_abs`` is the worst single
    position. ``total_l1`` sums the layers and is what the serving drift
    gate (benchmarks/check_regression.py --drift) scales into a bound on
    steady-state logits divergence. The stored distilled passthrough
    absorbed the explicit bias (h0_total = h[0] + bias), so the bias is
    subtracted back out before comparing against the raw filters. Returns
    plain floats (JSON-ready).
    """
    from repro.models.hyena import materialize_filters
    from repro.configs.base import HYENA

    hcfg = cfg.hyena
    L = L or min(cfg.max_seq, 8192)
    layers: Dict[str, Dict[str, float]] = {}
    total = 0.0

    def entry_err(block_params):
        h, bias = materialize_filters(block_params["filter"], L, hcfg)
        dp = block_params["distilled"]
        ssm = ModalSSM(dp["log_a"], dp["theta"], dp["R_re"], dp["R_im"],
                       dp["h0"] - bias)
        return jnp.abs(eval_filter(ssm, L) - h)

    for i, kind in enumerate(cfg.pattern):
        if kind != HYENA:
            continue
        gp = params["groups"][f"l{i}"]["mix"]
        if "distilled" not in gp:
            raise ValueError("distillation_certificate requires distilled "
                             "params (run distill_model first)")
        err = jax.vmap(entry_err)(gp)               # (G, filters..., L)
        per_pos = jnp.max(err.reshape(-1, L), axis=0)
        l1 = float(jnp.sum(per_pos))
        layers[f"l{i}"] = {"max_abs": float(jnp.max(err)), "l1": l1}
        total += l1
    return {"layers": layers, "total_l1": total, "horizon": int(L)}
