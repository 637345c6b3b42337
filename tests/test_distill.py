"""Distillation quality: exact recovery, order monotonicity, init comparison,
truncation baselines (App. E.3)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import balanced_truncation, eval_filter, init_modal, modal_truncation
from repro.core.distill import (distill_filters, distill_model, fit_residues,
                                host_eigvals, kung_init, top_singular_pairs)
from repro.core.hankel import hankel_matrix
from repro.core.truncation import balanced_truncation_modal


def _rel_err(ssm, h):
    hh = eval_filter(ssm, h.shape[-1])
    return jnp.linalg.norm(hh - h, axis=-1) / jnp.linalg.norm(h, axis=-1)


@pytest.fixture(scope="module")
def target():
    true = init_modal(jax.random.PRNGKey(0), (2,), 6, r_minmax=(0.5, 0.92))
    return eval_filter(true, 384)


def test_exact_recovery_same_order(target):
    ssm, _ = distill_filters(target, 6, steps=1500)
    err = _rel_err(ssm, target)
    assert float(jnp.max(err)) < 0.05, err


def test_error_decreases_with_order(target):
    errs = []
    for m in (1, 2, 4, 6):
        ssm, _ = distill_filters(target, m, steps=600)
        errs.append(float(jnp.max(_rel_err(ssm, target))))
    assert errs[-1] < errs[0]
    # loosely monotone (gradient noise tolerance)
    assert errs[2] <= errs[0] + 1e-3 and errs[3] <= errs[1] + 1e-3


def test_kung_init_beats_random_init_start(target):
    """Kung warm start should begin at much lower loss than random init."""
    kg = kung_init(target, 6)
    rd = init_modal(jax.random.PRNGKey(1), (2,), 6)
    rd = rd._replace(h0=target[..., 0])
    assert float(jnp.max(_rel_err(kg, target))) < \
        float(jnp.max(_rel_err(rd, target)))


def test_fit_residues_is_optimal_given_true_poles(target):
    """With the exact poles, the linear residue solve nearly interpolates."""
    true = init_modal(jax.random.PRNGKey(0), (2,), 6, r_minmax=(0.5, 0.92))
    R = fit_residues(true.poles(), target)
    refit = true._replace(R_re=jnp.real(R), R_im=jnp.imag(R))
    assert float(jnp.max(_rel_err(refit, target))) < 1e-3


def test_balanced_truncation_baseline(target):
    """App. E.3.2: Kung balanced realization reproduces the filter at full
    order and degrades gracefully at low order."""
    h = np.asarray(target[0])
    A, B, C, h0 = balanced_truncation(jnp.asarray(h), 12)
    # impulse response of the realization
    x = B
    imp = [float(h0)]
    for _ in range(len(h) - 1):
        imp.append(float(C @ x))
        x = A @ x
    rel = np.linalg.norm(np.array(imp) - h) / np.linalg.norm(h)
    assert rel < 0.05, rel


def test_modal_truncation_ranking(target):
    ssm, _ = distill_filters(target, 6, steps=800)
    tr = modal_truncation(ssm, 3, refit=True, h=target)
    assert tr.log_a.shape[-1] == 3
    # truncation error bounded by the discarded-mode influence (E.2 spirit)
    full = float(jnp.max(_rel_err(ssm, target)))
    trunc = float(jnp.max(_rel_err(tr, target)))
    assert trunc >= full - 1e-5
    assert trunc < 1.0


def test_h2_equals_l2_objective(target):
    """Parseval: H2- and l2-distilled systems reach similar errors."""
    s1, _ = distill_filters(target, 4, steps=600, objective="l2")
    s2, _ = distill_filters(target, 4, steps=600, objective="h2")
    e1 = float(jnp.max(_rel_err(s1, target)))
    e2 = float(jnp.max(_rel_err(s2, target)))
    assert abs(e1 - e2) < 0.15, (e1, e2)


def test_host_eigvals_match_jnp():
    """The host-callback eigensolver (usable on every backend) agrees with
    jnp.linalg.eigvals on random 16x16 shift matrices, as Kung builds them."""
    Od = jax.random.normal(jax.random.PRNGKey(3), (4, 40, 16))
    A = jnp.linalg.pinv(Od[:, :-1]) @ Od[:, 1:]
    got = np.asarray(jax.jit(host_eigvals)(A))
    want = np.asarray(jnp.linalg.eigvals(A))
    assert got.dtype == np.complex64 and got.shape == (4, 16)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.sort_complex(g), np.sort_complex(w),
                                   rtol=1e-4, atol=1e-4)


def test_hankel_matrix_entries():
    h = jax.random.normal(jax.random.PRNGKey(0), (3, 21))
    S = np.asarray(hankel_matrix(h))
    i, j = np.meshgrid(np.arange(10), np.arange(10), indexing="ij")
    np.testing.assert_array_equal(S, np.asarray(h)[:, i + j + 1])


def test_top_singular_pairs_match_dense_svd(target):
    """Block iteration recovers the leading singular values and subspace of
    the Hankel matrix that a dense SVD gives."""
    S = hankel_matrix(target)
    U, s = top_singular_pairs(S, 8)
    U0, s0, _ = jnp.linalg.svd(S)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s0[:, :8]),
                               rtol=1e-3, atol=1e-6)
    # same subspace: projecting the dense vectors onto U loses nothing
    proj = jnp.einsum("bmk,bmj->bkj", U, U0[..., :8])
    np.testing.assert_allclose(np.asarray(jnp.linalg.norm(proj, axis=1)),
                               1.0, atol=1e-3)


def test_distill_model_layerwise_matches_stacked():
    """Layer-at-a-time distill_model writes the same distilled params as one
    distill_filters call over every stacked layer's filters at once. Compared
    at the Kung initialization (0 gradient steps): Adam's normalized steps
    amplify the float rounding that batching changes, so later iterates of
    the two runs differ by more than rounding."""
    from repro.configs import get_config, smoke_config
    from repro.distributed.sharding import unzip
    from repro.models.hyena import materialize_filters
    from repro.models.model import init_params
    cfg = smoke_config(get_config("multihyena-153m"))
    params, _ = unzip(init_params(jax.random.PRNGKey(0), cfg))
    L, steps, d = 128, 0, cfg.hyena.distill_order // 2
    out, errs = distill_model(params, cfg, steps=steps, L=L)
    filt = params["groups"]["l0"]["mix"]["filter"]
    h, bias = jax.vmap(lambda f: materialize_filters(f, L, cfg.hyena))(filt)
    ssm, _ = distill_filters(h, d, steps=steps)
    got = out["groups"]["l0"]["mix"]["distilled"]
    want = {"log_a": ssm.log_a, "theta": ssm.theta, "R_re": ssm.R_re,
            "R_im": ssm.R_im, "h0": ssm.h0 + bias}
    assert errs["l0"].shape == h.shape[:2]
    for k, w in want.items():
        np.testing.assert_allclose(np.asarray(got[k]), np.asarray(w),
                                   rtol=1e-4, atol=1e-5, err_msg=k)
