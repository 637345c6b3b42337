"""The sampler's top-k/top-p cut (`filter_logits`, an exact threshold
search) must keep the same support as the full-vocabulary sort it replaced,
kept here as the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serve.sampling import filter_logits


def filter_logits_sorted(logits, *, temperature, top_k, top_p):
    """Reference: the sort-based cut (two descending sorts and a cumsum)."""
    B, V = logits.shape
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    lg = logits.astype(jnp.float32) / jnp.clip(temperature, 1e-6)[:, None]
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    kth = jnp.take_along_axis(srt, (k_eff - 1)[:, None], axis=-1)
    lg = jnp.where(lg < kth, -jnp.inf, lg)
    srt2 = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt2, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    cutoff_idx = jnp.sum(cum < top_p[:, None], axis=-1)
    cutoff = jnp.take_along_axis(srt2, jnp.clip(cutoff_idx, 0, V - 1)[:, None],
                                 axis=-1)
    return jnp.where((top_p[:, None] < 1.0) & (lg < cutoff), -jnp.inf, lg)


def _mixed(rng, B, V, scale):
    x = (rng.standard_normal((B, V)) * scale).astype(np.float32)
    t = rng.uniform(0.3, 1.5, B).astype(np.float32)
    k = np.where(rng.random(B) < 0.3, rng.integers(1, V // 2, B), 0)
    p = rng.uniform(0.05, 0.99, B).astype(np.float32)
    return x, t, k.astype(np.int32), p


def _ties(rng, B, V):
    # few distinct values, so the k-th value and the nucleus edge are tied
    x, t, k, p = _mixed(rng, B, V, 2.0)
    return np.round(x * 2.0) / 2.0, np.ones(B, np.float32), k, p


def _neg_inf(rng, B, V):
    x, t, k, p = _mixed(rng, B, V, 1.0)
    x[rng.random((B, V)) < 0.5] = -np.inf
    x[0, 3:] = -np.inf                   # three finite values, top_k above
    k[0] = 10
    x[1] = -np.inf                       # no finite value at all
    return x, t, k, p


def _nan(rng, B, V):
    x, t, k, p = _mixed(rng, B, V, 1.0)
    x[0, 7] = np.nan                     # one NaN (the sampler's bad row)
    k[0] = 5
    x[2, 9] = -np.nan                    # sign bit set: still at the top
    k[2] = 3
    x[1] = np.nan                        # all NaN
    return x, t, k, p


def _top_p_edges(rng, B, V):
    x, t, k, _ = _mixed(rng, B, V, 1.0)
    p = np.where(np.arange(B) % 2 == 0, 0.0, 1.0).astype(np.float32)
    return x, t, k, p


def _top_k_edges(rng, B, V):
    x, t, _, p = _mixed(rng, B, V, 1.0)
    k = np.asarray([1, V, V + 5, 0] * (B // 4), np.int32)
    return x, t, k, p


def _cold(rng, B, V):
    x, _, k, p = _mixed(rng, B, V, 1.0)
    t = np.asarray([1e-7, 1e-6, 1e-4, 1e-2] * (B // 4), np.float32)
    return x, t, k, p


def _constant(rng, B, V):
    _, t, k, p = _mixed(rng, B, V, 1.0)
    x = np.full((B, V), 0.25, np.float32)
    x[1] = 0.0                           # -0.0 and +0.0 compare equal
    x[1, ::2] = -0.0
    return x, t, k, p


CASES = {
    "mixed_scale_0.5": lambda r: _mixed(r, 16, 4096, 0.5),
    "mixed_scale_2": lambda r: _mixed(r, 16, 4096, 2.0),
    "mixed_scale_8": lambda r: _mixed(r, 16, 4096, 8.0),
    "ties_at_cutoff": lambda r: _ties(r, 16, 4096),
    "neg_inf": lambda r: _neg_inf(r, 16, 4096),
    "nan_rows": lambda r: _nan(r, 16, 4096),
    "top_p_0_and_1": lambda r: _top_p_edges(r, 16, 4096),
    "top_k_1_and_vocab": lambda r: _top_k_edges(r, 16, 4096),
    "temperature_near_0": lambda r: _cold(r, 16, 4096),
    "constant_rows": lambda r: _constant(r, 16, 4096),
    "full_vocab": lambda r: _mixed(r, 4, 50304, 1.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_filter_logits_matches_sort(case):
    """Same finite set and NaNs as the sort, row for row. The one allowed
    difference is float32 summation at the nucleus edge: a masked sum and a
    cumulative sum round differently, so where the kept mass lies within
    float32 rounding of top_p (sqrt(V) ulps, checked in float64) the two
    may keep one value more or less."""
    x, t, k, p = CASES[case](np.random.default_rng(sum(map(ord, case))))
    V = x.shape[1]
    got = np.asarray(jax.jit(filter_logits)(x, temperature=t, top_k=k,
                                            top_p=p))
    want = np.asarray(jax.jit(filter_logits_sorted)(x, temperature=t,
                                                    top_k=k, top_p=p))
    np.testing.assert_array_equal(np.isnan(got), np.isnan(want))
    tol = np.sqrt(V) * np.finfo(np.float32).eps
    for r in np.nonzero((np.isfinite(got) != np.isfinite(want)).any(-1))[0]:
        small, large = sorted([np.isfinite(got[r]), np.isfinite(want[r])],
                              key=np.sum)
        assert small.any() and not (small & ~large).any(), f"row {r}"
        assert np.unique(x[r][large & ~small]).size == 1, f"row {r}"
        # float64 mass of the smaller set among the top-k survivors
        lg = x[r].astype(np.float64) / max(float(t[r]), 1e-6)
        kk = k[r] if k[r] > 0 else V
        pr = np.where(lg >= np.sort(lg)[::-1][min(kk, V) - 1],
                      np.exp(lg - lg.max()), 0.0)
        mass = pr[small].sum() / pr.sum()
        assert abs(mass - p[r]) <= tol, f"row {r}: {mass} vs top_p {p[r]}"
