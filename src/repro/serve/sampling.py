"""Token sampling: greedy, temperature, top-k, top-p (nucleus).

`sample_token` takes python-scalar params shared across the batch (one
request replicated, or homogeneous batches). `sample_token_slots` takes
per-row (B,) parameter vectors — the continuous-batching engine serves
requests with heterogeneous sampling params in one batched step.

PRNG key streams: `sample_token_slots` accepts either one key (2,) that is
split across rows (legacy behavior), or per-row keys (B, 2). The serving
engine derives per-row keys from a per-(slot, token-index) key tree (see
serve/README.md "Key tree") so the speculative and non-speculative decode
paths consume identical key streams per emitted-token position — that is
what `filter_logits` is factored out for: the speculative verifier applies
the exact same temperature/top-k/top-p filtering to target and draft
distributions before rejection sampling.

The top-k and top-p cutoffs come from an exact per-row threshold search
on the logits' float32 order (`filter_logits`), not from a sort of the
vocabulary.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def sample_token(key, logits, *, temperature: float = 1.0, top_k: int = 0,
                 top_p: float = 1.0):
    """logits: (B, V) -> (B,) int32. One pipeline: scalar params broadcast
    into the per-slot implementation so the two paths can never diverge."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    B = logits.shape[0]
    return sample_token_slots(
        key, logits,
        temperature=jnp.full((B,), temperature, jnp.float32),
        top_k=jnp.full((B,), top_k, jnp.int32),
        top_p=jnp.full((B,), top_p, jnp.float32))


_I32_MIN = -(2 ** 31)
_I32_MAX = 2 ** 31 - 1


def _order_keys(x):
    """int32 keys that order as the float32 values `x` compare: the bit
    pattern with the low 31 bits flipped for negatives (so -inf is the
    lowest), -0.0 folded onto +0.0 (they compare equal), and every NaN at
    the top (where a descending sort puts it)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    k = jnp.where(b < 0, b ^ _I32_MAX, b)
    k = jnp.where(x == 0.0, 0, k)
    return jnp.where(jnp.isnan(x), _I32_MAX, k)


def _largest_key(holds, hi):
    """Per row, the largest int32 t <= hi for which `holds(t)` ((B,) bool,
    true for every t below some row cutoff) is true, by bisection over the
    whole int32 range: 32 fixed steps, each one reduction over the row.
    A row where it holds nowhere above INT32_MIN gets INT32_MIN."""
    def step(_, lohi):
        lo, hi = lohi
        # ceil((lo + hi) / 2) without int32 overflow
        mid = (lo >> 1) + (hi >> 1) + (((lo & 1) + (hi & 1) + 1) >> 1)
        ok = holds(mid)
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid - 1)

    lo = jnp.full(hi.shape, _I32_MIN, jnp.int32)
    return jax.lax.fori_loop(0, 32, step, (lo, hi))[0]


def filter_logits(logits, *, temperature, top_k, top_p):
    """Temperature-scaled + top-k/top-p-filtered logits.

    logits: (B, V); temperature/top_k/top_p: (B,). Returns (B, V) float32
    with -inf outside each row's sampling support — softmax of the result is
    the exact distribution `sample_token_slots` draws from (rows with
    temperature <= 0 are greedy there and ignore this). Shared by the
    per-slot sampler and the speculative-decoding verifier so the rejection
    test compares the same filtered distributions the sampler uses.

    Each cutoff is found by an exact threshold search on int32 order keys
    (`_order_keys`), not a sort: top-k keeps every token at or above the
    largest key with at least k tokens at or above it (the k-th largest
    value, ties kept); top-p, over the top-k survivors, every token at or
    above the largest key whose at-or-above probability mass reaches top_p
    (the value where a descending cumulative sum first reaches top_p, ties
    kept). Both are the sets a full sort would keep. "Exact" holds up to
    float32 summation: the masked sums add in another order than a
    cumulative sum does, so a row whose mass at the cutoff lies within
    float32 rounding of top_p may keep one value more or less.
    """
    B, V = logits.shape
    temperature = jnp.asarray(temperature, jnp.float32)
    top_k = jnp.asarray(top_k, jnp.int32)
    top_p = jnp.asarray(top_p, jnp.float32)
    lg = logits.astype(jnp.float32) / jnp.clip(temperature, 1e-6)[:, None]
    key = _order_keys(lg)
    hi = jnp.max(key, axis=-1)

    def no_cut():
        return jnp.full((B,), _I32_MIN, jnp.int32)

    # per-row top-k (k <= 0 -> V keeps all); skipped when no row sets it
    k_eff = jnp.where(top_k > 0, jnp.clip(top_k, 1, V), V)
    t_k = jax.lax.cond(
        jnp.any(top_k > 0),
        lambda: _largest_key(
            lambda t: jnp.sum(key >= t[:, None], axis=-1) >= k_eff, hi),
        no_cut)
    lg = jnp.where(key < t_k[:, None], -jnp.inf, lg)
    # per-row top-p over the top-k survivors (skipped when no row sets
    # it); the cutoff is at most the row's largest key, so top_p = 0 keeps
    # the argmax and its ties
    def top_p_cut():
        e = jnp.exp(lg - jnp.max(lg, axis=-1, keepdims=True))
        need = top_p * jnp.sum(e, axis=-1)
        return _largest_key(
            lambda t: jnp.sum(jnp.where(key >= t[:, None], e, 0.0), axis=-1)
            >= need, hi)

    t_p = jax.lax.cond(jnp.any(top_p < 1.0), top_p_cut, no_cut)
    return jnp.where((top_p[:, None] < 1.0) & (key < t_p[:, None]), -jnp.inf,
                     lg)


def sample_token_slots(key, logits, *, temperature, top_k, top_p):
    """Per-slot sampling. logits: (B, V); temperature/top_k/top_p: (B,).

    Rows with temperature <= 0 are greedy; top_k <= 0 / top_p >= 1 disable
    the respective filter for that row. `key` is either a single PRNG key
    (2,) split across rows, or per-row keys (B, 2) — the serving engine
    passes per-row keys from its per-(slot, token-index) key tree so one
    slot's draw never perturbs another's and the speculative path can replay
    the identical stream.
    """
    B, V = logits.shape
    temperature = jnp.asarray(temperature, jnp.float32)
    # NaN-proof greedy: argmax over the raw logits with NaN masked to -inf,
    # so a poisoned row yields a deterministic token (index 0 when the whole
    # row is non-finite) instead of NaN-comparison-dependent junk
    raw = jnp.where(jnp.isnan(logits), -jnp.inf, logits).astype(jnp.float32)
    greedy = jnp.argmax(raw, axis=-1).astype(jnp.int32)

    def sample(_):
        lg = filter_logits(logits, temperature=temperature, top_k=top_k,
                           top_p=top_p)
        # degenerate rows — filtering left no finite support (e.g. top_p=0)
        # or NaN logits leaked through — would softmax to NaN probabilities;
        # fall back to argmax over the raw logits for those rows
        bad = (~jnp.any(jnp.isfinite(lg), axis=-1)
               | jnp.any(jnp.isnan(lg), axis=-1))
        keys = key if key.ndim == 2 else jax.random.split(key, B)
        lg_safe = jnp.where(bad[:, None], 0.0, lg)
        sampled = jax.vmap(jax.random.categorical)(keys,
                                                   lg_safe).astype(jnp.int32)
        sampled = jnp.where(bad, greedy, sampled)
        return jnp.where(temperature <= 0.0, greedy, sampled)

    # all-greedy fast path: skips the top-k/top-p filter (the serving hot
    # loop calls this every tick / every draft-scan step)
    return jax.lax.cond(jnp.all(temperature <= 0.0), lambda _: greedy,
                        sample, None)
