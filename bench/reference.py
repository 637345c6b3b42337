"""What every architecture's plain reference shares.

Each architecture's forward pass is in `bench/archs/<arch>.py`
(`logits_at`): plain jax.numpy in float32 with every matrix product at
HIGHEST precision, one request at a time, importing nothing of the program
and reading only the weights that its module made. Here: the matrix product
they use, whose `precision="fp8"` is the control (both operands rounded to
float8 e4m3 with one scale per tensor, the next precision below the
bfloat16 that the configurations state), and what the harness reads from
the reference's logits: the greedy gap of served tokens (`gaps`) and the
statistics of sampled ones (`sampled`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
FP8_MAX = 448.0                       # largest finite float8 e4m3 value


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul(a, b, precision: str):
    """a @ b at HIGHEST; with `precision="fp8"`, of the operands rounded to
    float8 e4m3."""
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.matmul(a, b, precision=HIGHEST)


def gaps(ref_logits, tokens, n):
    """Per position j < n: how far the logit of tokens[j] lies below the
    reference's best (0 where it is the reference's argmax)."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return jnp.where(jnp.arange(tokens.shape[0]) < n, best - got, 0.0)


@jax.jit
def sampled(ref_logits, tokens, n, temperature, top_p):
    """What the reference says of tokens sampled at `temperature` with
    nucleus `top_p`, per position j < n (0 beyond):

      * `above`: the probability mass, at that temperature, of the tokens
        more likely than tokens[j]; the nucleus holds a token iff this is
        below `top_p`;
      * `surprise`: -log p(tokens[j]) at that temperature;
      * `mean`, `var`: the surprise's mean and variance when the token is
        drawn from the reference's own nucleus, renormalised.

    A sampler that draws from the right distribution gives surprises whose
    sum, less the sum of means, is about as large as the root of the
    summed variances; a wrong temperature or a missing nucleus cut moves it
    by many of those."""
    logp = jax.nn.log_softmax(ref_logits / temperature, axis=-1)
    p = jnp.exp(logp)
    tok = jnp.take_along_axis(logp, tokens[:, None], axis=-1)
    above = jnp.sum(jnp.where(logp > tok, p, 0.0), axis=-1)
    srt = jnp.sort(logp, axis=-1)[:, ::-1]
    before = jnp.cumsum(jnp.exp(srt), axis=-1) - jnp.exp(srt)
    k = jnp.sum(before < top_p, axis=-1, keepdims=True) - 1
    keep = logp >= jnp.take_along_axis(srt, k, axis=-1)
    q = jnp.where(keep, p, 0.0)
    q = q / jnp.sum(q, axis=-1, keepdims=True)
    mean = jnp.sum(q * -logp, axis=-1)
    var = jnp.sum(q * jnp.square(logp), axis=-1) - jnp.square(mean)
    live = jnp.arange(tokens.shape[0]) < n
    z = lambda x: jnp.where(live, x, 0.0)
    return {"above": z(above), "surprise": z(-tok[:, 0]), "mean": z(mean),
            "var": z(var)}
