"""Per-kernel allclose sweeps vs the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.modal_filter.modal_filter import modal_filter_pallas
from repro.kernels.modal_filter.ref import modal_filter_ref
from repro.kernels.ssm_decode.ref import ssm_decode_ref
from repro.kernels.ssm_decode.ssm_decode import ssm_decode_pallas


def _modal_params(key, C, d):
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    return (jnp.log(jax.random.uniform(k1, (C, d), minval=0.4, maxval=0.97)),
            jax.random.uniform(k2, (C, d), maxval=np.pi),
            jax.random.normal(k3, (C, d)),
            jax.random.normal(k4, (C, d)),
            jax.random.normal(k5, (C,)))


@pytest.mark.parametrize("C,d,L,cb,lb", [
    (8, 4, 512, 8, 128),
    (16, 8, 1024, 8, 512),
    (32, 16, 2048, 16, 256),
    (8, 3, 512, 4, 512),          # odd mode count
])
def test_modal_filter_sweep(C, d, L, cb, lb):
    params = _modal_params(jax.random.PRNGKey(C + d), C, d)
    ref = modal_filter_ref(*params, L)
    out = modal_filter_pallas(*params, L=L, cb=cb, lb=lb, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("B,C,d,bb,cb", [
    (8, 128, 8, 8, 128),
    (16, 256, 16, 8, 64),
    (4, 64, 4, 4, 64),
    (32, 512, 8, 16, 128),
])
def test_ssm_decode_sweep(B, C, d, bb, cb):
    key = jax.random.PRNGKey(B * C)
    params = _modal_params(key, C, d)
    xr = jax.random.normal(jax.random.PRNGKey(1), (B, C, d))
    xi = jax.random.normal(jax.random.PRNGKey(2), (B, C, d))
    u = jax.random.normal(jax.random.PRNGKey(3), (B, C))
    ref = ssm_decode_ref(xr, xi, u, *params)
    out = ssm_decode_pallas(xr, xi, u, *params, bb=bb, cb=cb, interpret=True)
    for r, o in zip(ref, out):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


@pytest.mark.parametrize("B,C", [(8, 864), (12, 864), (8, 2048), (5, 864)])
def test_ssm_decode_default_blocks(B, C):
    """Default tiling at served widths (MultiHyena-153M / -1.3B channels,
    slot counts that are not multiples of 8)."""
    d = 8
    params = _modal_params(jax.random.PRNGKey(C), C, d)
    xr = jax.random.normal(jax.random.PRNGKey(1), (B, C, d))
    xi = jax.random.normal(jax.random.PRNGKey(2), (B, C, d))
    u = jax.random.normal(jax.random.PRNGKey(3), (B, C))
    ref = ssm_decode_ref(xr, xi, u, *params)
    out = ssm_decode_pallas(xr, xi, u, *params, interpret=True)
    for r, o in zip(ref, out):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=2e-5)


def test_ssm_decode_rejects_untileable_block():
    params = _modal_params(jax.random.PRNGKey(0), 96, 4)
    x = jnp.zeros((4, 96, 4))
    with pytest.raises(ValueError, match="multiple of 8"):
        ssm_decode_pallas(x, x, jnp.zeros((4, 96)), *params, cb=12,
                          interpret=True)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,Hq,Hkv,hd,window", [
    (2, 256, 4, 2, 64, 0),
    (1, 512, 8, 1, 64, 0),        # MQA
    (2, 256, 4, 4, 128, 0),       # MHA
    (2, 256, 4, 2, 64, 128),      # windowed
])
def test_flash_attention_sweep(B, S, Hq, Hkv, hd, window, dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (B, S, Hq, hd), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (B, S, Hkv, hd), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (B, S, Hkv, hd), dtype)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    out = flash_attention_pallas(q, k, v, causal=True, window=window,
                                 interpret=True)
    atol = 2e-6 * S if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=max(atol, 0.05))


def test_flash_attention_noncausal():
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 256, 4, 64))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 2, 64))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 2, 64))
    ref = flash_attention_ref(q, k, v, causal=False)
    out = flash_attention_pallas(q, k, v, causal=False, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.slow
def test_chunked_mha_matches_ref_paths():
    """The portable chunked path and the unrolled dry-run path agree with the
    dense reference (both window and full causal)."""
    from repro.models.attention import _chunked_mha_unrolled, chunked_mha, mha
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 512, 8, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (2, 512, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (2, 512, 2, 32))
    for w in (0, 128):
        ref = mha(q, k, v, causal=True, window=w)
        c1 = chunked_mha(q, k, v, causal=True, window=w, block=128)
        c2 = _chunked_mha_unrolled(q, k, v, causal=True, window=w, block=128)
        np.testing.assert_allclose(np.asarray(c1), np.asarray(ref), atol=2e-5)
        np.testing.assert_allclose(np.asarray(c2), np.asarray(ref), atol=2e-5)
