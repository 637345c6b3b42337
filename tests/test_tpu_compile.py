"""Compile the serving path's device programs for a described TPU v5e.

Nothing runs: the TPU compiler that ships with JAX compiles for one chip of
a `v5e:2x2` topology described in a fixture, so a Mosaic refusal, a missing
TPU lowering or a program that does not fit is caught on the CPU. The
topology is described inside the fixture (never at import: only one process
at a time may load the TPU library), and the persistent compilation cache is
off around these compiles (a described-device entry cannot be read back).
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("B,C", [(8, 864), (12, 864), (8, 2048)])
def test_ssm_decode_kernel_compiles(one_chip, B, C):
    from repro.kernels.ssm_decode.ssm_decode import ssm_decode_pallas
    d, f32 = 8, jnp.float32
    shapes = _on(one_chip, [jax.ShapeDtypeStruct(s, f32) for s in (
        (B, C, d), (B, C, d), (B, C), (C, d), (C, d), (C, d), (C, d), (C,))])
    fn = jax.jit(functools.partial(ssm_decode_pallas, interpret=False))
    compiled = fn.lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B", [8, 32])
def test_mamba2_decode_kernel_compiles(one_chip, B):
    """Granite-4.0-H's Mamba-2 widths: 64 heads of 64 x 128 state, one
    group."""
    from repro.kernels.mamba2_decode.mamba2_decode import mamba2_decode_pallas
    H, P, N, f32 = 64, 64, 128, jnp.float32
    shapes = _on(one_chip, [jax.ShapeDtypeStruct(s, f32) for s in (
        (B, H, P, N), (B, H, P), (B, H), (B, H), (B, 1, N), (B, 1, N), (H,))])
    fn = jax.jit(functools.partial(mamba2_decode_pallas, interpret=False))
    compiled = fn.lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_multihyena_decode_step_compiles_with_kernel(one_chip, monkeypatch):
    """Full-width MultiHyena-153M pooled decode (8 slots) takes the Pallas
    kernel on a TPU and fits the chip."""
    from repro.configs import get_config
    from repro.distributed.sharding import unzip
    from repro.kernels.ssm_decode import ops
    from repro.models.model import decode_step, init_cache, init_params
    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = get_config("multihyena-153m")
    params = _on(one_chip, jax.eval_shape(
        lambda: unzip(init_params(jax.random.PRNGKey(0), cfg))[0]))
    cache = _on(one_chip, jax.eval_shape(
        lambda: unzip(init_cache(cfg, 8, 1024, cache_kind="native",
                                 per_slot=True))[0]))
    tokens = jax.ShapeDtypeStruct((8, 1), jnp.int32, sharding=one_chip)
    # a fresh partial: no trace cached by a CPU-path caller is reused
    step = jax.jit(functools.partial(decode_step, cfg=cfg))
    compiled = step.lower(params, cache, tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < V5E_HBM_BYTES


def test_distill_filters_compiles(one_chip):
    """One MultiHyena-153M layer's filters (8 heads) through Kung init (the
    Hankel block iteration, the host eigenvalue callback) and the fit."""
    from repro.core.distill import distill_filters
    h = jax.ShapeDtypeStruct((8, 1024), jnp.float32, sharding=one_chip)
    fn = jax.jit(functools.partial(distill_filters, d=8, steps=100))
    compiled = fn.lower(h).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < V5E_HBM_BYTES
