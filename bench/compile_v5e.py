#!/usr/bin/env python3
"""Compile each cell's device programs for a described TPU v5e, no chip.

  JAX_PLATFORMS=cpu python3 bench/compile_v5e.py [--workload <cell> ...]

For each cell of BENCHMARK.json: the pooled decode step the engine runs
(guarded variant, with the TPU kernels its architecture takes on the chip,
`use_tpu_kernels`) at the cell's slots and `max_len`, and the bucketed
prefill at the largest bucket its traffic uses, batch `prefills_per_step`.
Compiled by the TPU compiler that ships with JAX for one chip of a
described `v5e:2x2`; prints each program's `memory_analysis()` and, for
each kernel of the architecture's `KERNELS`, whether the decode holds it.
Nothing runs, so this says nothing of times. The persistent compile cache
stays off.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import re
import sys

import run
import trace_reduce
import traffic


def _on(sharding, tree):
    import jax
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    return {k: int(getattr(m, k)) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "generated_code_size_in_bytes",
        "alias_size_in_bytes")}


def kernels_held(hlo: str, kernels: dict) -> dict:
    """For each kernel: whether an instruction of the compiled HLO has a
    name its trace-op pattern matches (the trace names ops after them)."""
    names = {trace_reduce.stable_name(n)
             for n in re.findall(r"%([\w.-]+) = ", hlo)}
    return {k: any(re.search(op, n) for n in names)
            for k, (op, _) in kernels.items()}


def compile_cell(cell: run.Cell, chip) -> dict:
    import jax
    import jax.numpy as jnp
    import program
    program._import_path()
    from repro.models.layers import NOCTX
    from repro.models.model import init_cache, prefill
    from repro.serve.engine import _decode_step_guarded
    arch = cell.arch
    arch.use_tpu_kernels()
    mix, mcfg = cell.mix, arch.model_config(cell.cfg)
    B, max_len, K = mix["slots"], mix["max_len"], mix["prefills_per_step"]
    params = _on(chip, jax.eval_shape(
        lambda: arch.to_program(arch.make_weights(cell.cfg, 0))))
    cache = _on(chip, jax.eval_shape(lambda: jax.tree.map(
        lambda p: p.value, init_cache(mcfg, B, max_len, cache_kind="native",
                                      per_slot=True),
        is_leaf=lambda x: hasattr(x, "axes"))))
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    dec = jax.jit(functools.partial(_decode_step_guarded, cfg=mcfg,
                                    ctx=NOCTX), donate_argnums=(1,))
    dec_c = dec.lower(params, cache, s((B, 1), jnp.int32),
                      s((), jnp.float32)).compile()
    longest = traffic.length_bounds(mix["prompt"])[1]
    bucket = min(max(8, 1 << (longest - 1).bit_length()), max_len)
    pre = jax.jit(functools.partial(prefill, cfg=mcfg, max_len=max_len,
                                    cache_kind="native"))
    pre_c = pre.lower(params, s((K, bucket), jnp.int32),
                      lengths=s((K,), jnp.int32)).compile()
    return {"cell": cell.name,
            "decode": dict(mem(dec_c), slots=B, max_len=max_len,
                           kernels=kernels_held(dec_c.as_text(),
                                                arch.KERNELS)),
            "prefill": dict(mem(pre_c), batch=K, bucket=bucket)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", action="append")
    a = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    bench = json.loads((run.ROOT / "BENCHMARK.json").read_text())
    names = a.workload or [c["name"] for c in bench["workloads"]]
    for name in names:
        print(json.dumps(compile_cell(run.Cell(run.ROOT, name), chip)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
