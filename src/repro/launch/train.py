"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch multihyena-153m \
      --smoke --steps 200 --batch 8 --seq 512 --ckpt /tmp/run1

Uses the local device set (tests/examples) or the production mesh under the
dry-run device flag. Supports restart (--ckpt), remat policy, grad accum and
MoE implementation selection.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_config
from repro.data.pipeline import SyntheticLM, MemmapTokens, make_batches
from repro.distributed.sharding import TRAIN_RULES, tree_shardings, unzip
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.models.model import init_params
from repro.train.checkpoint import Checkpointer
from repro.train.loop import train
from repro.train.train_step import init_opt, make_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--data", type=str, default=None, help=".bin memmap path")
    ap.add_argument("--ckpt", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--remat", default="none")
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--moe-impl", default="dropless")
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    mesh = None
    if args.data_par * args.model_par > 1:
        mesh = make_local_mesh(args.data_par, args.model_par)

    key = jax.random.PRNGKey(args.seed)
    ptree = init_params(key, cfg)
    params, axes = unzip(ptree)
    if mesh is not None:
        shardings = tree_shardings(params, axes, TRAIN_RULES, mesh)
        params = jax.device_put(params, shardings)
    opt = init_opt(params)
    n = sum(p.size for p in jax.tree.leaves(params))
    print(f"[launch] {cfg.name}: {n/1e6:.1f}M params, mesh={mesh}", flush=True)

    if args.data:
        src = MemmapTokens(args.data, vocab=cfg.vocab, seq_len=args.seq,
                           global_batch=args.batch)
    else:
        src = SyntheticLM(vocab=cfg.vocab, seq_len=args.seq,
                          global_batch=args.batch, seed=args.seed)

    step_fn = jax.jit(make_train_step(
        cfg, mesh, base_lr=args.lr, warmup=max(args.steps // 20, 1),
        total_steps=args.steps, moe_impl=args.moe_impl, remat=args.remat,
        accum=args.accum, grad_compression=args.grad_compression))

    ckpt = Checkpointer(args.ckpt) if args.ckpt else None
    start = (ckpt.latest_step() + 1) if (ckpt and ckpt.latest_step() is not None) else 0
    t0 = time.time()
    out = train(step_fn, params, opt,
                make_batches(src, mesh, start_step=start),
                steps=args.steps, ckpt=ckpt, ckpt_every=args.ckpt_every)
    dt = time.time() - t0
    toks = (out["step"] + 1 - start) * args.batch * args.seq
    print(f"[launch] done: step={out['step']} loss={float(out['metrics']['loss']):.4f} "
          f"({toks/dt:.0f} tok/s, stragglers={out['straggler_count']})")


if __name__ == "__main__":
    main()
