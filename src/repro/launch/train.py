"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs real steps on the available devices (CPU smoke scale by default; the
same driver pjit-compiles on TPU meshes).  Wires together: config system,
synthetic data pipeline, sharded train step, SplitZip-compressed
checkpointing, fault-tolerant resume, and optional compressed cross-pod
gradient sync.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ShapeConfig, get_config
from repro.distributed import checkpoint as CKPT
from repro.distributed.sharding import ShardingPolicy
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.training import grad_compress as GC
from repro.training import optimizer as OPT
from repro.training import train_step as TS
from repro.training.data import SyntheticTokenStream


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compress", action="store_true")
    ap.add_argument("--mesh", default="", help="e.g. '2,2' => data=2,model=2")
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", seq_len=args.seq, global_batch=args.batch,
                        kind="train")

    policy = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split(","))
        axes = ("pod", "data", "model")[-len(dims):]
        policy = ShardingPolicy(make_mesh(dims, axes))

    opt_cfg = OPT.AdamWConfig(lr=args.lr, total_steps=max(args.steps, 2),
                              warmup_steps=max(args.steps // 10, 1))
    step_fn = jax.jit(TS.make_train_step(cfg, opt_cfg, policy,
                                         grad_compress=args.grad_compress,
                                         kv_block=min(args.seq, 1024)))
    data = SyntheticTokenStream(cfg, shape)

    # one Checkpointer for the whole run: the TransferPlan (and its session)
    # is built once per state structure, and every save/restore accumulates
    # into one TransferStats surface
    ckpt = CKPT.Checkpointer(args.ckpt_dir) if args.ckpt_dir else None

    start_step = 0
    state = TS.init_state(cfg, jax.random.PRNGKey(0))
    if args.resume and ckpt and CKPT.latest_step(args.ckpt_dir) is not None:
        state, extra, start_step = ckpt.restore(state)
        print(f"resumed from step {start_step}")

    t0 = time.time()
    for step in range(start_step, args.steps):
        batch = data.batch_at(step)
        state, metrics = step_fn(state, batch)
        if step % args.log_every == 0:
            print(f"step {step:5d}  loss {float(metrics['loss']):.4f}  "
                  f"ce {float(metrics['ce']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}", flush=True)
        if ckpt and (step + 1) % args.ckpt_every == 0:
            path = ckpt.save(step + 1, state, extra={"arch": cfg.name})
            print(f"checkpointed -> {path}")
    dt = time.time() - t0
    tok = (args.steps - start_step) * args.batch * args.seq
    print(f"done: {args.steps - start_step} steps, {tok / max(dt, 1e-9):.0f} tok/s")
    if ckpt is not None:
        s = ckpt.stats
        print(f"checkpoint plane: {s.wire_bytes:.0f} wire bytes  "
              f"refetches {s.refetches}  verify_failures {s.verify_failures}")
    if args.grad_compress and GC.last_stats is not None:
        g = GC.last_stats
        print(f"gradient plane (per step): {g.wire_bytes:.0f} wire bytes  "
              f"raw ring fallbacks {g.raw_refetches}")


if __name__ == "__main__":
    main()
