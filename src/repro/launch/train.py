"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Single-host it runs directly; on a real cluster each host calls
``jax.distributed.initialize()`` first (``--distributed``) and the same
program runs SPMD across pods. Mesh axes and sharding rules are the
launch-time levers; the DOLMA tiering decision (moment ladder, FSDP
streaming) happens automatically per device budget.

CPU-demo sizes by default; pass --full to use the architecture's real config
(requires accelerators).
"""
from __future__ import annotations

import argparse
import json

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config, reduced_config
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models.sharding import use_mesh, use_rules
from repro.optim import AdamWConfig, CompressionConfig
from repro.train.loop import LoopConfig, train
from repro.train.step import TrainStepConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="mamba2-130m", choices=ARCH_IDS)
    ap.add_argument("--full", action="store_true",
                    help="full config (accelerator-scale)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--remat", default="full",
                    help="none|full|full_flat|dots|dots_no_batch")
    ap.add_argument("--no-prefetch-under-remat", action="store_true",
                    help="disable the dual buffer inside remat boundaries "
                         "(pre-unification behaviour; overlap left to XLA)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--moment-style", default="f32", choices=["f32", "bf16", "int8"])
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--rules", default=None, help="JSON sharding-rule overrides")
    ap.add_argument("--mesh", default=None,
                    help="'data,model[,pod]' axis sizes, e.g. '4,2'")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--distributed", action="store_true",
                    help="call jax.distributed.initialize() (multi-host)")
    args = ap.parse_args()

    if args.distributed:
        jax.distributed.initialize()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = reduced_config(cfg, dtype=jnp.float32)

    mesh = None
    if args.mesh:
        sizes = tuple(int(s) for s in args.mesh.split(","))
        axes = ("data", "model", "pod")[: len(sizes)]
        mesh = make_mesh(sizes, axes)

    step_cfg = TrainStepConfig(
        remat=args.remat,
        microbatches=args.microbatches,
        prefetch_under_remat=not args.no_prefetch_under_remat,
        compression=CompressionConfig(enabled=args.compress_grads),
    )
    opt_cfg = AdamWConfig(lr=args.lr, moment_style=args.moment_style,
                          decay_steps=args.steps)
    loop_cfg = LoopConfig(
        steps=args.steps, batch=args.batch, seq=args.seq, seed=args.seed,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
    )
    rules = json.loads(args.rules) if args.rules else {}

    print(f"arch={cfg.name} params~{cfg.param_count()/1e6:.1f}M "
          f"devices={jax.device_count()} mesh={mesh and dict(mesh.shape)}")
    with use_mesh(mesh), use_rules(**rules):
        res = train(cfg, step_cfg, opt_cfg, loop_cfg)
    print(f"done: step {res.final_step}, loss {res.losses[0]:.4f} -> "
          f"{res.losses[-1]:.4f}; stragglers={len(res.straggler_events)}"
          + (f"; resumed from {res.restored_from}" if res.restored_from else ""))


if __name__ == "__main__":
    main()
