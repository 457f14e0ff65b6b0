"""Production training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-8b \
      --shape train_4k [--reduced] [--steps N] [--ckpt-dir DIR] \
      [--mesh dxm] [--ps-mode] [--resume]

On real hardware the full config runs on the production mesh; on this
CPU container use --reduced (same family, small dims) and optionally a
small --mesh over forced host devices.
"""
from __future__ import annotations

import argparse
import dataclasses
import logging

import jax

from repro.configs import get_config, get_reduced_config, get_shape
from repro.data.pipeline import DataConfig
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.parallel.sharding import make_ctx
from repro.train.trainer import Trainer, TrainerConfig


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default=None,
                    help="e.g. 2x2 (data x model) over host devices; "
                         "'production' for (16,16)")
    ap.add_argument("--ps-mode", action="store_true",
                    help="parameter-server (ZeRO-3/fsdp) weight sharding")
    ap.add_argument("--grad-compression", default=None,
                    choices=[None, "bf16", "int8"])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")

    acfg = (get_reduced_config(args.arch) if args.reduced
            else get_config(args.arch))
    if args.ps_mode:
        acfg = acfg.replace(parallel=dataclasses.replace(
            acfg.parallel, fsdp=True, ps_mode=True))
    if args.grad_compression:
        acfg = acfg.replace(train=dataclasses.replace(
            acfg.train, grad_compression=args.grad_compression))

    shape = get_shape(args.shape)
    if args.seq_len or args.global_batch:
        shape = dataclasses.replace(
            shape, seq_len=args.seq_len or shape.seq_len,
            global_batch=args.global_batch or shape.global_batch)

    mesh = None
    if args.mesh == "production":
        mesh = make_production_mesh()
    elif args.mesh:
        d, m = (int(x) for x in args.mesh.split("x"))
        mesh = make_mesh((d, m), ("data", "model"))
    ctx = make_ctx(acfg, mesh)

    tcfg = TrainerConfig(total_steps=args.steps, ckpt_dir=args.ckpt_dir,
                         ckpt_every=args.ckpt_every)
    trainer = Trainer(ctx, acfg, shape, tcfg, DataConfig())
    if mesh is not None:
        with mesh:
            trainer.train(seed=args.seed)
    else:
        trainer.train(seed=args.seed)
    losses = [r.loss for r in trainer.history]
    print(f"done: {len(trainer.history)} steps, "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, "
          f"stragglers={len(trainer.straggler_events)}")


if __name__ == "__main__":
    main()
