"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Builds the mesh (if >1 device), resolves the arch config, applies CLI
overrides, and runs the fault-tolerant trainer.
"""
from __future__ import annotations

import argparse

from repro.config import (CheckpointConfig, OptimizerConfig, ShapeConfig,
                          SHAPES, TrainConfig, apply_overrides, get_config,
                          list_archs)
from repro.launch.mesh import make_mesh
from repro.parallel.sharding import act_rules_for, use_mesh
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(list_archs()))
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--mesh", default=None,
                    help="e.g. 4x2 -> (data=4, model=2)")
    ap.add_argument("--set", nargs="*", default=[],
                    help="config overrides key=value")
    args = ap.parse_args()

    model_cfg = get_config(args.arch, smoke=args.smoke)
    overrides = dict(kv.split("=", 1) for kv in args.set)
    if overrides:
        model_cfg = apply_overrides(model_cfg, overrides)

    shape = (SHAPES[args.shape] if args.shape
             else ShapeConfig("cli", "train", args.seq, args.batch))
    cfg = TrainConfig(
        model=model_cfg, shape=shape,
        optimizer=OptimizerConfig(total_steps=args.steps),
        checkpoint=CheckpointConfig(directory=args.ckpt_dir),
    )

    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[:len(dims)]
        mesh = make_mesh(dims, axes)

    with use_mesh(mesh, act_rules_for(model_cfg, mesh)):
        result = Trainer(cfg, mesh=mesh).run(max_steps=args.steps)
    print(f"done: {result.steps_run} steps, final loss "
          f"{result.losses[-1]:.4f}, stragglers {result.straggler_steps}")


if __name__ == "__main__":
    main()
