"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On a real TPU fleet each host runs this under the cluster supervisor with
``jax.distributed.initialize()``; device meshes come from launch.mesh.  On
CPU it trains reduced configs (the examples use it).  On a TPU the train
loop compiles its step with compute/communication overlap options
(``train.loop._TPU_OPTIONS``).
"""

from __future__ import annotations

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--reduced", action="store_true",
                    help="train the reduced (CPU-size) config")
    ap.add_argument("--ckpt", default="/tmp/repro_train")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", choices=["none", "local"], default="none")
    args = ap.parse_args()

    from ..configs import SHAPES, ShapeConfig, get_config, reduced_config
    from ..models import build_model
    from ..train.loop import TrainLoopConfig, train
    from ..train.optimizer import AdamWConfig

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced_config(cfg)
    model = build_model(cfg, remat=not args.reduced)
    shape = ShapeConfig("cli", "train", args.seq_len, args.batch)
    schedule = "wsd" if args.arch == "minicpm-2b" else "cosine"
    stats = train(model, shape, TrainLoopConfig(
        n_steps=args.steps, ckpt_root=args.ckpt, grad_accum=args.grad_accum,
        opt=AdamWConfig(peak_lr=args.lr, schedule=schedule,
                        warmup_steps=max(args.steps // 20, 5),
                        total_steps=args.steps)))
    print(f"done: {stats['steps_run']} steps, {stats['restarts']} restarts, "
          f"{stats['wall_s']:.1f}s")


if __name__ == "__main__":
    main()
