"""Training entrypoint.

    PYTHONPATH=src python -m repro.launch.train --arch h2o-danube3-4b \
        --smoke --steps 50 [--mesh-test]     # CPU-sized run
    # On a real fleet: run under the production mesh with --mesh-test
    # replaced by the cluster's jax.distributed initialization.
"""
import argparse

import jax

from repro.configs import base as cb
from repro.configs.base import ShapeConfig
from repro.core import config
from repro.models.transformer import build_model
from repro.optim.adamw import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="h2o-danube3-4b",
                    choices=cb.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--policy", default="bf16",
                    choices=["fp32", "bf16", "int8"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()
    config.use_compile_cache()

    cfg = cb.get(args.arch, smoke=args.smoke)
    model = build_model(cfg, policy=args.policy)
    print(f"[train] {cfg.name}: {cfg.total_params()/1e6:.1f}M params, "
          f"policy={args.policy}, devices={jax.device_count()}")
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    tcfg = TrainerConfig(steps=args.steps, microbatches=args.microbatches,
                         checkpoint_dir=args.ckpt,
                         opt=AdamWConfig(lr=args.lr))
    trainer = Trainer(model, shape, tcfg)
    params = opt = None
    start = 0
    if args.resume and args.ckpt:
        p_like, o_like = trainer.init_state()
        params, opt, start = trainer.restore(p_like, o_like)
        print(f"[train] resumed from step {start}")
    trainer.run(params, opt, start_step=start)
    print("[train] done; final loss",
          trainer.metrics_log[-1]["loss"] if trainer.metrics_log else "n/a")


if __name__ == "__main__":
    main()
