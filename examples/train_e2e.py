"""End-to-end driver: train the FULL smollm-135m (135M params) for a few
hundred steps with fault-tolerant checkpointing and a mid-run simulated
node failure + automatic restart.

    PYTHONPATH=src python examples/train_e2e.py \\
        [--steps 300] [--seq 512] [--batch 4] [--fail-at 150] [--reduced]

On this CPU container a full-size step at seq 512 / batch 4 takes a few
seconds; pass --reduced for a quick functional pass.
"""
import argparse
import os
import time

from repro.configs import ARCHS, reduced as make_reduced
from repro.configs.model_config import ShapeConfig, TrainConfig
from repro.launch import compile_cache
from repro.train.trainer import FailureInjector, Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/xar_e2e_ckpt")
    ap.add_argument("--fail-at", type=int, default=0,
                    help="inject a failure at this step (0=off)")
    ap.add_argument("--reduced", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = ARCHS[args.arch]
    if args.reduced:
        cfg = make_reduced(cfg)
    print(f"arch={cfg.name}: {cfg.param_count()/1e6:.0f}M params")

    shape = ShapeConfig("e2e", args.seq, args.batch, "train")
    tcfg = TrainConfig(learning_rate=args.lr)
    trainer = Trainer(cfg, shape, tcfg, ckpt_dir=args.ckpt_dir,
                      ckpt_every=50, async_ckpt=True,
                      total_steps=args.steps)

    injector = (FailureInjector(fail_at_steps=(args.fail_at,))
                if args.fail_at else None)
    t0 = time.time()
    log = trainer.run(steps=args.steps, injector=injector, log_every=10)
    dt = time.time() - t0
    tokens = args.steps * args.seq * args.batch
    print(f"\ndone: loss {log[0]['loss']:.3f} -> {log[-1]['loss']:.3f} "
          f"in {dt/60:.1f} min ({tokens/dt:.0f} tok/s)")
    print(f"checkpoints: {sorted(os.listdir(args.ckpt_dir))}")


if __name__ == "__main__":
    main()
