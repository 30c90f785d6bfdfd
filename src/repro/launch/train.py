"""Training launcher.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \\
      --reduced --steps 100 --seq 256 --batch 8 --ckpt-dir /tmp/ckpt
  PYTHONPATH=src python -m repro.launch.train --arch yi-6b \\
      --mesh 2x2 --devices 4 --reduced --steps 20

``--devices N`` forces N host devices (CPU testing); the production
path runs under real TPU runtime device counts.  ``--xartrek`` routes
steps through the migration runtime with HOST/AUX variants.
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default=None,
                    help="assigned shape name (default: custom --seq/--batch)")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--mesh", default=None, help="e.g. 2x2 or 2x2x2 (pod)")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N host devices (CPU testing)")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--async-ckpt", action="store_true")
    ap.add_argument("--fail-at", type=int, nargs="*", default=[],
                    help="inject failures at these steps (tests restart)")
    ap.add_argument("--xartrek", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    from repro.configs import get_arch, get_shape, reduced
    from repro.configs.model_config import ShapeConfig, TrainConfig
    from repro.launch import compile_cache
    from repro.parallel.mesh import make_auto_mesh
    from repro.train.trainer import FailureInjector, Trainer

    compile_cache.enable()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    shape = (get_shape(args.shape) if args.shape
             else ShapeConfig("custom", args.seq, args.batch, "train"))

    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = (("pod", "data", "model") if len(dims) == 3
                else ("data", "model"))
        mesh = make_auto_mesh(dims, axes)

    tcfg = TrainConfig(microbatches=args.microbatches,
                       learning_rate=args.lr, seed=args.seed)
    trainer = Trainer(cfg, shape, tcfg, mesh=mesh, ckpt_dir=args.ckpt_dir,
                      ckpt_every=args.ckpt_every, async_ckpt=args.async_ckpt,
                      total_steps=args.steps, seed=args.seed)

    if args.xartrek:
        from repro.core.function import FunctionRegistry
        from repro.core.runtime import XarTrekRuntime
        registry = FunctionRegistry()
        trainer.register_migratable(registry, aux_step=trainer.step_fn)
        runtime = XarTrekRuntime(mesh=mesh, registry=registry)
        params, opt_state = trainer.init_or_restore()[:2]
        batch = trainer.pipeline.batch(0)
        runtime.prepare("train_step", params, opt_state, batch)
        trainer.runtime = runtime

    injector = (FailureInjector(tuple(args.fail_at))
                if args.fail_at else None)
    log = trainer.run(steps=args.steps, injector=injector)
    print(f"final loss: {log[-1]['loss']:.4f} after {log[-1]['step']} steps")


if __name__ == "__main__":
    main()
