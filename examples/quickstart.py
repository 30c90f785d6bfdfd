"""Quickstart: train a small model, checkpoint it, and serve from it.

    PYTHONPATH=src python examples/quickstart.py [--steps 60]

Uses the reduced smollm-135m config so the whole thing runs on a laptop
CPU in about a minute.  See examples/train_e2e.py for the full-size run.
"""
import argparse
import tempfile

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, reduced
from repro.configs.model_config import ShapeConfig, TrainConfig
from repro.launch import compile_cache
from repro.serve.engine import ServeEngine
from repro.train.trainer import Trainer


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = reduced(ARCHS["smollm-135m"])
    shape = ShapeConfig("quickstart", seq_len=64, global_batch=8, kind="train")
    tcfg = TrainConfig(learning_rate=3e-3)

    ckpt_dir = tempfile.mkdtemp(prefix="xar_quickstart_")
    trainer = Trainer(cfg, shape, tcfg, ckpt_dir=ckpt_dir, ckpt_every=20,
                      total_steps=args.steps)
    log = trainer.run(steps=args.steps, log_every=20)
    print(f"\ntrained {args.steps} steps: loss "
          f"{log[0]['loss']:.3f} -> {log[-1]['loss']:.3f}")

    params, _ = trainer.final_state
    engine = ServeEngine(cfg, params=params)
    prompts = jax.random.randint(jax.random.PRNGKey(7), (4, 16), 0,
                                 cfg.vocab_size, jnp.int32)
    res = engine.generate(prompts, max_new_tokens=8)
    print(f"generated {res.tokens.shape} tokens at "
          f"{res.tokens_per_second:.1f} tok/s")
    print(f"checkpoints in {ckpt_dir}")


if __name__ == "__main__":
    main()
