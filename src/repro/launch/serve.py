"""Serving launcher: batched generation with the ServeEngine.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \\
      --reduced --batch 4 --prompt-len 32 --new-tokens 16
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--devices", type=int, default=0)
    ap.add_argument("--mesh", default=None)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}")

    import jax
    from repro.launch import compile_cache
    compile_cache.enable()
    import jax.numpy as jnp
    from repro.configs import get_arch, reduced
    from repro.parallel.mesh import make_auto_mesh
    from repro.serve.engine import ServeEngine

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    mesh = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = (("pod", "data", "model") if len(dims) == 3
                else ("data", "model"))
        mesh = make_auto_mesh(dims, axes)

    engine = ServeEngine(cfg, mesh=mesh, seed=args.seed)
    key = jax.random.PRNGKey(args.seed)
    if cfg.family == "audio" and cfg.num_codebooks > 1:
        prompts = jax.random.randint(
            key, (args.batch, cfg.num_codebooks, args.prompt_len), 0,
            cfg.vocab_size, jnp.int32)
    else:
        prompts = jax.random.randint(
            key, (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32)
    kw = {}
    if cfg.family == "vlm":
        kw["patch_embeds"] = jax.random.normal(
            key, (args.batch, min(cfg.num_patches, args.prompt_len),
                  cfg.d_model), jnp.bfloat16)
    res = engine.generate(prompts, max_new_tokens=args.new_tokens, **kw)
    print(f"generated {res.tokens.shape} tokens | "
          f"prefill {res.prefill_ms:.0f} ms | decode {res.decode_ms:.0f} ms "
          f"| {res.tokens_per_second:.1f} tok/s")


if __name__ == "__main__":
    main()
