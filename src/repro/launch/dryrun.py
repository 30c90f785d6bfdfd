import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this produces — with ShapeDtypeStruct inputs only, no
device allocation — the compiled SPMD executable plus:

  * ``memory_analysis()``  (bytes/device: proves the cell fits),
  * ``cost_analysis()``    (per-partition FLOPs / bytes accessed),
  * collective bytes parsed from the optimized HLO,
  * the derived roofline terms (launch/roofline.py).

Artifacts go to ``artifacts/dryrun/<arch>__<shape>__<mesh>.json`` and are
skipped when already present (incremental; delete to re-run).

Usage:
  python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--jobs-file cells.txt]
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, SHAPES, cell_applicable, get_arch, get_shape
from repro.configs.model_config import ModelConfig, ShapeConfig, TrainConfig
from repro.launch.hlo_cost import analyze as hlo_analyze
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import RooflineTerms, model_flops
from repro.models.model import Model, build_model
from repro.parallel.mesh import use_mesh
from repro.train.step import make_train_step, train_step_shardings

ARTIFACT_DIR = os.path.join("artifacts", "dryrun")

# Per-cell step configuration (memory-driven; see EXPERIMENTS.md §Dry-run).
TRAIN_MICROBATCHES: dict[str, int] = {
    "qwen1.5-32b": 16, "pixtral-12b": 16, "yi-6b": 8, "mamba2-2.7b": 8,
}
DEFAULT_TRAIN_MICROBATCHES = 8
# remat policy for train cells ("full" = recompute inside each layer;
# hillclimbed per-cell in EXPERIMENTS.md §Perf)
TRAIN_REMAT: dict[str, str] = {}
DEFAULT_TRAIN_REMAT = "full" 


def cell_name(arch: str, shape: str, multi_pod: bool,
              variant: str = "") -> str:
    mesh = "2x16x16" if multi_pod else "16x16"
    suffix = f"__{variant}" if variant else ""
    return f"{arch}__{shape}__{mesh}{suffix}"


def _ns(mesh, tree):
    from repro.parallel.sharding import named_tree
    return named_tree(mesh, tree)


def build_step(model: Model, cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Returns (jitted_fn, example_specs) for the cell's step kind."""
    if shape.kind == "train":
        mb = TRAIN_MICROBATCHES.get(cfg.name, DEFAULT_TRAIN_MICROBATCHES)
        if cfg.sharding_recipe == "dp":
            mb = 1      # batch spreads over all axes; 1 sample/chip
        remat = TRAIN_REMAT.get(cfg.name, DEFAULT_TRAIN_REMAT)
        cfg = dataclasses.replace(cfg, remat=remat)
        model = dataclasses.replace(model, cfg=cfg)
        tcfg = TrainConfig(microbatches=mb)
        step = make_train_step(model, tcfg)
        in_s, out_s = train_step_shardings(model, tcfg, mesh)
        pshapes = model.shapes()
        oshapes = {
            "m": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jax.numpy.float32),
                pshapes),
            "v": jax.tree.map(
                lambda s: jax.ShapeDtypeStruct(s.shape, jax.numpy.float32),
                pshapes),
            "step": jax.ShapeDtypeStruct((), jax.numpy.int32),
        }
        batch = model.input_specs(shape)
        jitted = jax.jit(step, in_shardings=in_s, out_shardings=out_s,
                         donate_argnums=(0, 1))
        return jitted, (pshapes, oshapes, batch)

    pspecs = _ns(mesh, model.specs())
    pshapes = model.shapes()
    bspecs = _ns(mesh, model.batch_spec(shape.global_batch))
    batch = model.input_specs(shape)

    if shape.kind == "prefill":
        bspec_sub = {k: bspecs[k] for k in batch}
        # explicit out_shardings: without them GSPMD replicated the cache
        # output across the model axis (41 GiB/chip on qwen; §Perf 3)
        logits_s = _ns(mesh, model.fitted_rules(shape.global_batch)
                       .spec("batch", None, None))
        cache_s = _ns(mesh, model.cache_specs(shape.global_batch))
        jitted = jax.jit(model.prefill, in_shardings=(pspecs, bspec_sub),
                         out_shardings=(logits_s, cache_s))
        return jitted, (pshapes, batch)

    # decode: serve_step(params, cache, token_batch)
    cache_specs = _ns(mesh, model.cache_specs(shape.global_batch))
    cache_shapes = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, shape.seq_len))
    tok_spec = _ns(mesh, {k: v
                          for k, v in model.batch_spec(shape.global_batch).items()
                          if k == "tokens"})
    tok_spec["index"] = NamedSharding(mesh, P())
    jitted = jax.jit(model.decode,
                     in_shardings=(pspecs, cache_specs, tok_spec),
                     donate_argnums=(1,))
    return jitted, (pshapes, cache_shapes, batch)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             out_dir: str = ARTIFACT_DIR, verbose: bool = True,
             overrides: dict | None = None, variant: str = "") -> dict:
    cfg = get_arch(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = get_shape(shape_name)
    ok, reason = cell_applicable(cfg, shape)
    record = {"arch": arch, "shape": shape_name, "variant": variant,
              "mesh": "2x16x16" if multi_pod else "16x16",
              "status": "skipped", "reason": reason}
    name = cell_name(arch, shape_name, multi_pod, variant)
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, name + ".json")
    if not ok:
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
        if verbose:
            print(f"[dryrun] {name}: SKIP ({reason})")
        return record

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    model = build_model(cfg, mesh)

    t0 = time.perf_counter()
    with use_mesh(mesh):
        jitted, specs = build_step(model, cfg, shape, mesh)
        lowered = jitted.lower(*specs)
        t_lower = time.perf_counter() - t0
        t0 = time.perf_counter()
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    # XLA's cost_analysis counts loop bodies once; re-derive with loop
    # multipliers from the optimized HLO (launch/hlo_cost.py).
    cond_weight = (1.0 / cfg.attn_every if cfg.family == "hybrid" else 0.5)
    walked = hlo_analyze(compiled.as_text(), cond_weight=cond_weight)
    coll = walked["collectives"]

    flops, bts = walked["flops"], walked["bytes"]
    adjustment = None
    if variant.endswith("flash"):
        # ACCEL variant: swap the attention function's terms for the
        # Pallas kernel's analytic profile (launch/kernel_model.py)
        from repro.launch.kernel_model import flash_adjustment
        mb = (TRAIN_MICROBATCHES.get(cfg.name, DEFAULT_TRAIN_MICROBATCHES)
              if shape.kind == "train" else 1)
        if cfg.sharding_recipe == "dp" and shape.kind == "train":
            mb = 1
        tp = (1 if cfg.sharding_recipe == "dp" else mesh.shape["model"])
        dp = chips // tp
        adj = flash_adjustment(cfg, shape, chips=chips, tp=tp, dp=dp,
                               microbatches=mb)
        flops += adj.d_flops
        bts += adj.d_bytes
        adjustment = {"ref_attn_flops": adj.ref_flops,
                      "ref_attn_bytes": adj.ref_bytes,
                      "kernel_attn_flops": adj.kernel_flops,
                      "kernel_attn_bytes": adj.kernel_bytes}

    terms = RooflineTerms(
        flops_per_chip=flops,
        hbm_bytes_per_chip=bts,
        collective_bytes_per_chip=sum(coll.values()),
        model_flops_per_chip=model_flops(cfg, shape, chips),
    )

    record.update({
        "status": "ok",
        "chips": chips,
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": mem.peak_memory_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
        },
        "cost_xla_once": {k: v for k, v in cost.items()
                          if "flops" in k or k == "bytes accessed"},
        "collectives": coll,
        "kernel_adjustment": adjustment,
        "roofline": terms.as_dict(),
    })
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
    if verbose:
        m = record["memory"]
        r = record["roofline"]
        print(f"[dryrun] {name}: OK lower={t_lower:.1f}s "
              f"compile={t_compile:.1f}s "
              f"peak={m['peak_bytes']/2**30:.2f}GiB/chip "
              f"args={m['argument_bytes']/2**30:.2f}GiB "
              f"bottleneck={r['bottleneck']} "
              f"roofline_frac={r['roofline_fraction']:.3f}")
    return record


def all_cells() -> list[tuple[str, str]]:
    return [(a, s) for a in ARCHS for s in SHAPES]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--variant", default="",
                    help="'flash' -> ACCEL kernel-substituted roofline")
    args = ap.parse_args()
    overrides = ({"sharding_recipe": "dp"} if args.variant.startswith("dp")
                 else None)

    cells = (all_cells() if args.all
             else [(args.arch, args.shape)])
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in cells:
        for mp in meshes:
            out_path = os.path.join(
                args.out, cell_name(arch, shape, mp, args.variant) + ".json")
            if os.path.exists(out_path) and not args.force:
                print(f"[dryrun] {cell_name(arch, shape, mp, args.variant)}: cached")
                continue
            try:
                run_cell(arch, shape, multi_pod=mp, out_dir=args.out,
                         overrides=overrides, variant=args.variant)
            except Exception as e:  # noqa: BLE001 — record and continue
                failures.append((arch, shape, mp, repr(e)))
                print(f"[dryrun] {cell_name(arch, shape, mp)}: FAIL {e!r}")
                traceback.print_exc(limit=4)
    if failures:
        print(f"\n{len(failures)} FAILURES:")
        for f in failures:
            print(" ", f)
        raise SystemExit(1)
    print("\nall requested cells OK")


if __name__ == "__main__":
    main()
