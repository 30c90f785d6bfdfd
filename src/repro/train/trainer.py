"""Trainer: the fault-tolerant loop with Xar-Trek hooks.

Responsibilities:
  * auto-resume from the newest valid checkpoint (elastic: restores onto
    whatever mesh it is launched with);
  * periodic (optionally async) checkpoints;
  * failure injection for tests/examples (SimulatedFailure at a step);
  * optional XarTrekRuntime integration: the train step is registered as
    a MigratableFunction and each step is dispatched through the
    scheduler (straggler mitigation: a slow target's observed step times
    raise its threshold and traffic drains away — Algorithm 1).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Optional

import jax

from repro.checkpoint.manager import CheckpointManager
from repro.configs.model_config import ModelConfig, ShapeConfig, TrainConfig
from repro.core.function import FunctionRegistry, MigratableFunction
from repro.core.runtime import XarTrekRuntime
from repro.core.targets import TargetKind
from repro.data.pipeline import SyntheticPipeline
from repro.models.model import build_model
from repro.train.step import (init_train_state, make_train_step,
                              train_step_shardings)


class SimulatedFailure(RuntimeError):
    pass


@dataclasses.dataclass
class FailureInjector:
    """Deterministically kills given steps (tests the restart path)."""

    fail_at_steps: tuple[int, ...] = ()
    _fired: set = dataclasses.field(default_factory=set)

    def maybe_fail(self, step: int) -> None:
        if step in self.fail_at_steps and step not in self._fired:
            self._fired.add(step)
            raise SimulatedFailure(f"injected failure at step {step}")


@dataclasses.dataclass
class Trainer:
    cfg: ModelConfig
    shape: ShapeConfig
    tcfg: TrainConfig
    mesh: Optional[jax.sharding.Mesh] = None
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    keep: int = 3
    async_ckpt: bool = False
    total_steps: int = 200
    runtime: Optional[XarTrekRuntime] = None
    seed: int = 0

    def __post_init__(self):
        self.model = build_model(self.cfg, self.mesh)
        self.step_fn = make_train_step(self.model, self.tcfg,
                                       total_steps=self.total_steps)
        if self.mesh is not None:
            in_s, out_s = train_step_shardings(self.model, self.tcfg,
                                               self.mesh)
            self._jitted = jax.jit(self.step_fn, in_shardings=in_s,
                                   out_shardings=out_s,
                                   donate_argnums=(0, 1))
        else:
            self._jitted = jax.jit(self.step_fn, donate_argnums=(0, 1))
        self.pipeline = SyntheticPipeline(
            self.cfg, self.shape, seed=self.seed, mesh=self.mesh,
            batch_spec=self.model.batch_spec() if self.mesh else None)
        self.manager = (CheckpointManager(self.ckpt_dir, keep=self.keep,
                                          save_async=self.async_ckpt)
                        if self.ckpt_dir else None)
        self.metrics_log: list[dict] = []

    # -------------------------------------------------------------- state
    def init_or_restore(self):
        params, opt_state = init_train_state(self.model, self.tcfg,
                                             self.mesh, seed=self.seed)
        start = 0
        if self.manager and self.manager.has_checkpoint():
            target = {"params": params, "opt": opt_state}
            shardings = None
            if self.mesh is not None:
                from repro.optim.adamw import AdamW
                from repro.parallel.sharding import named_tree
                pspecs = self.model.specs()
                ospecs = AdamW(self.tcfg).state_specs(
                    pspecs, self.model.shapes(), _dp(self.mesh))
                shardings = named_tree(self.mesh,
                                       {"params": pspecs, "opt": ospecs})
            state, step, _ = self.manager.restore(target, shardings)
            params, opt_state = state["params"], state["opt"]
            start = step
        return params, opt_state, start

    # --------------------------------------------------------------- run
    def run(self, steps: Optional[int] = None,
            injector: Optional[FailureInjector] = None,
            log_every: int = 10,
            max_restarts: int = 3) -> list[dict]:
        steps = steps or self.total_steps
        restarts = 0
        while True:
            try:
                self._run_once(steps, injector, log_every)
                return self.metrics_log
            except SimulatedFailure as e:
                restarts += 1
                if restarts > max_restarts or not self.manager:
                    raise
                print(f"[trainer] {e} -> restarting from latest checkpoint "
                      f"({restarts}/{max_restarts})")

    def _run_once(self, steps, injector, log_every):
        params, opt_state, start = self.init_or_restore()
        from repro.parallel.mesh import use_mesh
        ctx = use_mesh(self.mesh)
        with ctx:
            for step in range(start, steps):
                batch = self.pipeline.batch(step)
                if injector:
                    injector.maybe_fail(step)
                t0 = time.perf_counter()
                if self.runtime is not None:
                    params, opt_state, metrics = self.runtime.call(
                        "train_step", params, opt_state, batch)
                else:
                    params, opt_state, metrics = self._jitted(
                        params, opt_state, batch)
                metrics = {k: float(v) for k, v in metrics.items()}
                metrics["step"] = step + 1
                metrics["step_ms"] = (time.perf_counter() - t0) * 1e3
                self.metrics_log.append(metrics)
                if log_every and (step + 1) % log_every == 0:
                    print(f"[trainer] step {step+1}: "
                          f"loss={metrics['loss']:.4f} "
                          f"({metrics['step_ms']:.0f} ms)")
                if (self.manager and (step + 1) % self.ckpt_every == 0):
                    self.manager.save(
                        step + 1, {"params": params, "opt": opt_state},
                        meta={"arch": self.cfg.name})
            if self.manager:
                self.manager.save(steps, {"params": params,
                                          "opt": opt_state},
                                  meta={"arch": self.cfg.name})
                self.manager.wait()
        self.final_state = (params, opt_state)

    # --------------------------------------------- Xar-Trek registration
    def register_migratable(self, registry: FunctionRegistry,
                            accel_step: Optional[Callable] = None,
                            aux_step: Optional[Callable] = None) -> None:
        """Register the train step as a migratable function: HOST is the
        plain jit path, ACCEL the kernel-variant step, AUX an alternative
        configuration (e.g. different remat/sharding)."""
        variants = {TargetKind.HOST: self.step_fn}
        if aux_step is not None:
            variants[TargetKind.AUX] = aux_step
        if accel_step is not None:
            variants[TargetKind.ACCEL] = accel_step
        registry.register(MigratableFunction(
            "train_step", f"train-{self.cfg.name}", variants))


def _dp(mesh) -> int:
    dp = 1
    for a in mesh.axis_names:
        if a in ("pod", "data"):
            dp *= mesh.shape[a]
    return dp


class _nullcontext:
    def __enter__(self):
        return None

    def __exit__(self, *a):
        return False
