"""Step C/D — multi-target binary generation (the Popcorn/Vitis analogue).

``MultiTargetBinary`` AOT-lowers and compiles every variant of a
MigratableFunction with its per-target shardings.  The shared pytree
treedef + dtypes across variants are the aligned ABI (Popcorn's symbol
alignment); ``serialized_sizes`` reproduces the paper's Figure-10
binary-size comparison using ``jax.export``.
"""
from __future__ import annotations

import dataclasses
import time
from collections import OrderedDict
from typing import Any, Optional

import jax
from jax.sharding import SingleDeviceSharding

from repro.core.function import MigratableFunction
from repro.core.policy import ewma
from repro.core.targets import TargetKind


def shape_key(args: tuple) -> tuple:
    """Hashable (treedef, leaf shapes/dtypes) signature of a call's args.
    Computed per runtime call, so no stringification — PyTreeDef hashes
    and compares natively, shapes/dtypes are already hashable.

    Every leaf participates, so paged-decode calls key on their
    block-table shape (B, table_width) alongside the cache pool and
    token leaves: a paged engine's steady-state decode signature is
    static and compiles exactly once, outside Algorithm 1's timed
    region.  The prefix-cache chunked prefill (``{prefix}_prefill_ctx``)
    keys the same way: its ``offset``/``length`` leaves are (1,) DATA
    vectors — match length and feed length vary per request without
    forking the signature — so only the chunk's power-of-two token
    bucket (and the pool/table shapes, static per engine) key the
    compile, bounding it to O(log max_chunk) buckets exactly like the
    plain bucketed prefill.  Non-array leaves (python scalars riding in
    a batch dict) key on (type, value) — a changed static scalar must
    not silently reuse another signature's executable."""
    leaves, treedef = jax.tree.flatten(args)
    return (treedef, tuple(
        (l.shape, l.dtype) if hasattr(l, "shape") else (type(l), l)
        for l in leaves))


def committed_device(tree) -> Optional[jax.Device]:
    """The one device every committed array of ``tree`` lives on, or None
    (nothing committed, or spread over a mesh)."""
    devices = {d for x in jax.tree.leaves(tree)
               if isinstance(x, jax.Array) and x.committed
               for d in x.devices()}
    return devices.pop() if len(devices) == 1 else None


def abstract_args(args: tuple) -> tuple:
    """Shape-only specs of a call's args for an AOT compile.  Args
    committed to one device (an engine replica pinned to its chip) pin
    the specs, and so the executable, to that device; otherwise the
    compile targets the default device."""
    dev = committed_device(args)
    sharding = None if dev is None else SingleDeviceSharding(dev)
    return tuple(jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        a) for a in args)


@dataclasses.dataclass
class CompiledVariant:
    kind: TargetKind
    compiled: Any                  # jax.stages.Compiled
    compile_seconds: float
    flops: float = 0.0
    bytes_accessed: float = 0.0

    def __call__(self, *args):
        return self.compiled(*args)


class MultiTargetBinary:
    """All compiled variants of one function for one mesh."""

    def __init__(self, fn: MigratableFunction,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 donate_argnums: tuple = (),
                 max_shape_buckets: int = 8):
        self.fn = fn
        self.mesh = mesh
        self.donate_argnums = donate_argnums
        self.variants: dict[TargetKind, CompiledVariant] = {}
        self._jitted: dict[TargetKind, Any] = {}
        # shape-bucketed recompile cache: continuous batching calls the
        # same function with varying prefill widths; each shape signature
        # compiles once and lives in a small per-target LRU (per-target so
        # migration between kinds can't thrash the other kind's buckets)
        self.max_shape_buckets = max_shape_buckets
        self._default_keys: dict[TargetKind, tuple] = {}
        self._shape_cache: dict[TargetKind,
                                OrderedDict[tuple, CompiledVariant]] = {}
        self.shape_stats = {"hits": 0, "misses": 0, "evictions": 0}
        # per-target compile accounting (default + shape-bucket compiles),
        # surfaced by XarTrekRuntime.summary()
        self.compile_stats: dict[TargetKind, dict] = {}

    def _jit(self, kind: TargetKind):
        if kind not in self._jitted:
            fn = self.fn.variants[kind]
            kw = {}
            if kind in self.fn.shardings:
                in_s, out_s = self.fn.shardings[kind]
                kw = {"in_shardings": in_s, "out_shardings": out_s}
            self._jitted[kind] = jax.jit(
                fn, donate_argnums=self.donate_argnums, **kw)
        return self._jitted[kind]

    def _compile_specs(self, kind: TargetKind, specs: tuple) -> CompiledVariant:
        t0 = time.perf_counter()
        jitted = self._jit(kind)
        if self.mesh is not None:
            with jax.set_mesh(self.mesh):
                lowered = jitted.lower(*specs)
                compiled = lowered.compile()
        else:
            lowered = jitted.lower(*specs)
            compiled = lowered.compile()
        dt = time.perf_counter() - t0
        flops = bytes_acc = 0.0
        try:
            cost = compiled.cost_analysis() or {}
            flops = float(cost.get("flops", 0.0))
            bytes_acc = float(cost.get("bytes accessed", 0.0))
        except Exception:
            pass
        cs = self.compile_stats.setdefault(
            kind, {"compiles": 0, "compile_seconds": 0.0})
        cs["compiles"] += 1
        cs["compile_seconds"] += dt
        return CompiledVariant(kind=kind, compiled=compiled,
                               compile_seconds=dt, flops=flops,
                               bytes_accessed=bytes_acc)

    def compile(self, kind: TargetKind, *example_specs) -> CompiledVariant:
        """Lower + compile one variant (used eagerly at launch for HOST,
        asynchronously by the KernelBank for ACCEL)."""
        if kind in self.variants:
            return self.variants[kind]
        cv = self._compile_specs(kind, example_specs)
        self.variants[kind] = cv
        self._default_keys[kind] = shape_key(example_specs)
        return cv

    def variant_for(self, kind: TargetKind, args: tuple) -> CompiledVariant:
        """Compiled variant matching ``args``' exact shapes: the eagerly
        compiled default when the signature matches, else a bounded-LRU
        shape-bucket recompile (ragged continuous-batching prefills)."""
        key = shape_key(args)
        if self._default_keys.get(kind) == key:
            return self.variants[kind]
        lru = self._shape_cache.setdefault(kind, OrderedDict())
        cv = lru.get(key)
        if cv is not None:
            lru.move_to_end(key)
            self.shape_stats["hits"] += 1
            return cv
        self.shape_stats["misses"] += 1
        cv = lru[key] = self._compile_specs(kind, abstract_args(args))
        while len(lru) > self.max_shape_buckets:
            lru.popitem(last=False)
            self.shape_stats["evictions"] += 1
        return cv

    def note_exec(self, kind: TargetKind, ms: float) -> None:
        """Record one executed call's wall time against the target's
        stats: ``recent_exec_ms`` is an EWMA of the step time — the
        per-target speed signal ``LoadSignals`` carries to scheduling
        policies (a LatencyAwarePolicy compares HOST vs ACCEL step cost
        from here, not from a synthetic profile)."""
        cs = self.compile_stats.setdefault(
            kind, {"compiles": 0, "compile_seconds": 0.0})
        cs["calls"] = cs.get("calls", 0) + 1
        cs["recent_exec_ms"] = ewma(cs.get("recent_exec_ms"), ms)

    def compile_all(self, *example_specs) -> None:
        for kind in self.fn.targets():
            self.compile(kind, *example_specs)

    def is_compiled(self, kind: TargetKind) -> bool:
        return kind in self.variants

    # ------------------------------------------------------ Fig-10 support
    def serialized_sizes(self, *example_specs) -> dict[str, int]:
        """Bytes of the exported (serialized) executable per target."""
        sizes = {}
        for kind in self.fn.targets():
            jitted = self._jit(kind)
            try:
                exported = jax.export.export(jitted)(*example_specs)
                sizes[kind.value] = len(exported.serialize())
            except Exception:
                # fall back to HLO text size if export unsupported
                lowered = jitted.lower(*example_specs)
                sizes[kind.value] = len(lowered.as_text())
        return sizes
