"""XarTrekRuntime — the JAX-native integration of compiler + run-time.

Ties the pieces together for *real* jitted step functions:

  * ``prepare`` is the instrumentation the paper injects at main() start:
    eagerly compile the HOST variant, kick the ACCEL variant's
    asynchronous load (FPGA pre-configuration), seed the threshold table.
  * ``call`` is the instrumented call site: scheduler-client query ->
    execute the chosen compiled variant -> measure -> report (Alg. 1).
    It emits the profiler spans ``runtime.call`` (args ``fn``,
    ``target``), and inside it ``runtime.decide`` (the scheduler-client
    exchange) and ``runtime.wait`` (the variant's execution to
    ``block_until_ready``).
  * live state handed between variants is resharded via migration.py
    when targets disagree on shardings.
"""
from __future__ import annotations

import collections
import time
from typing import Any, Optional

import jax
from jax.profiler import TraceAnnotation

from repro.core.binary import MultiTargetBinary, abstract_args
from repro.core.function import GLOBAL_REGISTRY, FunctionRegistry
from repro.core.kernel_bank import KernelBank
from repro.core.migration import migrate
from repro.core.monitor import LoadMonitor
from repro.core.policy import LoadSignals, PolicyLike
from repro.core.scheduler import (
    SchedulerClient, SchedulerServer, TcpSchedulerClient,
)
from repro.core.targets import Platform, TargetKind, TPU_PLATFORM
from repro.core.thresholds import ThresholdTable

# records a CallLog keeps: far above the ~2,300 calls of a 51 s serving
# window, so a reader of one window sees all of its calls
CALL_LOG_MAX = 65536


class CallLog(collections.deque):
    """The runtime's most recent call records, oldest first, at most
    ``maxlen`` of them, with running counts of every call appended since
    the last ``clear()``: calls per function and target, and migrations
    per function (consecutive calls of it on different targets).  The
    counts stay exact past the bound.  Indexes and slices like a list."""

    def __init__(self, maxlen: int = CALL_LOG_MAX):
        super().__init__(maxlen=maxlen)
        self._zero()

    def _zero(self) -> None:
        self.calls = 0
        self.per_fn: dict[str, dict[str, int]] = {}
        self.migrations: dict[str, int] = {}
        self._last: dict[str, str] = {}

    def append(self, rec: dict) -> None:
        super().append(rec)
        fn, target = rec["fn"], rec["target"]
        self.calls += 1
        d = self.per_fn.setdefault(fn, {})
        d[target] = d.get(target, 0) + 1
        prev = self._last.get(fn)
        if prev is not None and prev != target:
            self.migrations[fn] = self.migrations.get(fn, 0) + 1
        self._last[fn] = target

    def clear(self) -> None:
        super().clear()
        self._zero()

    def __getitem__(self, i):
        if isinstance(i, slice):
            return list(self)[i]
        return super().__getitem__(i)


class XarTrekRuntime:
    def __init__(self, platform: Platform = TPU_PLATFORM,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 registry: FunctionRegistry = GLOBAL_REGISTRY,
                 table: Optional[ThresholdTable] = None,
                 policy: Optional[PolicyLike] = None,
                 bank_slots: Optional[int] = None,
                 min_reconfig_seconds: float = 0.0,
                 server: Optional[SchedulerServer] = None,
                 scheduler_address: Optional[tuple] = None):
        """``policy`` is a ``SchedulingPolicy`` instance or a legacy
        alias string.  ``server`` shares an EXTERNAL scheduler (the
        cluster case: N runtimes, one central policy over aggregate
        signals) — the runtime then registers its kernels/bank there and
        adopts the server's table and monitor.  ``scheduler_address``
        additionally routes client traffic (request/report/publish) over
        the paper-faithful TCP transport instead of in-process calls."""
        self.platform = platform
        self.mesh = mesh
        self.registry = registry
        self.binaries: dict[str, MultiTargetBinary] = {}
        self._specs: dict[str, tuple] = {}
        self.bank = KernelBank(
            slots=bank_slots or platform.accel_slots,
            load_fn=self._load_accel,
            min_load_seconds=min_reconfig_seconds)
        if server is not None:
            # the central scheduler owns policy and table; a caller who
            # passes either alongside server= would silently get the
            # server's — refuse the ambiguous combination instead
            if policy is not None or table is not None:
                raise ValueError(
                    "policy=/table= conflict with server=: the shared "
                    "scheduler already owns both (set them there)")
            self.server = server
            self.table = server.table
            self.monitor = server.monitor
        else:
            self.table = table or ThresholdTable()
            self.monitor = LoadMonitor(platform)
            self.server = SchedulerServer(platform, self.table, self.bank,
                                          self.monitor,
                                          policy=policy or "xartrek")
        self._scheduler_address = scheduler_address
        self._clients: dict[str, object] = {}
        self.call_log = CallLog()

    # ----------------------------------------------------------- prepare
    def prepare(self, fn_name: str, *example_args,
                table_row: Optional[dict] = None,
                donate_argnums: tuple = (),
                eager_accel: bool = False) -> None:
        """main()-start instrumentation: compile HOST now, pre-configure
        ACCEL (asynchronously by default; ``eager_accel=True`` blocks
        until the ACCEL build is bank-resident, so the first migration
        never pays compile time inside the timed region — the serve
        engine's choice), seed thresholds.  ``donate_argnums`` lets
        state-carrying callers (serve decode's KV cache) alias in place."""
        fn = self.registry.get(fn_name)
        fn.check_abi(example_args)
        specs = abstract_args(example_args)
        self._specs[fn_name] = specs
        binary = MultiTargetBinary(fn, mesh=self.mesh,
                                   donate_argnums=donate_argnums)
        self.binaries[fn_name] = binary
        binary.compile(TargetKind.HOST, *specs)
        if TargetKind.AUX in fn.variants:
            binary.compile(TargetKind.AUX, *specs)
        row = self.table.row(fn.app, hw_kernel=fn_name)
        if table_row:
            for k, v in table_row.items():
                setattr(row, k, v)
        if TargetKind.ACCEL in fn.variants:
            # bind this kernel to THIS runtime's bank on the scheduler
            # (shared-server clusters: residency and reconfiguration
            # must reach the worker that owns the compiled variants)
            self.server.register_kernel(fn_name, self.bank)
            if eager_accel:
                self.bank.load_sync(fn_name)
            else:
                self.bank.load_async(fn_name)   # pre-configuration

    def _load_accel(self, fn_name: str):
        return self.binaries[fn_name].compile(TargetKind.ACCEL,
                                              *self._specs[fn_name])

    # -------------------------------------------------------------- call
    def _client(self, app: str):
        if app not in self._clients:
            if self._scheduler_address is not None:
                self._clients[app] = TcpSchedulerClient(
                    app, self._scheduler_address)
            else:
                self._clients[app] = SchedulerClient(app, self.server)
        return self._clients[app]

    def publish_signals(self, engine_id: str, signals: LoadSignals) -> None:
        """Feed one engine's serve telemetry to the scheduler (TCP when
        a scheduler_address was given, in-process otherwise); the policy
        sees it merged into the aggregate on the next decision."""
        if self._scheduler_address is not None:
            self._client("_signals").publish(engine_id, signals)
        else:
            self.server.publish(engine_id, signals)

    def call(self, fn_name: str, *args,
             state_shardings: Optional[dict] = None) -> Any:
        """The instrumented call site (steps B + §3.2).

        Args may differ in shape from the ``prepare`` examples (ragged
        continuous-batching prefills): the binary's shape-bucket cache
        then compiles/reuses a variant for the exact signature."""
        with TraceAnnotation("runtime.call", fn=fn_name) as span:
            fn = self.registry.get(fn_name)
            binary = self.binaries[fn_name]
            client = self._client(fn.app)

            with TraceAnnotation("runtime.decide"):
                decision = client.before_call()
            kind = decision.target
            if kind == TargetKind.ACCEL and not binary.is_compiled(kind):
                # HOST stands in only while the ACCEL load is in flight
                # (the paper's latency hiding); a load that failed raises
                self.bank.raise_if_failed(fn_name)
                kind = TargetKind.HOST
            if kind not in fn.variants:
                kind = TargetKind.HOST
            span.set_metadata(target=kind.value)

            if state_shardings and kind in state_shardings:
                args = migrate(args, state_shardings[kind])

            # resolve (and possibly bucket-compile) BEFORE the timed
            # region: compile time must not reach Algorithm 1 as
            # execution time or hold the load monitor elevated
            variant = binary.variant_for(kind, args)

            self.monitor.job_started(kind)
            with TraceAnnotation("runtime.wait"):
                t0 = time.perf_counter()
                try:
                    out = variant(*args)
                    out = jax.block_until_ready(out)
                finally:
                    self.monitor.job_finished(kind)
                dt = time.perf_counter() - t0
            binary.note_exec(kind, dt * 1e3)
            client.after_call(kind, dt * 1e3)
            self.call_log.append({"fn": fn_name, "target": kind.value,
                                  "t": t0, "ms": dt * 1e3,
                                  "reconfigure": decision.reconfigure})
            return out

    # ------------------------------------------------------------- stats
    def summary(self) -> dict:
        """Aggregate call/compile/migration accounting.

        ``per_function[fn]`` reports, per target, how many calls that
        variant actually served and how many compiles it cost (default
        + shape-bucket), plus how many times consecutive calls of ``fn``
        switched target (= run-time migrations) — so a benchmark artifact
        can prove which backend served tokens, not just which was
        registered.
        """
        log = self.call_log
        per_target = {k.value: 0 for k in TargetKind}
        for calls in log.per_fn.values():
            for target, n in calls.items():
                per_target[target] += n
        per_function = {}
        for name, binary in self.binaries.items():
            per_function[name] = {
                "calls": dict(log.per_fn.get(name, {})),
                "compiles": {k.value: dict(v)
                             for k, v in binary.compile_stats.items()},
                "migrations": log.migrations.get(name, 0),
            }
        return {"calls": log.calls, "per_target": per_target,
                "per_function": per_function,
                "migrations": sum(log.migrations.values()),
                "bank": dict(self.bank.stats),
                "shape_buckets": {name: dict(b.shape_stats)
                                  for name, b in self.binaries.items()
                                  if sum(b.shape_stats.values())},
                "decisions": {k.value: v
                              for k, v in self.server.decisions.items()}}
