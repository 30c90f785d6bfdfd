"""Multi-engine cluster serving under ONE central scheduler.

Closes the ROADMAP's multi-process item: N ``ContinuousBatchingEngine``
workers (each with its own ``XarTrekRuntime``, compiled variants and
kernel bank) register with one central ``SchedulerServer``; the shared
``SchedulingPolicy`` is evaluated over the *aggregate* cross-engine
``LoadSignals``, so one engine's queue pressure migrates another
engine's decode steps to ACCEL — Algorithm 2 balancing real co-tenant
load, as in the paper's evaluation, instead of a synthetic process
counter.

Topology (the paper's Figure-2 run-time, serve-shaped):

* ``ClusterFrontEnd`` owns the central scheduler (policy + threshold
  table + load monitor) and, by default, a ``TcpSchedulerServer``
  wrapping it — workers then talk to the scheduler over the
  paper-faithful line-JSON socket transport (``transport="inproc"``
  skips the sockets for tests).
* Each ``EngineWorker`` runs its engine loop on its own thread; its
  runtime's scheduler *clients* (one per step function, plus the
  signal publisher) connect to the central server, and its kernel bank
  is registered there so residency checks and async reconfigurations
  reach the worker that owns the compiled variants.
* ``ClusterFrontEnd.submit(GenerationRequest)`` routes to the
  least-loaded worker (queued + in-flight) and returns the v2
  ``RequestHandle`` — streaming, ``result()``, ``abort()`` all work
  unchanged; the application-facing contract does not know the cluster
  exists.

**Disaggregation** (``roles=``): workers can be pinned to one serving
phase — ``"prefill"`` workers run chunked prefill into scratch pool
blocks and hand the finished KV off as a serialized ``KVSpan``;
``"decode"`` (or ``"mixed"``) workers rehydrate the span into their own
pool and decode.  The handoff rides the scheduler control plane (a
``handoff`` op next to ``request``/``report``/``publish`` — base64
payload over the line-JSON TCP transport, a direct call in-proc), so
phase migration uses exactly the machinery step migration does.  The
front-end picks the decode owner at submit time (least loaded) and the
prefill worker by shortest prefill queue; the central policy sees both
phases' published signals.

Workers are threads, not OS processes: one JAX runtime serves all
engines (this is the single-host analogue; the TCP control plane is
exactly what a multi-host deployment would speak), which is also what a
TPU host needs — a chip belongs to one process.  Worker ``i`` is placed
on local device ``i mod n_devices``: its params copy, KV pool and
compiled builds live there, so a four-chip host runs four replicas and
a one-device host runs co-tenants of one accelerator, as in SYNERGY's
multiplexing argument.  Model parameters are built once and copied to
each worker's device.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.configs.model_config import ModelConfig
from repro.core.function import FunctionRegistry
from repro.core.monitor import LoadMonitor
from repro.core.policy import PolicyLike
from repro.core.runtime import XarTrekRuntime
from repro.core.scheduler import (
    SchedulerServer, TcpSchedulerClient, TcpSchedulerServer,
)
from repro.core.targets import Platform, TPU_PLATFORM
from repro.core.thresholds import ThresholdTable
from repro.models.model import build_model
from repro.serve.api import GenerationRequest, RequestHandle, RequestOutput
from repro.serve.batch import KVSpan
from repro.serve.engine import ContinuousBatchingEngine

WORKER_ROLES = ("mixed", "prefill", "decode")


class EngineWorker:
    """One engine + runtime + serve-loop thread behind the cluster.

    ``role`` pins the worker to one serving phase: a ``"prefill"``
    worker additionally services a span queue (``submit_prefill`` →
    ``engine.prefill_to_span`` → ``on_handoff``); a ``"decode"`` worker
    receives spans via ``submit_span``.  ``"mixed"`` (default) serves
    both phases locally, exactly the pre-role behaviour."""

    def __init__(self, worker_id: str, cfg: ModelConfig,
                 server: SchedulerServer,
                 scheduler_address: Optional[tuple] = None,
                 params=None, seed: int = 0, role: str = "mixed",
                 **engine_kwargs):
        if role not in WORKER_ROLES:
            raise ValueError(f"role must be one of {WORKER_ROLES}: {role!r}")
        self.worker_id = worker_id
        self.role = role
        self.runtime = XarTrekRuntime(
            registry=FunctionRegistry(), server=server,
            scheduler_address=scheduler_address)
        self.engine = ContinuousBatchingEngine(
            cfg, params=params, seed=seed, runtime=self.runtime,
            fn_prefix=worker_id, **engine_kwargs)
        self._prefill_q: collections.deque = collections.deque()
        # set by the front-end on prefill-role workers: called with
        # (request, span_bytes) once a span is ready to hand off
        self.on_handoff = None
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=f"engine-{worker_id}")

    # ----------------------------------------------------------- serving
    def start(self) -> None:
        self._thread.start()

    def stop(self, timeout: float = 10.0) -> None:
        """Idempotent, and safe on a worker whose thread never started
        (a front-end torn down from a constructor failure path)."""
        self._stop.set()
        self._wake.set()
        if self._thread.ident is not None:
            self._thread.join(timeout)
        for client in self.runtime._clients.values():
            close = getattr(client, "close", None)
            if close is not None:
                close()

    def _loop(self) -> None:
        """Drain-and-wait: ``run()`` serves everything queued (new
        submissions land in the thread-safe queue mid-run and are
        admitted the same loop), then the thread parks until the next
        ``submit`` wakes it.  run()'s return dict is dropped — the
        caller-facing results are the RequestHandles, which resolve the
        step each request finishes (reading a worker-side dict here
        would race the front-end, whose drain() returns as soon as the
        handles resolve).  Host inputs the loop builds land directly on
        the worker's device."""
        with jax.default_device(self.engine.device):
            while not self._stop.is_set():
                busy = False
                while self._prefill_q:
                    req = self._prefill_q.popleft()
                    # publish load BEFORE the span: prefill_to_span never
                    # enters run(), so this is the prefill phase's pressure
                    # feed to the central policy
                    self.engine._publish_signals()
                    payload = self.engine.prefill_to_span(req).to_bytes()
                    if self.on_handoff is not None:
                        self.on_handoff(req, payload)
                    busy = True
                if len(self.engine.queue) or self.engine.slots.active:
                    self.engine.run()
                    busy = True
                if not busy:
                    self._wake.wait(timeout=0.05)
                    self._wake.clear()

    def submit(self, request: GenerationRequest,
               on_token=None) -> RequestHandle:
        handle = self.engine.submit(request, on_token=on_token)
        self._wake.set()
        return handle

    def submit_prefill(self, request: GenerationRequest) -> None:
        """Queue a prefill-only job (disaggregation: the span hands off
        via ``on_handoff`` when ready)."""
        self._prefill_q.append(request)
        self._wake.set()

    def submit_span(self, request: GenerationRequest,
                    span: KVSpan) -> RequestHandle:
        """Queue a request whose prefill KV arrives pre-computed."""
        handle = self.engine.submit_span(request, span)
        self._wake.set()
        return handle

    def load(self) -> int:
        """Routing weight: requests queued plus rows in flight."""
        return len(self.engine.queue) + len(self.engine.slots.active)

    def prefill_load(self) -> int:
        """Prefill-routing weight: spans queued but not yet computed."""
        return len(self._prefill_q)


class ClusterFrontEnd:
    """N engine workers, one scheduler, one ``submit()`` surface.

    ``policy`` is the SHARED SchedulingPolicy (instance or alias
    string) the central server evaluates over aggregate signals.
    ``transport="tcp"`` (default) runs the scheduler behind a
    ``TcpSchedulerServer`` on loopback; ``"inproc"`` wires the workers
    straight to the server object.  ``engine_kwargs`` (max_slots,
    max_seq, paged, block_size, ...) apply to every worker.  Parameters
    are built once and copied to each worker's device (worker ``i`` on
    local device ``i mod n_devices``).

    ``roles`` (one per worker, e.g. ``("prefill", "decode")``) enables
    disaggregated serving: requests route decode-first (the least-loaded
    decode-capable worker owns the request and its handle from submit
    time), the shortest-queue prefill worker computes the KV span, and
    the span travels dest-addressed over the scheduler control plane's
    ``handoff`` op into the owner's pool.  Requires ``paged=True`` and
    at least one decode-capable (``decode``/``mixed``) worker.
    """

    def __init__(self, cfg: ModelConfig, n_engines: int = 2,
                 policy: PolicyLike = "xartrek",
                 transport: str = "tcp",
                 platform: Platform = TPU_PLATFORM,
                 table: Optional[ThresholdTable] = None,
                 params=None, seed: int = 0,
                 worker_prefix: str = "w",
                 roles: Optional[Sequence[str]] = None,
                 **engine_kwargs):
        if n_engines < 1:
            raise ValueError(f"need at least one engine: {n_engines}")
        if transport not in ("tcp", "inproc"):
            raise ValueError(f"transport must be tcp|inproc: {transport!r}")
        if roles is None:
            roles = ("mixed",) * n_engines
        roles = tuple(roles)
        if len(roles) != n_engines:
            raise ValueError(f"roles {roles} must name all "
                             f"{n_engines} workers")
        if not any(r in ("decode", "mixed") for r in roles):
            raise ValueError("need at least one decode-capable worker "
                             "(role 'decode' or 'mixed')")
        if any(r == "prefill" for r in roles) \
                and not engine_kwargs.get("paged"):
            raise ValueError("disaggregated roles require paged=True "
                             "(spans move KV at block granularity)")
        self.roles = roles
        self.cfg = cfg
        self.table = table or ThresholdTable()
        self.server = SchedulerServer(platform, self.table, bank=None,
                                      monitor=LoadMonitor(platform),
                                      policy=policy)
        self._tcp: Optional[TcpSchedulerServer] = None
        self._handoff_client = None
        self.workers: list[EngineWorker] = []
        self._stopped = False
        try:
            address = None
            if transport == "tcp":
                self._tcp = TcpSchedulerServer(self.server)
                address = self._tcp.start()
            if params is None:
                params = build_model(cfg, engine_kwargs.get("mesh")).init(
                    jax.random.PRNGKey(seed))
            devices = jax.local_devices()
            for i in range(n_engines):
                w_params = params
                if engine_kwargs.get("mesh") is None:
                    w_params = jax.device_put(
                        params, devices[i % len(devices)])
                w = EngineWorker(f"{worker_prefix}{i}", cfg, self.server,
                                 scheduler_address=address, role=roles[i],
                                 params=w_params, seed=seed, **engine_kwargs)
                self.workers.append(w)
            # disaggregation plumbing: decode-capable workers register a
            # span sink under their worker_id; prefill workers hand
            # spans to the control plane addressed at the request's
            # decode owner
            self._pending_spans: dict[int, tuple[GenerationRequest,
                                                 EngineWorker]] = {}
            # prompts at or under this length prefill in place on their
            # decode owner: the span tier exists for prompts whose
            # prefill would stall co-resident decodes, and a one-chunk
            # prompt costs less to compute locally than to serialize
            # and hand off
            self._span_threshold = int(
                engine_kwargs.get("prefill_tokens_per_step")
                or engine_kwargs.get("block_size") or 16)
            if any(r == "prefill" for r in roles):
                for w in self.workers:
                    if w.role != "prefill":
                        self.server.register_handoff_sink(
                            w.worker_id, self._make_sink(w))
                    else:
                        w.on_handoff = self._handoff_out
                if address is not None:
                    self._handoff_client = TcpSchedulerClient("handoff",
                                                              address)
        except BaseException:
            # a worker that failed to build mid-list, or a handoff
            # client that could not connect, must not leak the TCP
            # server thread / listener socket or the workers' runtime
            # clients into the caller's except path
            self.stop()
            raise
        self._owner: dict[int, EngineWorker] = {}
        self._handles: dict[int, RequestHandle] = {}
        # req_id -> worker_id of requests completed by the last drain()
        # (ownership survives the pop so callers can attribute outputs
        # per engine without racing the worker threads)
        self.last_owners: dict[int, str] = {}
        self._lock = threading.Lock()
        self._started = False

    # ------------------------------------------------------------ control
    def start(self) -> "ClusterFrontEnd":
        if not self._started:
            self._started = True
            for w in self.workers:
                w.start()
        return self

    def stop(self) -> None:
        """Idempotent: workers, the handoff client and the TCP server
        all tolerate repeated/unstarted teardown, so ``with`` blocks,
        explicit ``stop()`` calls and constructor-failure cleanup can
        overlap without double-close errors."""
        if self._stopped:
            return
        self._stopped = True
        for w in self.workers:
            w.stop()
        if self._handoff_client is not None:
            self._handoff_client.close()
        if self._tcp is not None:
            self._tcp.stop()
        self._started = False

    # ------------------------------------------------------ disaggregation
    def _make_sink(self, worker: EngineWorker):
        """Span consumer for one decode-capable worker (runs on the
        delivering thread — TCP handler or prefill worker)."""
        def sink(req_id: int, payload: bytes) -> None:
            with self._lock:
                request, _ = self._pending_spans.pop(req_id)
            worker.submit_span(request, KVSpan.from_bytes(payload))
        return sink

    def _handoff_out(self, request: GenerationRequest,
                     payload: bytes) -> None:
        """Prefill-worker exit: ship the span to the request's decode
        owner over the control plane (TCP when the cluster runs the
        socket transport, a direct server call in-proc)."""
        with self._lock:
            dest = self._pending_spans[request.req_id][1].worker_id
        plane = self._handoff_client or self.server
        plane.handoff(dest, request.req_id, payload)

    def __enter__(self) -> "ClusterFrontEnd":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def warmup(self, timeout: float = 120.0) -> None:
        """Run one tiny request through every worker, then zero the
        stats: engines compile their lazy pieces (slot-write /
        block-scatter jits) outside any measured or scenario-sensitive
        window, exactly like the single-engine benchmarks' warm pass.
        Without this, a worker's first admission can stall seconds in
        compilation while its co-tenants' load comes and goes unseen."""
        if not self._started:
            raise RuntimeError("cluster not started (use start() or with)")
        vocab = max(getattr(self.cfg, "vocab_size", 2), 2)
        handles = [w.submit(GenerationRequest(
            np.arange(1, 5, dtype=np.int32) % vocab, max_new_tokens=2))
            for w in self.workers]
        for h in handles:
            h.result(timeout=timeout)
        if any(w.role == "prefill" for w in self.workers):
            # warm the disaggregated path too: prefill-to-span on the
            # prefill workers, span-rehydrate scatter on the decoders
            # (long enough to clear the local-prefill threshold)
            n = self._span_threshold + 4
            h = self.submit(GenerationRequest(
                np.arange(1, n + 1, dtype=np.int32) % vocab,
                max_new_tokens=2))
            h.result(timeout=timeout)
            with self._lock:
                self._handles.pop(h.req_id, None)
                self._owner.pop(h.req_id, None)
        for w in self.workers:
            w.runtime.call_log.clear()
            w.engine.reset_stats()

    def set_decode_thresholds(self, fpga_thr: float,
                              arm_thr: float = float("inf")) -> None:
        """Seed every worker's decode-step threshold row (the Table-2
        artifact the compiler would have produced): the load above
        which offloading that worker's decode to ACCEL is profitable."""
        for w in self.workers:
            row = self.table.row(w.engine._decode_name)
            row.fpga_thr, row.arm_thr = fpga_thr, arm_thr

    # ------------------------------------------------------------- serve
    def submit(self, request: GenerationRequest,
               on_token=None) -> RequestHandle:
        """Route one request to the least-loaded worker; the returned
        handle is the worker engine's own (streaming/abort included).

        With prefill roles in play the split is explicit: the decode
        owner is fixed (and its handle returned) at submit time, the
        prefill worker with the shortest span queue computes the KV,
        and admission on the owner waits for the handoff — TTFT covers
        the whole disaggregated path."""
        if not self._started:
            raise RuntimeError("cluster not started (use start() or with)")
        prefillers = [w for w in self.workers if w.role == "prefill"]
        with self._lock:
            if not prefillers:
                worker = min((w for w in self.workers
                              if w.role != "prefill"),
                             key=lambda w: w.load())
                handle = worker.submit(request, on_token=on_token)
                self._owner[request.req_id] = worker
                self._handles[request.req_id] = handle
                return handle
            dest = min((w for w in self.workers if w.role != "prefill"),
                       key=lambda w: w.load())
            if request.prompt_len <= self._span_threshold:
                # interactive class: prefill locally on the owner
                handle = dest.submit(request, on_token=on_token)
                self._owner[request.req_id] = dest
                self._handles[request.req_id] = handle
                return handle
            dest.engine.slots.validate(request)     # fail fast, pre-span
            handle = dest.engine._handle_for(request, on_token=on_token)
            self._pending_spans[request.req_id] = (request, dest)
            self._owner[request.req_id] = dest
            self._handles[request.req_id] = handle
            source = min(prefillers, key=lambda w: w.prefill_load())
        source.submit_prefill(request)
        return handle

    def drain(self, timeout: float = 120.0) -> dict[int, RequestOutput]:
        """Block until every submitted request finished; returns (and
        forgets) their outputs keyed by req_id."""
        deadline = time.monotonic() + timeout
        with self._lock:
            handles = dict(self._handles)
        out = {}
        for rid, h in handles.items():
            out[rid] = h.result(timeout=max(deadline - time.monotonic(),
                                            0.001))
        with self._lock:
            self.last_owners = {
                rid: self._owner[rid].worker_id
                for rid in out if rid in self._owner}
            for rid in out:
                self._handles.pop(rid, None)
                self._owner.pop(rid, None)
        return out

    # ------------------------------------------------------------- stats
    def summary(self) -> dict:
        """Cluster-wide accounting: per-worker runtime summaries plus
        the aggregate migration count and the central server's decision
        histogram — the benchmark artifact's proof that co-tenant load
        moved steps between targets."""
        per_engine = {w.worker_id: w.runtime.summary()
                      for w in self.workers}
        out = {
            "per_engine": per_engine,
            "migrations": sum(s["migrations"]
                              for s in per_engine.values()),
            "decisions": {k.value: v
                          for k, v in self.server.decisions.items()},
            "signals": dataclasses.asdict(self.server.signals()),
            "roles": {w.worker_id: w.role for w in self.workers},
            "handoffs": self.server.handoffs,
            # per-worker chunked-prefill / stall observability (the
            # policy's view of prefill pressure, not just throughput)
            "chunked_prefill": {
                w.worker_id: {
                    "prefill_chunks": w.engine.stats["prefill_chunks"],
                    "decode_stall_ms": w.engine.stats["decode_stall_ms"],
                    "decode_stall_max_ms":
                        w.engine.stats["decode_stall_max_ms"],
                    "chunk_hist": dict(w.engine.stats["chunk_hist"]),
                    "spans_admitted": w.engine.stats["spans_admitted"],
                } for w in self.workers},
        }
        if any(w.engine.prefix_cache for w in self.workers):
            # aggregate prefix-cache effectiveness: each worker has its
            # own pool, so hit rates are per-tenant, summed here the way
            # migrations are
            per_worker = {w.worker_id: w.engine.prefix_stats()
                          for w in self.workers}
            hit = sum(p["prefix_hit_tokens"] for p in per_worker.values())
            computed = sum(p["prefill_tokens"] for p in per_worker.values())
            out["prefix_cache"] = {
                "per_engine": per_worker,
                "prefix_hit_tokens": hit,
                "prefill_tokens": computed,
                "prefix_hit_rate": hit / max(hit + computed, 1),
            }
        return out
