"""OS-process cluster serving: real workers, a streaming result plane,
and a fault-tolerant supervisor.

The threaded cluster (``serve/cluster.py``) shares one Python process,
so the GIL caps its scaling and one worker's crash is everyone's crash.
This module promotes each worker to a real OS process (``spawn``, not
``fork`` — its own JAX runtime, registry, kernel bank and
``ContinuousBatchingEngine``), connected to the parent by two planes:

* **Control plane** — the existing line-JSON TCP scheduler transport
  (``core/scheduler.py``).  Each worker's runtime clients talk to the
  parent's central ``SchedulerServer`` (``request``/``report``/
  ``publish``), workers report their kernel-bank residency with the
  ``kernel`` op (the central server cannot query a bank in another
  address space), liveness beats ride the ``heartbeat`` op, and
  disaggregated KV spans ride ``handoff`` exactly as in the threaded
  cluster.
* **Result plane** — a NEW full-duplex line-JSON socket per worker,
  carrying commands down (``submit``/``abort``/``prefill``/``span``/
  ``warmup``/``reset``/``summary``/``stop``) and streaming events up:

      {"ev": "token", "req": id, "i": abs_index, "t": tok, "lp": lp}
      {"ev": "finish", "req": id, "tokens": [...], "logprobs": [...],
       "finish_reason": "stop|length|aborted", "queue_wait_s": s}

  The parent rehydrates its ``RequestHandle`` for the request from
  these events (``RequestHandle.apply_event``), so streaming
  iteration, ``on_token`` callbacks, ``result()`` and ``abort()`` keep
  their exact v2 semantics across the process boundary.  Token events
  carry the ABSOLUTE index so a re-routed request's replayed prefix
  dedups instead of double-emitting.

**Fault tolerance** — ``ClusterSupervisor`` owns worker lifecycle:
spawn, warmup, per-worker heartbeat deadlines, and failure handling.
A worker is declared dead when its result-plane socket hits EOF, its
process exits, or its heartbeat goes silent past the liveness deadline
(stragglers are killed, not waited out).  The dead worker's in-flight
requests re-route to the least-loaded survivor via
resume-by-re-prefill: the parent hands the survivor the prompt plus
every token already streamed, the survivor re-prefills
prompt + tokens[:-1] and replays the stash (``submit_resume``), and —
because sampling keys depend only on (seed, position) — the
continuation is byte-identical to a run with no failure at all.

``ProcClusterFrontEnd`` presents the same ``submit``/``warmup``/
``drain``/``summary`` surface as ``ClusterFrontEnd``, including the
prefill/decode role split over real processes.  All model parameters
are rebuilt deterministically in each worker from the shared seed
(``model.init(PRNGKey(seed))``), so every process serves identical
weights without shipping arrays over a pipe.

CPU only: every worker starts its own JAX runtime, and a TPU chip
belongs to one process, so on a TPU backend the front-end refuses to
start (``require_cpu_backend``).  A TPU host runs the threaded
``ClusterFrontEnd``, which places one replica per device in one process.
"""
from __future__ import annotations

import base64
import collections
import dataclasses
import itertools
import json
import queue as queue_lib
import socket
import threading
import time
from typing import Optional, Sequence

import jax
import numpy as np

from repro.core.policy import PolicyLike
from repro.core.monitor import LoadMonitor
from repro.core.scheduler import (
    SchedulerServer, TcpSchedulerClient, TcpSchedulerServer,
)
from repro.core.targets import Platform, TPU_PLATFORM
from repro.core.thresholds import ThresholdTable
from repro.serve.api import (
    FINISH_ABORTED, GenerationRequest, RequestHandle, RequestOutput,
    SamplingParams,
)
from repro.serve.cluster import WORKER_ROLES

# worker-internal requests (warmup) live above this id so they can never
# collide with parent-assigned req_ids (both processes count from 0)
_INTERNAL_RID_BASE = 1_000_000_000


def _req_to_wire(req: GenerationRequest) -> dict:
    return {"req_id": int(req.req_id),
            "prompt": np.asarray(req.prompt, np.int32).tolist(),
            "max_new_tokens": int(req.max_new_tokens),
            "arrival_s": 0.0,     # parent routes on submit; no deferral
            "stop_tokens": [int(t) for t in req.stop_tokens],
            "sampling": dataclasses.asdict(req.sampling)}


def _req_from_wire(msg: dict) -> GenerationRequest:
    return GenerationRequest(
        np.asarray(msg["prompt"], np.int32),
        max_new_tokens=msg["max_new_tokens"],
        arrival_s=msg.get("arrival_s", 0.0),
        stop_tokens=tuple(msg.get("stop_tokens", ())),
        sampling=SamplingParams(**msg["sampling"]),
        req_id=msg["req_id"])


def _jsonable(x):
    """Wire-safe copy: enum/tuple keys stringify, numpy scalars box."""
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.integer):
        return int(x)
    if isinstance(x, np.floating):
        return float(x)
    return x


# --------------------------------------------------------- worker process

def _worker_main(worker_id: str, cfg, seed: int, engine_kwargs: dict,
                 scheduler_addr: tuple, result_addr: tuple, role: str,
                 heartbeat_interval_s: float) -> None:
    """Entry point of one spawned worker process.

    Order matters: the result-plane ``hello`` and the heartbeat thread
    start BEFORE the engine builds, so the multi-second JAX compile at
    boot is never mistaken for a dead worker, and the parent's accept
    loop can match this connection to its worker slot immediately."""
    sock = socket.create_connection(result_addr)
    rfile = sock.makefile("r")
    wfile = sock.makefile("w")
    send_lock = threading.Lock()

    def send(obj: dict) -> None:
        try:
            with send_lock:
                wfile.write(json.dumps(obj) + "\n")
                wfile.flush()
        except OSError:        # parent gone: nothing left to report to
            pass

    send({"ev": "hello", "worker": worker_id})

    hb_stop = threading.Event()

    def beat() -> None:
        try:
            client = TcpSchedulerClient(f"{worker_id}_hb", scheduler_addr)
        except OSError:
            return
        seq = 0
        while not hb_stop.wait(heartbeat_interval_s):
            try:
                client.heartbeat(worker_id, seq)
                seq += 1
            except Exception:  # noqa: BLE001 — scheduler gone: stop beating
                break
        client.close()

    threading.Thread(target=beat, daemon=True,
                     name=f"{worker_id}-heartbeat").start()

    # heavy imports deferred past hello/heartbeat so boot liveness does
    # not wait on jax initialisation
    from repro.core.function import FunctionRegistry
    from repro.core.runtime import XarTrekRuntime
    from repro.serve.batch import KVSpan
    from repro.serve.engine import ContinuousBatchingEngine

    runtime = XarTrekRuntime(registry=FunctionRegistry(),
                             scheduler_address=scheduler_addr)
    # params=None + shared seed: every worker rebuilds IDENTICAL weights
    # deterministically instead of receiving arrays over the pipe
    engine = ContinuousBatchingEngine(cfg, params=None, seed=seed,
                                      runtime=runtime,
                                      fn_prefix=worker_id, **engine_kwargs)
    ctl = TcpSchedulerClient(f"{worker_id}_ctl", scheduler_addr)
    for name in runtime.binaries:
        # push this process's bank state to the central server (it
        # cannot query across the address-space boundary)
        ctl.register_remote_kernel(name, name,
                                   runtime.bank.is_resident(name),
                                   runtime.bank.is_loading(name))

    internal_rid = itertools.count(_INTERNAL_RID_BASE).__next__
    stop = threading.Event()
    wake = threading.Event()
    prefill_q: collections.deque = collections.deque()
    warmup_q: collections.deque = collections.deque()

    def emit_token(handle: RequestHandle):
        def on_token(tok: int) -> None:
            send({"ev": "token", "req": handle.req_id,
                  "i": len(handle.tokens) - 1, "t": int(tok),
                  "lp": float(handle.logprobs[-1])
                  if handle.logprobs else 0.0})
        return on_token

    def on_finish(handle: RequestHandle, out) -> None:
        if handle.req_id >= _INTERNAL_RID_BASE:
            return                       # warmup traffic stays local
        send({"ev": "finish", "req": handle.req_id,
              "tokens": [int(t) for t in out.tokens],
              "logprobs": [float(x) for x in handle.logprobs],
              "finish_reason": out.finish_reason,
              "queue_wait_s": float(out.queue_wait_s)})

    engine.on_finish = on_finish

    def accept(msg: dict, resume_tokens=(), resume_logprobs=None,
               span=None) -> None:
        """Register the handle (with its emitter) BEFORE queueing, so a
        token admitted by an already-running engine loop can never beat
        the emitter attachment; a validation failure reports as an
        aborted finish instead of dying silently in another process."""
        req = _req_from_wire(msg)
        handle = engine._handle_for(req)
        handle.on_token = emit_token(handle)
        try:
            if span is not None:
                engine.submit_span(req, span)
            else:
                engine.submit_resume(req, resume_tokens, resume_logprobs)
        except Exception as e:  # noqa: BLE001 — report, keep serving
            engine._handles.pop(req.req_id, None)
            send({"ev": "finish", "req": req.req_id, "tokens": [],
                  "logprobs": [], "finish_reason": FINISH_ABORTED,
                  "queue_wait_s": 0.0, "error": str(e)})
            return
        wake.set()

    def summary_dict() -> dict:
        d = {"worker": worker_id, "role": role,
             "engine_stats": _jsonable(engine.stats),
             "runtime": _jsonable(runtime.summary())}
        if engine.paged:
            pool = engine.slots.pool
            d["pool"] = {"num_blocks": int(pool.num_blocks),
                         "free_blocks": int(pool.free_blocks()),
                         "cached_blocks": int(pool.cached_blocks())}
        return d

    def read_loop() -> None:
        try:
            for line in rfile:
                msg = json.loads(line)
                cmd = msg.get("cmd")
                if cmd == "submit":
                    accept(msg["req"],
                           resume_tokens=msg.get("resume_tokens") or (),
                           resume_logprobs=msg.get("resume_logprobs"))
                elif cmd == "abort":
                    engine.abort(int(msg["req"]))
                    wake.set()
                elif cmd == "prefill":
                    prefill_q.append((_req_from_wire(msg["req"]),
                                      msg["dest"]))
                    wake.set()
                elif cmd == "span":
                    accept(msg["req"], span=KVSpan.from_bytes(
                        base64.b64decode(msg["payload"])))
                elif cmd == "warmup":
                    warmup_q.append(msg)
                    wake.set()
                elif cmd == "reset":
                    runtime.call_log.clear()
                    engine.reset_stats()
                    send({"ev": "reset_done", "worker": worker_id})
                elif cmd == "summary":
                    send({"ev": "summary", "worker": worker_id,
                          "data": summary_dict()})
                elif cmd == "stop":
                    break
        except (OSError, ValueError):
            pass
        finally:
            stop.set()                   # EOF/parent death: shut down
            wake.set()

    threading.Thread(target=read_loop, daemon=True,
                     name=f"{worker_id}-reader").start()

    def do_warmup(msg: dict) -> None:
        vocab = max(getattr(cfg, "vocab_size", 2), 2)
        reqs = [GenerationRequest(np.arange(1, 5, dtype=np.int32) % vocab,
                                  max_new_tokens=2, req_id=internal_rid())]
        mp = int(msg.get("max_prompt") or 0)
        if mp > 8:
            # pre-compile the longest prompt bucket the caller will use
            reqs.append(GenerationRequest(
                np.arange(1, mp + 1, dtype=np.int32) % vocab,
                max_new_tokens=2, req_id=internal_rid()))
        for r in reqs:
            engine.submit(r)
        engine.run()
        runtime.call_log.clear()
        engine.reset_stats()
        send({"ev": "warmed", "worker": worker_id})

    send({"ev": "ready", "worker": worker_id})
    try:
        while not stop.is_set():
            busy = False
            while warmup_q:
                do_warmup(warmup_q.popleft())
                busy = True
            while prefill_q:
                req, dest = prefill_q.popleft()
                engine._publish_signals()
                payload = engine.prefill_to_span(req).to_bytes()
                ctl.handoff(dest, req.req_id, payload)
                busy = True
            if len(engine.queue) or engine.slots.active:
                engine.run()
                busy = True
            if not busy:
                wake.wait(timeout=0.02)
                wake.clear()
    except Exception as e:  # noqa: BLE001 — last words, then die
        send({"ev": "error", "worker": worker_id, "error": repr(e)})
        raise
    finally:
        hb_stop.set()
        try:
            sock.close()
        except OSError:
            pass


# ----------------------------------------------------------- parent side

class ProcessEngineWorker:
    """Parent-side proxy for one spawned worker process.

    Owns the process handle and the result-plane connection; ``owned``
    is the set of parent req_ids currently routed here (the routing
    weight AND the re-route worklist if this worker dies)."""

    def __init__(self, worker_id: str, role: str, process):
        self.worker_id = worker_id
        self.role = role
        self.process = process
        self.sock: Optional[socket.socket] = None
        self._rfile = None
        self._wfile = None
        self._send_lock = threading.Lock()
        self.ready = threading.Event()
        self.warmed = threading.Event()
        self.reset_done = threading.Event()
        self.dead = threading.Event()    # result-plane EOF / send failure
        self.failed = False              # supervisor verdict (final)
        self.summaries: queue_lib.Queue = queue_lib.Queue()
        self.owned: set[int] = set()     # unfinished parent req_ids here
        self.pending_prefills = 0        # span jobs routed here (prefill)

    def attach(self, sock: socket.socket) -> None:
        self.sock = sock
        self._rfile = sock.makefile("r")
        self._wfile = sock.makefile("w")

    def send(self, obj: dict) -> bool:
        """Best-effort command write; a broken pipe marks the worker
        dead (the supervisor picks it up) instead of raising into the
        caller's submit path."""
        if self._wfile is None or self.dead.is_set():
            return False
        try:
            with self._send_lock:
                self._wfile.write(json.dumps(obj) + "\n")
                self._wfile.flush()
            return True
        except OSError:
            self.dead.set()
            return False

    def alive(self) -> bool:
        return (not self.failed and not self.dead.is_set()
                and self.process.is_alive())

    def load(self) -> int:
        return len(self.owned)

    def kill(self) -> None:
        if self.process.is_alive():
            self.process.kill()          # SIGKILL: no cleanup, no mercy
        self.process.join(timeout=10.0)
        self.close()

    def close(self) -> None:
        if self.sock is not None:
            try:
                self.sock.close()
            except OSError:
                pass


class ClusterSupervisor:
    """Worker lifecycle daemon: liveness via result-plane EOF, process
    exit, and heartbeat deadlines; failures hand the dead worker's
    in-flight requests back to the front-end for re-routing.

    A straggler (no heartbeat within ``liveness_deadline_s`` of the
    previous one, measured on the parent's clock at the central
    scheduler) is treated as failed: it is killed first, so a wedged
    process can never hold requests hostage while technically alive."""

    def __init__(self, front, liveness_deadline_s: float,
                 poll_s: float = 0.05):
        self.front = front
        self.liveness_deadline_s = liveness_deadline_s
        self.poll_s = poll_s
        self.stragglers = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="cluster-supervisor")

    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)

    def _run(self) -> None:
        while not self._stop.wait(self.poll_s):
            for w in self.front.workers:
                if w.failed:
                    continue
                straggling = self._straggling(w)
                if (w.dead.is_set() or not w.process.is_alive()
                        or straggling):
                    if straggling:
                        self.stragglers += 1
                    self.front._on_worker_failure(w)

    def _straggling(self, w: ProcessEngineWorker) -> bool:
        beat = self.front.server.heartbeats.get(w.worker_id)
        if beat is None:
            return False     # no beat yet: spawn grace, EOF covers death
        return (time.monotonic() - beat["t"]) > self.liveness_deadline_s


def require_cpu_backend() -> None:
    """Refuse to spawn process workers where they cannot get a device."""
    if jax.default_backend() == "tpu":
        raise RuntimeError(
            "ProcClusterFrontEnd is CPU-only: each worker process starts "
            "its own JAX runtime, and a TPU chip belongs to one process "
            "(this one already holds it).  On a TPU host use the threaded "
            "ClusterFrontEnd, which places one replica per device.")


class ProcClusterFrontEnd:
    """N OS-process engine workers, one central scheduler, one
    ``submit()`` surface — the ``ClusterFrontEnd`` contract over real
    processes, plus fault tolerance.

    The scheduler control plane is ALWAYS the TCP transport (an
    in-process server cannot cross address spaces).  ``roles`` enables
    the prefill/decode split exactly as in the threaded cluster; spans
    travel over the central ``handoff`` op and are forwarded to the
    decode owner's process as a ``span`` command.

    ``heartbeat_interval_s``/``liveness_deadline_s`` tune failure
    detection; the deadline should be several beats deep so one
    GC pause or scheduler hiccup is not a death sentence.  On failure,
    every in-flight request owned by the dead worker re-submits to the
    least-loaded survivor with its already-streamed tokens as a resume
    stash — greedy/seeded output is byte-identical to the no-failure
    run (see ``ContinuousBatchingEngine.submit_resume``).  ``summary()``
    reports ``failures`` and ``rerouted`` counts.
    """

    def __init__(self, cfg, n_workers: int = 2,
                 policy: PolicyLike = "xartrek",
                 platform: Platform = TPU_PLATFORM,
                 table: Optional[ThresholdTable] = None,
                 seed: int = 0, worker_prefix: str = "pw",
                 roles: Optional[Sequence[str]] = None,
                 heartbeat_interval_s: float = 0.25,
                 liveness_deadline_s: float = 10.0,
                 spawn_timeout_s: float = 300.0,
                 **engine_kwargs):
        require_cpu_backend()
        if n_workers < 1:
            raise ValueError(f"need at least one worker: {n_workers}")
        if roles is None:
            roles = ("mixed",) * n_workers
        roles = tuple(roles)
        if len(roles) != n_workers:
            raise ValueError(f"roles {roles} must name all "
                             f"{n_workers} workers")
        if any(r not in WORKER_ROLES for r in roles):
            raise ValueError(f"roles must be in {WORKER_ROLES}: {roles}")
        if not any(r in ("decode", "mixed") for r in roles):
            raise ValueError("need at least one decode-capable worker "
                             "(role 'decode' or 'mixed')")
        if any(r == "prefill" for r in roles) \
                and not engine_kwargs.get("paged"):
            raise ValueError("disaggregated roles require paged=True "
                             "(spans move KV at block granularity)")
        self.cfg = cfg
        self.roles = roles
        self.seed = seed
        self.engine_kwargs = dict(engine_kwargs)
        self.spawn_timeout_s = spawn_timeout_s
        self.heartbeat_interval_s = heartbeat_interval_s
        self.table = table or ThresholdTable()
        self.server = SchedulerServer(platform, self.table, bank=None,
                                      monitor=LoadMonitor(platform),
                                      policy=policy)
        self.failures = 0
        self.rerouted = 0
        self._span_threshold = int(
            engine_kwargs.get("prefill_tokens_per_step")
            or engine_kwargs.get("block_size") or 16)
        self._lock = threading.Lock()
        self._handles: dict[int, RequestHandle] = {}
        self._owner: dict[int, ProcessEngineWorker] = {}
        # req_id -> (request, owner, prefiller): spans in flight; the
        # central handoff sink resolves the CURRENT owner at delivery
        # time, so an owner that died meanwhile redirects to a survivor
        self._pending_spans: dict[
            int, tuple[GenerationRequest, ProcessEngineWorker,
                       ProcessEngineWorker]] = {}
        self.last_owners: dict[int, str] = {}
        self._started = False
        self._stopped = False
        self._tcp: Optional[TcpSchedulerServer] = None
        self._listener: Optional[socket.socket] = None
        self.workers: list[ProcessEngineWorker] = []
        self.supervisor = ClusterSupervisor(self, liveness_deadline_s)
        try:
            self._tcp = TcpSchedulerServer(self.server)
            self._sched_addr = self._tcp.start()
            # port 0 = kernel-assigned ephemeral port, race-free by
            # construction (no pick-then-bind window)
            self._listener = socket.socket(socket.AF_INET,
                                           socket.SOCK_STREAM)
            self._listener.bind(("127.0.0.1", 0))
            self._listener.listen(n_workers)
            self._result_addr = self._listener.getsockname()
            import multiprocessing as mp
            ctx = mp.get_context("spawn")   # own JAX runtime per worker
            for i in range(n_workers):
                wid = f"{worker_prefix}{i}"
                proc = ctx.Process(
                    target=_worker_main,
                    args=(wid, cfg, seed, dict(engine_kwargs),
                          self._sched_addr, self._result_addr, roles[i],
                          heartbeat_interval_s),
                    daemon=True, name=f"engine-{wid}")
                self.workers.append(ProcessEngineWorker(wid, roles[i],
                                                        proc))
            if any(r == "prefill" for r in roles):
                for w in self.workers:
                    if w.role != "prefill":
                        self.server.register_handoff_sink(
                            w.worker_id, self._make_sink())
        except BaseException:
            # construction failed halfway: release every socket/thread
            # already acquired so the caller's except path leaks nothing
            self._teardown_transport()
            raise

    # ------------------------------------------------------------ control
    def start(self) -> "ProcClusterFrontEnd":
        if self._started:
            return self
        self._started = True
        for w in self.workers:
            w.process.start()
        deadline = time.monotonic() + self.spawn_timeout_s
        try:
            pending = {w.worker_id: w for w in self.workers}
            while pending:
                self._listener.settimeout(
                    max(deadline - time.monotonic(), 0.001))
                sock, _ = self._listener.accept()
                hello = json.loads(sock.makefile("r").readline())
                w = pending.pop(hello["worker"])
                w.attach(sock)
                threading.Thread(target=self._read_loop, args=(w,),
                                 daemon=True,
                                 name=f"reader-{w.worker_id}").start()
        except (socket.timeout, OSError) as e:
            self.stop()
            raise TimeoutError(
                f"workers failed to connect within "
                f"{self.spawn_timeout_s}s: {sorted(pending)}") from e
        self.supervisor.start()
        return self

    def __enter__(self) -> "ProcClusterFrontEnd":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    def stop(self) -> None:
        """Idempotent full teardown: supervisor first (so deliberate
        shutdown is never misread as failure), then workers, then the
        transports."""
        if self._stopped:
            return
        self._stopped = True
        self.supervisor.stop()
        for w in self.workers:
            w.send({"cmd": "stop"})
        for w in self.workers:
            if w.process.ident is not None:
                w.process.join(timeout=10.0)
                if w.process.is_alive():
                    w.process.kill()
                    w.process.join(timeout=10.0)
            w.close()
        self._teardown_transport()
        self._started = False

    def _teardown_transport(self) -> None:
        if self._listener is not None:
            try:
                self._listener.close()
            except OSError:
                pass
            self._listener = None
        if self._tcp is not None:
            self._tcp.stop()
            self._tcp = None

    # ------------------------------------------------------- result plane
    def _read_loop(self, w: ProcessEngineWorker) -> None:
        try:
            for line in w._rfile:
                ev = json.loads(line)
                kind = ev.get("ev")
                if kind in ("token", "finish"):
                    with self._lock:
                        handle = self._handles.get(ev["req"])
                    if handle is not None:
                        handle.apply_event(ev)
                    if kind == "finish":
                        with self._lock:
                            w.owned.discard(ev["req"])
                elif kind == "ready":
                    w.ready.set()
                elif kind == "warmed":
                    w.warmed.set()
                elif kind == "reset_done":
                    w.reset_done.set()
                elif kind == "summary":
                    w.summaries.put(ev["data"])
                # "hello" handled at accept; "error" falls through to EOF
        except (OSError, ValueError):
            pass
        finally:
            w.dead.set()     # supervisor re-routes anything still owned

    # ---------------------------------------------------- fault tolerance
    def _on_worker_failure(self, w: ProcessEngineWorker) -> None:
        """Supervisor callback: declare ``w`` dead, kill what's left of
        it, and re-route its in-flight requests to survivors via
        resume-by-re-prefill.  Requests replay their already-streamed
        tokens, so consumers observe a seamless, byte-identical
        stream."""
        with self._lock:
            if w.failed:
                return
            w.failed = True
            self.failures += 1
        w.kill()
        with self._lock:
            rids = sorted(w.owned)
            w.owned.clear()
            # spans this worker was still prefilling: hand the whole
            # request to its decode owner (local prefill beats waiting
            # for a span that will never arrive)
            orphan_spans = [rid for rid, (_, _, src)
                            in self._pending_spans.items() if src is w]
        for rid in orphan_spans:
            with self._lock:
                entry = self._pending_spans.pop(rid, None)
            if entry is None:
                continue
            request, owner, _ = entry
            if owner.failed:
                with self._lock:
                    rids.append(rid)     # owner died too: full re-route
            else:
                owner.send({"cmd": "submit",
                            "req": _req_to_wire(request)})
        for rid in rids:
            self._reroute(rid)

    def _reroute(self, rid: int) -> None:
        with self._lock:
            handle = self._handles.get(rid)
        if handle is None or handle.finished:
            return
        survivors = [v for v in self.workers
                     if v.role != "prefill" and v.alive()]
        if not survivors:
            # nobody left to serve it: fail the handle loudly instead
            # of letting result() hang to its timeout
            handle.apply_event({"ev": "finish",
                                "tokens": list(handle.tokens),
                                "logprobs": list(handle.logprobs),
                                "finish_reason": FINISH_ABORTED})
            return
        with self._lock:
            self._pending_spans.pop(rid, None)
            target = min(survivors, key=lambda v: v.load())
            target.owned.add(rid)
            self._owner[rid] = target
            self.rerouted += 1
        target.send({"cmd": "submit", "req": _req_to_wire(handle.request),
                     "resume_tokens": list(handle.tokens),
                     "resume_logprobs": list(handle.logprobs)})

    # ------------------------------------------------------ disaggregation
    def _make_sink(self):
        """Span consumer on the central scheduler: forward the span to
        the request's CURRENT decode owner's process.  Runs on the TCP
        handler thread."""
        def sink(req_id: int, payload: bytes) -> None:
            with self._lock:
                entry = self._pending_spans.pop(req_id, None)
            if entry is None:
                return               # request re-routed meanwhile: drop
            request, owner, _ = entry
            msg = {"cmd": "span", "req": _req_to_wire(request),
                   "payload": base64.b64encode(payload).decode()}
            if owner.failed or not owner.send(msg):
                # owner died between routing and delivery: serve the
                # request fresh on a survivor (prefill recomputes)
                self._reroute(req_id)
        return sink

    # ------------------------------------------------------------- serve
    def _require_started(self) -> None:
        if not self._started or self._stopped:
            raise RuntimeError("cluster not started (use start() or with)")

    def warmup(self, timeout: float = 300.0,
               max_prompt: Optional[int] = None) -> None:
        """Wait for every worker's engine build, then run each worker's
        warmup pass (compiles the lazy jits, then zeroes stats) —
        strictly outside any timed region, like the single-engine
        benchmarks.  ``max_prompt`` additionally pre-compiles the
        longest prompt bucket the caller intends to use."""
        self._require_started()
        deadline = time.monotonic() + timeout
        for w in self.workers:
            if not w.ready.wait(max(deadline - time.monotonic(), 0.001)):
                raise TimeoutError(
                    f"worker {w.worker_id} not ready within {timeout}s")
        for w in self.workers:
            w.warmed.clear()
            w.send({"cmd": "warmup", "max_prompt": max_prompt})
        for w in self.workers:
            if not w.warmed.wait(max(deadline - time.monotonic(), 0.001)):
                raise TimeoutError(
                    f"worker {w.worker_id} warmup timed out")
        if any(w.role == "prefill" for w in self.workers):
            # warm the span tier end to end (prefill-to-span, handoff,
            # span-rehydrate scatter), then reset every worker's stats
            vocab = max(getattr(self.cfg, "vocab_size", 2), 2)
            n = self._span_threshold + 4
            h = self.submit(GenerationRequest(
                np.arange(1, n + 1, dtype=np.int32) % vocab,
                max_new_tokens=2))
            h.result(timeout=max(deadline - time.monotonic(), 0.001))
            with self._lock:
                self._handles.pop(h.req_id, None)
                self._owner.pop(h.req_id, None)
            for w in self.workers:
                w.reset_done.clear()
                w.send({"cmd": "reset"})
            for w in self.workers:
                w.reset_done.wait(max(deadline - time.monotonic(), 0.001))

    def set_decode_thresholds(self, fpga_thr: float,
                              arm_thr: float = float("inf")) -> None:
        """Seed every worker's decode-step threshold row on the CENTRAL
        table (decisions happen here; the workers' local tables are
        bypassed by the TCP clients)."""
        for w in self.workers:
            row = self.table.row(f"{w.worker_id}_decode")
            row.fpga_thr, row.arm_thr = fpga_thr, arm_thr

    def submit(self, request: GenerationRequest,
               on_token=None) -> RequestHandle:
        """Route to the least-loaded live decode-capable worker; the
        returned handle rehydrates from result-plane events, so
        streaming, ``result()`` and ``abort()`` behave exactly as
        in-process.  With prefill roles, long prompts route through the
        span tier (prefill worker -> handoff -> owner), short ones
        prefill locally on the owner."""
        self._require_started()
        prefillers = [w for w in self.workers
                      if w.role == "prefill" and w.alive()]
        with self._lock:
            decoders = [w for w in self.workers
                        if w.role != "prefill" and w.alive()]
            if not decoders:
                raise RuntimeError("no live decode-capable workers")
            dest = min(decoders, key=lambda w: w.load())
            handle = RequestHandle(request, engine=self,
                                   on_token=on_token)
            self._handles[request.req_id] = handle
            self._owner[request.req_id] = dest
            dest.owned.add(request.req_id)
            span_tier = (prefillers
                         and request.prompt_len > self._span_threshold)
            if span_tier:
                source = min(prefillers,
                             key=lambda w: w.pending_prefills)
                source.pending_prefills += 1
                self._pending_spans[request.req_id] = (request, dest,
                                                       source)
        if span_tier:
            source.send({"cmd": "prefill", "req": _req_to_wire(request),
                         "dest": dest.worker_id})
        else:
            dest.send({"cmd": "submit", "req": _req_to_wire(request)})
        return handle

    def abort(self, req_id: int) -> bool:
        """RequestHandle.abort() proxy: forward to the owning worker.
        The worker's engine finishes the request as ``aborted`` and the
        finish event closes the parent handle."""
        with self._lock:
            handle = self._handles.get(req_id)
            owner = self._owner.get(req_id)
        if handle is None or handle.finished or owner is None:
            return False
        return owner.send({"cmd": "abort", "req": req_id})

    def drain(self, timeout: float = 300.0) -> dict[int, RequestOutput]:
        """Block until every submitted request finished (including any
        re-routed off a failed worker); returns (and forgets) their
        outputs keyed by req_id."""
        self._require_started()
        deadline = time.monotonic() + timeout
        with self._lock:
            handles = dict(self._handles)
            owners = {rid: w.worker_id for rid, w in self._owner.items()}
        out = {}
        for rid, h in handles.items():
            out[rid] = h.result(timeout=max(deadline - time.monotonic(),
                                            0.001))
        with self._lock:
            # attribution reflects the FINAL owner (post-re-route)
            self.last_owners = {rid: self._owner[rid].worker_id
                                if rid in self._owner else owners.get(rid)
                                for rid in out}
            for rid in out:
                self._handles.pop(rid, None)
                self._owner.pop(rid, None)
        return out

    # ------------------------------------------------------------- stats
    def summary(self, timeout: float = 30.0) -> dict:
        """Cluster-wide accounting: per-worker runtime/engine summaries
        fetched over the result plane, the central scheduler's decision
        histogram and signals, plus the fault-tolerance counters
        (``failures``, ``rerouted``, ``stragglers``) and each worker's
        liveness/heartbeat state."""
        per_engine: dict[str, dict] = {}
        pools: dict[str, dict] = {}
        deadline = time.monotonic() + timeout
        for w in self.workers:
            if not w.alive():
                continue
            while not w.summaries.empty():    # drop stale responses
                w.summaries.get_nowait()
            if not w.send({"cmd": "summary"}):
                continue
            try:
                data = w.summaries.get(
                    timeout=max(deadline - time.monotonic(), 0.001))
            except queue_lib.Empty:
                continue
            per_engine[w.worker_id] = data["runtime"]
            per_engine[w.worker_id]["engine_stats"] = data["engine_stats"]
            if "pool" in data:
                pools[w.worker_id] = data["pool"]
        hb = {wid: beat["seq"]
              for wid, beat in self.server.heartbeats.items()}
        return {
            "per_engine": per_engine,
            "migrations": sum(s.get("migrations", 0)
                              for s in per_engine.values()),
            "decisions": {k.value: v
                          for k, v in self.server.decisions.items()},
            "signals": dataclasses.asdict(self.server.signals()),
            "roles": {w.worker_id: w.role for w in self.workers},
            "handoffs": self.server.handoffs,
            "pools": pools,
            "failures": self.failures,
            "rerouted": self.rerouted,
            "stragglers": self.supervisor.stragglers,
            "workers": {w.worker_id: {"alive": w.alive(),
                                      "failed": w.failed,
                                      "heartbeats": hb.get(w.worker_id)}
                        for w in self.workers},
        }
