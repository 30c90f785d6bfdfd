"""Drives the system under test through one measured window.

Builds the served path — ``ContinuousBatchingEngine`` under an
``XarTrekRuntime`` with ``LatencyAwarePolicy`` on one chip, or a
``ClusterFrontEnd`` of one replica per chip — warms every shape the
cell's traffic can reach, for both builds, then offers the traffic and
timestamps every token on the host clock through ``on_token``.

Nothing here decides a build: the policy does, and which build served
each call is read back from the runtime's call log.
"""
from __future__ import annotations

import contextlib
import dataclasses
import faulthandler
import gc
import heapq
import queue
import sys
import threading
import time
from typing import Optional

import numpy as np

from bench import traffic as traffic_lib

# open-loop requests due in the window may finish this long after it
GRACE_S = 60.0
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def model_config(name: str, dims: dict):
    """The program's ``ModelConfig`` for a configuration's dimensions."""
    from repro.configs.model_config import ModelConfig
    return ModelConfig(
        name=name, family="dense", num_layers=dims["num_layers"],
        d_model=dims["d_model"], num_heads=dims["num_heads"],
        num_kv_heads=dims["num_kv_heads"], d_ff=dims["d_ff"],
        vocab_size=dims["vocab_size"], head_dim=dims["head_dim"],
        mlp_type="swiglu", dtype="bfloat16", kv_cache_dtype="bfloat16",
        rope_theta=float(dims["rope_theta"]), norm_eps=dims["norm_eps"],
        tie_embeddings=dims["tie_embeddings"])


@dataclasses.dataclass
class Record:
    """One request as the benchmark saw it."""
    req_id: int
    t_due: float
    t_submit: float
    prompt: np.ndarray
    max_new: int
    greedy: bool
    replica: int
    session: Optional[int]
    handle: object = None
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    aborted: bool = False
    on_done: object = None
    t_admit: Optional[float] = None     # host time its admission began

    @property
    def complete(self) -> bool:
        return len(self.tokens) >= self.max_new

    def on_token(self, tok: int) -> None:
        self.times.append(time.perf_counter())
        self.tokens.append(int(tok))
        if len(self.tokens) == self.max_new and self.on_done is not None:
            self.on_done(self)


class CompileCounter:
    """Counts XLA backend compiles (a window must have none)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, _secs: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.n += 1


class GcPauses:
    """Pauses of Python's garbage collector, (start, seconds, generation),
    for the run's log: a long one stalls every thread that steps the
    chip."""

    def __init__(self):
        self.pauses: list = []
        self._t = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append((self._t, time.perf_counter() - self._t,
                                info["generation"]))
            self._t = None

    def close(self) -> None:
        gc.callbacks.remove(self._on)


def token_gaps(records, t0: float, t1: float, n: int = 3) -> list:
    """The ``n`` longest spans of the window in which no request got a
    token: (seconds into the window, length)."""
    ts = np.sort(np.array([t for r in records for t in r.times
                           if t0 <= t <= t1] + [t0, t1]))
    d = np.diff(ts)
    top = np.argsort(-d)[:n]
    return [(float(ts[i] - t0), float(d[i])) for i in top]


class _EngineLoop:
    """The one-chip serve loop on its own thread: ``run()`` drains what
    is queued, then the thread waits for the next submission."""

    def __init__(self, engine):
        self.engine = engine
        self.error: Optional[BaseException] = None
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name="engine-loop")

    def _loop(self) -> None:
        eng = self.engine
        try:
            while not self._stop.is_set():
                if len(eng.queue) or eng.slots.active:
                    eng.run()
                else:
                    self._wake.wait(0.005)
                    self._wake.clear()
        except BaseException as e:      # reported by the harness
            self.error = e

    def start(self) -> None:
        self._thread.start()

    def wake(self) -> None:
        self._wake.set()

    def stop(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._thread.ident is None:
            return
        self._thread.join(timeout=120)
        if self._thread.is_alive():
            raise RuntimeError("engine loop did not stop")


class Served:
    """The system under test, built for one cell."""

    def __init__(self, cfg, params, engine_kw: dict, chips: int):
        from repro.core.function import FunctionRegistry
        from repro.core.policy import LatencyAwarePolicy
        from repro.core.runtime import XarTrekRuntime
        from repro.serve import ClusterFrontEnd, ContinuousBatchingEngine
        self.cfg = cfg
        self.chips = chips
        policy = LatencyAwarePolicy(
            prefill_tokens_per_step=engine_kw["prefill_tokens_per_step"])
        self.fe = None
        self.loop = None
        if chips == 1:
            rt = XarTrekRuntime(registry=FunctionRegistry())
            eng = ContinuousBatchingEngine(cfg, params=params, runtime=rt,
                                           policy=policy, **engine_kw)
            self.engines, self.runtimes = [eng], [rt]
            self.loop = _EngineLoop(eng)
        else:
            self.fe = ClusterFrontEnd(cfg, n_engines=chips, policy=policy,
                                      params=params, **engine_kw)
            self.engines = [w.engine for w in self.fe.workers]
            self.runtimes = [w.runtime for w in self.fe.workers]
            devices = [e.device for e in self.engines]
            if len(set(devices)) != chips:
                raise RuntimeError(f"replicas share devices: {devices}")
        self.ctx_log: list = []
        self.admitted: dict = {}
        for i, eng in enumerate(self.engines):
            self._log_chunks(eng, i)
            self._log_admissions(eng)

    def _log_chunks(self, eng, replica: int) -> None:
        """Record (host time, offset, tokens) of every prefill chunk, so
        the FLOPs of the prefill work in a traced window are exact."""
        orig = eng._ctx_chunk
        sink = self.ctx_log

        def logged(feed, offset, n_chunk, blocks, sampling):
            out = orig(feed, offset, n_chunk, blocks, sampling)
            sink.append((time.perf_counter(), replica, int(offset),
                         int(n_chunk)))
            return out

        eng._ctx_chunk = logged

    def _log_admissions(self, eng) -> None:
        """Record when each request's first admission began, on the
        benchmark's clock (the engine's own ``queue_wait_s`` counts from
        the start of its ``run()`` call unless the submitter stamps
        arrivals on that clock)."""
        orig = eng._admit
        sink = self.admitted

        def logged(req, now=0.0):
            t = time.perf_counter()
            out = orig(req, now)
            sink.setdefault(req.req_id, t)
            return out

        eng._admit = logged

    def stamp_admissions(self, records) -> None:
        for r in records:
            r.t_admit = self.admitted.get(r.req_id)

    # ------------------------------------------------------------ warm-up
    def warm(self, max_prompt: int) -> list[int]:
        """Compile and run once every program the traffic can reach, for
        both builds: prefill-at-offset at each power-of-two bucket from
        ``min_bucket`` to the longest prompt's, its chunk scatter, the
        decode step and the copy-on-write block copy.  Replicas warm in
        parallel (compiles release the interpreter lock)."""
        from repro.serve.engine import prompt_bucket
        eng0 = self.engines[0]
        top = prompt_bucket(max_prompt, eng0.min_bucket)
        buckets, b = [], eng0.min_bucket
        while b <= top:
            buckets.append(b)
            b *= 2
        errors: list = []
        self.uncompiled: list = []
        # a replica's loop runs under its device as the default device,
        # which is part of every jit's cache key: warm under the same
        pin = self.fe is not None

        def one(eng, rt):
            try:
                self.uncompiled += _warm_engine(eng, rt, buckets, pin)
            except BaseException as e:     # re-raised below
                errors.append(e)

        threads = [threading.Thread(target=one, args=(e, r))
                   for e, r in zip(self.engines, self.runtimes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]
        # one tiny request through the real loop: the small host-side
        # jits of admission and sampling compile here, not in the window
        if self.fe is not None:
            self.fe.start()
            self.fe.warmup(timeout=600)
        else:
            from repro.serve import GenerationRequest
            eng0.run([GenerationRequest(np.arange(1, 5, dtype=np.int32),
                                        max_new_tokens=2)])
            self.loop.start()
        self.reset()
        return buckets

    def reset(self) -> None:
        for eng, rt in zip(self.engines, self.runtimes):
            rt.call_log.clear()
            eng.reset_stats()
        self.ctx_log.clear()
        self.admitted.clear()

    # -------------------------------------------------------------- serve
    def submit(self, req: traffic_lib.Request, t_due: float,
               on_done) -> Record:
        from repro.serve import GenerationRequest, SamplingParams
        g = GenerationRequest(
            req.prompt, max_new_tokens=req.max_new_tokens,
            sampling=SamplingParams(temperature=req.temperature,
                                    top_p=req.top_p, seed=req.seed))
        rec = Record(g.req_id, t_due, time.perf_counter(), req.prompt,
                     req.max_new_tokens, req.greedy, 0, req.session,
                     on_done=on_done)
        if self.fe is not None:
            rec.handle = self.fe.submit(g, on_token=rec.on_token)
            eng = rec.handle._engine
            rec.replica = next(i for i, e in enumerate(self.engines)
                               if e is eng)
        else:
            rec.handle = self.engines[0].submit(g, on_token=rec.on_token)
            self.loop.wake()
        return rec

    def error(self) -> Optional[BaseException]:
        if self.loop is not None:
            return self.loop.error
        return None

    def idle(self) -> bool:
        return all(not len(e.queue) and not e.slots.active
                   for e in self.engines)

    def queued(self) -> int:
        return sum(len(e.queue) for e in self.engines)

    def abort(self, recs) -> None:
        for r in recs:
            if not r.handle.finished:
                r.aborted = True
                r.handle.abort()

    def peak_bytes(self) -> int:
        return max(int(((e.device or _default_device()).memory_stats()
                        or {}).get("peak_bytes_in_use", 0))
                   for e in self.engines)

    def close(self) -> None:
        """Stop every thread and drop every device buffer of the program."""
        if self.loop is not None:
            self.loop.stop()
        if self.fe is not None:
            self.fe.stop()
        self.engines = self.runtimes = []
        self.fe = self.loop = None
        gc.collect()


def _default_device():
    import jax
    return jax.devices()[0]


def _warm_engine(eng, rt, buckets, pin: bool) -> list:
    """Warm one engine; returns the (build, width) prefill buckets whose
    program does not compile.  A build stops at its first such bucket
    (wider chunks need more on-chip memory still): the program cannot
    serve those shapes on that build, and a window that sends one there
    fails the same way."""
    import jax
    import jax.numpy as jnp
    from repro.core.targets import TargetKind
    from repro.models.sampling import sampling_leaves
    from repro.serve.api import SamplingParams
    dev = eng.device or _default_device()
    greedy = SamplingParams()
    bs, tw, B = eng.slots.block_size, eng.slots.table_width, eng.slots.max_slots
    failed, dead = [], set()
    with (jax.default_device(dev) if pin else contextlib.nullcontext()):
        ctx = rt.binaries[eng._prefill_ctx_name]
        for W in buckets:
            batch = {"tokens": jnp.zeros((1, W), jnp.int32),
                     "offset": jnp.zeros((1,), jnp.int32),
                     "length": jnp.full((1,), W, jnp.int32),
                     "block_table": jnp.zeros((1, tw), jnp.int32),
                     **sampling_leaves(greedy, 1)}
            out = None
            for kind in (TargetKind.HOST, TargetKind.ACCEL):
                if kind in dead:
                    continue
                args = (eng.params, eng.cache, batch)
                try:
                    variant = ctx.variant_for(kind, args)
                except jax.errors.JaxRuntimeError as e:
                    log(f"{eng._prefill_ctx_name} {kind.value} W={W} does "
                        f"not compile: {str(e).splitlines()[0][:160]}")
                    failed.append((kind.value, W))
                    dead.add(kind)
                    continue
                out = jax.block_until_ready(variant(*args))
            if out is None:
                continue
            # the chunk's KV lands in the junk block 0 (no real position)
            nphys = (W + 2 * bs - 2) // bs
            eng.cache = eng._scatter_chunk(
                eng.cache, out[2], jnp.zeros((nphys,), jnp.int32),
                jnp.int32(0), jnp.int32(0))
        dec = rt.binaries[eng._decode_name]
        batch = {"tokens": jnp.zeros((B, 1), jnp.int32),
                 "index": jnp.zeros((B,), jnp.int32),
                 "block_table": jnp.zeros((B, tw), jnp.int32),
                 **sampling_leaves(greedy, B)}
        for kind in (TargetKind.HOST, TargetKind.ACCEL):
            args = (eng.params, eng.cache, batch)
            toks, _, eng.cache = dec.variant_for(kind, args)(*args)
            jax.block_until_ready(toks)
        eng.cache = eng._copy_block(eng.cache, jnp.int32(0), jnp.int32(0))
        jax.block_until_ready(eng.cache)
    return failed


class TraceWindow:
    """Profiles ``length`` seconds from ``at`` seconds into the window
    (host clock), started and stopped from the generator's thread; no
    profiling when ``log_dir`` is None."""

    def __init__(self, log_dir: Optional[str], at: float, length: float):
        self.log_dir, self.at, self.length = log_dir, at, length
        self.start = self.stop = float("inf")
        self.t_begin = self.t_end = None
        self.on = False

    def arm(self, t0: float) -> None:
        self.start = t0 + self.at
        self.stop = self.start + self.length

    def next_event(self) -> float:
        if self.log_dir is None or self.t_end is not None:
            return float("inf")
        return self.stop if self.on else self.start

    def poll(self, now: float) -> None:
        import jax
        if self.log_dir is None:
            return
        if not self.on and self.t_begin is None and now >= self.start:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            self.t_begin = time.perf_counter()
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.on = True
        elif self.on and now >= self.stop:
            self.t_end = time.perf_counter()
            jax.profiler.stop_trace()
            self.on = False

    def finish(self) -> None:
        if self.on:
            self.poll(float("inf"))


@dataclasses.dataclass
class Window:
    """What one measured window produced."""
    t0: float
    t1: float
    records: list              # requests due in the window
    calls: list                # per replica: runtime calls in the window
    stats: list                # per replica: engine counters at the close
    lateness: list             # seconds each submission ran late
    compiles: int              # XLA compiles inside the window
    trace: Optional[TraceWindow] = None
    queue_depth: list = dataclasses.field(default_factory=list)


def _calls_and_stats(served: Served) -> tuple[list, list]:
    return ([list(rt.call_log) for rt in served.runtimes],
            [dict(e.stats, prefix=e.prefix_stats())
             for e in served.engines])


def run_offline(served: Served, spec: dict, seed: int, seconds: float,
                tracer: TraceWindow, counter: CompileCounter) -> Window:
    """Documents queued at t=0 and refilled as each finishes; at the
    close, what is still queued or in flight is aborted."""
    vocab = served.cfg.vocab_size
    stream = traffic_lib.Offline(spec, seed, vocab)
    max_slots = served.engines[0].slots.max_slots
    done: queue.Queue = queue.Queue()
    records = []
    c0 = counter.n
    t0 = time.perf_counter()
    t1 = t0 + seconds
    tracer.arm(t0)
    for _ in range(max_slots + stream.backlog(max_slots)):
        records.append(served.submit(stream.next(), t0, done.put))
    while True:
        now = time.perf_counter()
        if now >= t1:
            break
        tracer.poll(now)
        if served.error() is not None:
            raise served.error()
        try:
            done.get(timeout=max(min(t1, tracer.next_event()) - now, 0.0))
        except queue.Empty:
            continue
        records.append(served.submit(stream.next(), time.perf_counter(),
                                     done.put))
        while True:                       # one refill per finished document
            try:
                done.get_nowait()
            except queue.Empty:
                break
            records.append(served.submit(stream.next(), time.perf_counter(),
                                         done.put))
    tracer.finish()
    calls, stats = _calls_and_stats(served)
    compiles = counter.n - c0
    served.abort(records)
    _wait_idle(served, GRACE_S)
    return Window(t0, t1, records, calls, stats, [], compiles, tracer)


def run_sessions(served: Served, spec: dict, seed: int, seconds: float,
                 tracer: TraceWindow, counter: CompileCounter,
                 rate: Optional[float] = None) -> Window:
    """Open loop: sessions open on the mix's schedule; a session's next
    turn is due one think time after its previous reply ends.  Every
    turn due in the window is served; what is unfinished a grace period
    after the close is aborted and counts as failed."""
    plan = traffic_lib.Sessions(spec, seed, served.cfg.vocab_size, seconds,
                                rate=rate)
    done: queue.Queue = queue.Queue()
    records, lateness = [], []
    c0 = counter.n
    t0 = time.perf_counter()
    t1 = t0 + seconds
    tracer.arm(t0)
    heap = [(t0 + s.start_s, s.index) for s in plan.sessions]
    heapq.heapify(heap)
    depth = []

    def finished(rec: Record) -> None:
        s = plan.sessions[rec.session]
        think = plan.finished(s, rec.prompt, rec.tokens)
        if not s.done:
            due = rec.times[-1] + think
            if due < t1:
                heapq.heappush(heap, (due, s.index))

    while True:
        while True:
            try:
                finished(done.get_nowait())
            except queue.Empty:
                break
        now = time.perf_counter()
        tracer.poll(now)
        if served.error() is not None:
            raise served.error()
        if heap and heap[0][0] <= now:
            due, si = heapq.heappop(heap)
            rec = served.submit(plan.request(plan.sessions[si]), due,
                                done.put)
            lateness.append(rec.t_submit - due)
            records.append(rec)
            continue
        if now >= t1:
            break
        nxt = min(heap[0][0] if heap else t1, t1, tracer.next_event())
        if not depth or now - t0 - depth[-1][0] >= 0.5:
            depth.append((now - t0, served.queued()))
        try:
            finished(done.get(timeout=max(min(nxt - now, 0.05), 0.0)))
        except queue.Empty:
            pass
    tracer.finish()
    compiles = counter.n - c0
    deadline = time.perf_counter() + GRACE_S
    for rec in records:
        left = deadline - time.perf_counter()
        if left <= 0 or not _wait_handle(rec, left):
            break
    served.abort(records)
    _wait_idle(served, GRACE_S)
    calls, stats = _calls_and_stats(served)
    return Window(t0, t1, records, calls, stats, lateness, compiles, tracer,
                  depth)


def _wait_handle(rec: Record, timeout: float) -> bool:
    try:
        rec.handle.result(timeout=timeout)
        return True
    except TimeoutError:
        return False


def _wait_idle(served: Served, timeout: float) -> None:
    deadline = time.perf_counter() + timeout
    while not served.idle():
        if time.perf_counter() > deadline:
            for i, e in enumerate(served.engines):
                log(f"r{i}: queued {len(e.queue)}, active "
                    f"{len(e.slots.active)}, stats {e.stats}, last calls "
                    f"{served.runtimes[i].call_log[-3:]}")
            faulthandler.dump_traceback(file=sys.stderr, all_threads=True)
            raise RuntimeError("engines did not go idle after the window")
        if served.error() is not None:
            raise served.error()
        time.sleep(0.01)


RUNNERS = {"offline": run_offline, "sessions": run_sessions}
