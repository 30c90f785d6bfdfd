"""Serving engines: synchronous batch and continuous batching.

``ServeEngine`` is the position-synchronised baseline: one batch of
equal-length (caller-left-padded) prompts runs prefill + max_new_tokens
decode steps in lockstep.

``ContinuousBatchingEngine`` serves a ragged arrival stream: requests
are admitted at arbitrary times into per-request KV-cache slots,
prefill of new arrivals interleaves with decode of in-flight ones, and
finished slots are evicted and reused immediately (no head-of-line
blocking on batch formation or on the batch's slowest request).

Serve API v2 (see ``serve.api``): requests are ``GenerationRequest``s
carrying a per-request ``SamplingParams``; ``submit`` returns a
streaming ``RequestHandle`` (iterate / ``on_token`` callback /
``result()``), ``abort`` cancels mid-stream, and completed requests
come back as ``RequestOutput`` (tokens + finish reason + queue-wait /
TTFT / TPOT metrics).  Sampling runs IN-GRAPH inside the jitted step
functions (``models/sampling.py``): the decode step takes (B,)
temperature/top_k/top_p/seed vectors and returns sampled tokens, so the
compile signature is static across any request mix and the sampling
math is identical on every backend.

Xar-Trek integration: both engines can dispatch every prefill/decode
step through an XarTrekRuntime so the scheduler (Algorithm 2) migrates
steps between HOST/AUX/ACCEL as load changes — the Figure-6 throughput
experiment's analogue, with the continuous engine playing the
multi-tenant arrival stream.

Profiler spans (``jax.profiler.TraceAnnotation``: host events on the
device trace's clock, recorded only while a profiler runs):
``engine.iteration`` (one pass of the ``run()`` loop; args ``queued``,
``active``), ``engine.admit`` (``req``, ``feed`` tokens),
``engine.prefill_chunk`` (``offset``, ``width``, ``bucket``),
``engine.decode`` (``step``, ``rows``, ``ctx`` = the pool positions the
step reads, ``blocks`` = the pool blocks they fill) with ``engine.blocks``, ``engine.upload``,
``engine.readback`` and ``engine.emit`` inside it, and ``engine.sleep``
(waiting for an arrival).  Each engine's loop runs on its own thread,
which the profiler gives its own line.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
import warnings
from typing import Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.configs.model_config import ModelConfig
from repro.core.binary import committed_device
from repro.core.function import MigratableFunction
from repro.core.policy import (
    LoadSignals, PinAccel, PinHost, SchedulingPolicy, ewma, resolve_policy,
)
from repro.core.runtime import XarTrekRuntime
from repro.core.targets import TargetKind
from repro.models.model import build_model
from repro.models.sampling import sampling_leaves
from repro.serve.api import (
    FINISH_ABORTED, FINISH_LENGTH, FINISH_STOP, GenerationRequest,
    RequestHandle, RequestOutput, SamplingParams,
)
from repro.serve.batch import (
    KVSpan, PagedSlotManager, PartialPrefill, Slot, SlotManager,
)
from repro.serve.scheduler import RequestQueue
from repro.serve import spec as spec_lib

_BACKEND_DEPRECATION_WARNED = False
_ON_STEP_DEPRECATION_WARNED = False
# dense draft-cache rows are whole multiples of this many positions:
# kernels.ops.gqa_decode_ragged views a row as blocks of its default
# block_k, and a block's position count must tile (8k rows) on TPU
DRAFT_BLOCK = 128


@dataclasses.dataclass
class GenerationResult:
    tokens: np.ndarray            # (B, n_generated)
    prefill_ms: float
    decode_ms: float

    @property
    def tokens_per_second(self) -> float:
        n = self.tokens.shape[0] * self.tokens.shape[1]
        return n / max((self.prefill_ms + self.decode_ms) / 1e3, 1e-9)


class ServeEngine:
    def __init__(self, cfg: ModelConfig,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 params=None, seed: int = 0,
                 runtime: Optional[XarTrekRuntime] = None):
        self.cfg = cfg
        self.model = build_model(cfg, mesh)
        self.mesh = mesh
        self.runtime = runtime
        if params is None:
            params = self.model.init(jax.random.PRNGKey(seed))
        self.params = params
        self._prefill = jax.jit(self.model.prefill)
        self._decode = jax.jit(self.model.decode)

    def _sample(self, logits: jax.Array) -> jax.Array:
        """Greedy over the last position.  logits: (B,1,V) or (B,1,K,V)."""
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def generate(self, prompts: jax.Array, max_new_tokens: int = 16,
                 patch_embeds: Optional[jax.Array] = None
                 ) -> GenerationResult:
        """prompts: (B, S) int32 (or (B, K, S) for audio)."""
        cfg = self.cfg
        audio = cfg.family == "audio" and cfg.num_codebooks > 1
        B = prompts.shape[0]
        S = prompts.shape[-1]
        max_seq = S + max_new_tokens

        batch = {"tokens": prompts}
        if patch_embeds is not None:
            batch["patch_embeds"] = patch_embeds

        t0 = time.perf_counter()
        if self.runtime is not None and "serve_prefill" in self.runtime.binaries:
            logits, cache = self.runtime.call("serve_prefill", self.params,
                                              batch)
        else:
            logits, cache = self._prefill(self.params, batch)
        logits = jax.block_until_ready(logits)
        prefill_ms = (time.perf_counter() - t0) * 1e3

        # grow the cache to max_seq (prefill cache covers the prompt only)
        cache = self._grow_cache(cache, B, max_seq, S)

        out_tokens = []
        t0 = time.perf_counter()
        tok = self._sample(logits[:, -1:])               # (B,1) or (B,1,K)
        for i in range(max_new_tokens):
            out_tokens.append(np.asarray(tok).reshape(B, -1))
            dec_batch = {
                "tokens": (jnp.moveaxis(tok, -1, 1) if audio else tok),
                "index": jnp.int32(S + i),
            }
            if self.runtime is not None and "serve_decode" in self.runtime.binaries:
                logits, cache = self.runtime.call("serve_decode", self.params,
                                                  cache, dec_batch)
            else:
                logits, cache = self._decode(self.params, cache, dec_batch)
            tok = self._sample(logits[:, -1:])
        jax.block_until_ready(tok)
        decode_ms = (time.perf_counter() - t0) * 1e3
        return GenerationResult(np.stack(out_tokens, axis=1).squeeze(-1)
                                if not audio else np.stack(out_tokens, 1),
                                prefill_ms, decode_ms)

    def _grow_cache(self, cache: dict, batch: int, max_seq: int,
                    prompt_len: int) -> dict:
        full = self.model.init_cache(batch, max_seq)
        for k in full:
            if k in ("k", "v", "k_scale", "v_scale", "attn_k", "attn_v"):
                full[k] = jax.lax.dynamic_update_slice(
                    full[k], cache[k].astype(full[k].dtype),
                    (0,) * full[k].ndim)
            else:
                full[k] = cache[k].astype(full[k].dtype)
        return full


# ------------------------------------------------------ continuous batching

def prompt_bucket(n: int, min_bucket: int = 8) -> int:
    """Next power-of-two prefill width >= n (bounds recompiles to
    O(log max_prompt) shape buckets)."""
    b = min_bucket
    while b < n:
        b *= 2
    return b


def spec_verify_batch(vb: dict) -> dict:
    """Assemble the multi-token verify batch IN-GRAPH from the draft
    chain's device-resident output, so the round needs no host sync
    between the two dispatches.  ``vb`` carries ``drafts`` (B, W) as
    produced by the chain plus the SAME ``last``/``pos`` vectors the
    chain consumed; row i's verify feed is [last_i, d_1..d_{n-1}] at
    offset pos_i — bitwise the batch the host loop used to build, with
    inactive rows (n_valid == 0) masked to the junk self-attention at
    offset 0 whose writes land in junk block 0."""
    vb = dict(vb)
    drafts = vb.pop("drafts")
    last, pos = vb.pop("last"), vb.pop("pos")
    n = vb["n_valid"]
    W = drafts.shape[1]
    cols = jnp.arange(W, dtype=jnp.int32)[None, :]
    # last rides as the (B, 1) token_vector the CHAIN consumed — the
    # column concatenates directly, no per-round slice dispatch
    toks = jnp.concatenate([last, drafts[:, :W - 1]], axis=1)
    keep = (n[:, None] > 0) & (cols < n[:, None])
    vb["tokens"] = jnp.where(keep, toks, 0).astype(jnp.int32)
    vb["offset"] = jnp.where(n > 0, pos, 0).astype(jnp.int32)
    vb["length"] = jnp.where(n > 0, pos + n, W).astype(jnp.int32)
    return vb


# cache dtypes that represent every value of the compute dtype exactly
# (f32 is a strict superset of bf16/f16; same-dtype is trivially exact)
_KV_WIDENING = {("bfloat16", "float32"), ("float16", "float32")}


def kv_cache_lossless(cfg) -> bool:
    """True when storing compute-dtype KV in ``kv_cache_dtype`` is exact.

    The byte-identity invariant (greedy tokens bitwise equal cache-on vs
    cache-off, across HOST/ACCEL/migration/preempt-resume) holds only
    for lossless pools: a lossy pool makes cache-on read ROUNDED prefix
    KV where cache-off attends the in-flight full-precision values.
    int8 is always lossy; a narrower float pool (f32 compute over bf16
    cache) is too.
    """
    kv = cfg.kv_cache_dtype
    if kv == "int8":
        return False
    return kv == cfg.dtype or (cfg.dtype, kv) in _KV_WIDENING


class ContinuousBatchingEngine:
    """Slot-based continuous batching over one shared KV cache.

    ``max_slots`` is the decode width (rows of the batched cache);
    ``max_seq`` bounds prompt + generation length per slot.  Requests
    arrive through ``submit``/``run``; each engine loop iteration
    admits arrived requests into free slots (one bucketed prefill each)
    and then advances every in-flight request by one token (one ragged
    decode across all slots, per-row cache positions).

    **Serve API v2.**  ``submit(prompt_or_request, ...)`` returns a
    ``RequestHandle``: iterate it (from another thread while ``run()``
    drains) or attach ``on_token`` to stream tokens as they are
    sampled; ``handle.result()`` / the dict ``run()`` returns carry
    ``RequestOutput`` (tokens, finish_reason stop|length|aborted, and
    queue-wait/TTFT/TPOT metrics).  ``abort(req_id)`` cancels a queued
    or in-flight request: its slot — and, under paging, its KV blocks —
    free the same loop iteration.  The v1 surface (``serve()``,
    ``scheduler.Request``) is gone: both raise with a pointer at the
    v2 replacement.  ``SamplingParams(logprobs=True)`` additionally
    surfaces each token's chosen-token logprob in
    ``RequestOutput.logprobs``.

    **In-graph sampling.**  Each request's ``SamplingParams``
    (temperature/top_k/top_p/seed; temperature 0.0 = greedy) ride the
    step batch as (B,) vectors and the jitted step returns sampled
    tokens — one static decode signature for any request mix (no
    per-request recompiles), and byte-identical seeded outputs across
    HOST/ACCEL backends, forced mid-stream migration, and
    preempt/resume (the per-token PRNG key is
    ``fold_in(PRNGKey(seed), absolute_position)``; resume replays
    stashed tokens, so only the KV is rebuilt).

    With ``paged=True`` the dense per-slot rows are replaced by a
    vLLM-style block pool (``block_size`` positions per block,
    ``num_blocks`` usable blocks — default: the dense engine's memory
    footprint).  Admission needs only the prompt's blocks (plus a
    one-block watermark), decode allocates blocks on demand, and the
    youngest slot is preempted-and-resumed if the pool runs dry — so
    concurrency is bounded by tokens actually in flight, not by
    ``max_slots x max_seq`` reservations.  ``lane_align`` (default:
    auto — on for native TPU, off in interpret mode) pads the pool's
    head_dim to the TPU lane width at allocation so the ACCEL paged
    kernel never copies the pool to pad it per call.

    **Prefix caching** (``prefix_cache=True``, paged only): admission
    matches the feed's full-block prefix against the pool's hash-chain
    index (``serve/batch.chain_hashes``); matched blocks are SHARED
    (refcounted) and only the uncached span is prefetched — a chunked
    ``prefill_ctx`` attends over the cached context and the engine
    scatters just the chunk's KV into freshly-allocated private blocks.
    Any write into a shared block forks it copy-on-write first; blocks
    whose last reference drops park in an evictable cached set (LRU)
    instead of freeing, so a later request with the same prefix revives
    them for free.  Greedy output is byte-identical cache-on vs
    cache-off on both backends (the cached KV is bitwise what a fresh
    prefill would recompute, and masked junk positions contribute exact
    zeros) — PROVIDED the pool dtype is lossless w.r.t. compute
    (``kv_cache_lossless``).  A lossy pool (int8, or f32 compute over a
    bf16 pool) raises at construction unless
    ``allow_lossy_prefix_cache=True`` explicitly opts into
    tolerance-level agreement (serve/README.md documents the int8
    tolerance story).

    **Chunked prefill** (``prefill_tokens_per_step``, paged only): a
    long prompt no longer stalls every in-flight decode for its whole
    prefill.  Admission claims the slot and all its blocks but computes
    nothing; each loop iteration then spends a token budget on
    partially-prefilled slots via the prefill-at-offset path
    (``prefill_ctx_sampled``), interleaved with decode steps.  The
    budget is policy-tunable per step: a policy exposing
    ``prefill_budget(signals, default)`` (e.g. ``LatencyAwarePolicy``)
    sees live ``LoadSignals`` and may return None to finish
    monolithically.  Only the FINAL chunk's in-graph sample (drawn at
    position = prompt length, exactly the monolithic draw) is kept, so
    greedy output is byte-identical chunked vs monolithic.  A slot
    preempted mid-prefill frees private blocks, keeps prefix-matched
    ones refcounted, and resumes from its completed-chunk offset.

    **Disaggregation** (``prefill_to_span`` / ``submit_span``): a
    prefill-role engine runs the chunk loop into scratch blocks and
    lifts the KV out as a ``KVSpan`` (pool dtype, serializable); a
    decode-role engine rehydrates it into local blocks and admits the
    request decode-ready — ``serve/cluster.py`` routes the spans over
    the scheduler control plane.

    **Speculative decoding** (``spec_decode=True``, paged only): each
    scheduler round a cheap DRAFT model — a layer-truncated share of
    the target's own weights (``serve/spec.py``) with a dense
    full-precision scratch cache — proposes up to ``spec_draft_len``
    tokens per slot in ONE fused chained dispatch, and the target
    verifies all of them in ONE multi-token prefill-at-offset step.
    The engine emits the longest drafted prefix matching verify's own
    samples plus verify's first divergent token; since every emitted
    token is verify's sample under the same ``fold_in(seed, position)``
    key sequential decode uses, GREEDY output stays byte-identical to
    non-speculative serving on every target, and seeded-sampled output
    is byte-identical across targets/migration/resume for a fixed spec
    configuration (see serve/spec.py for the ulp caveat vs
    non-speculative sampling).  Up to k tokens per 2 dispatches
    replaces k dispatches.  Under a runtime the draft chain
    and verify register as DISTINCT binaries (``{fn_prefix}_draft`` /
    ``{fn_prefix}_verify``) so the policy can hold draft-on-HOST /
    verify-on-ACCEL simultaneously and migrate either; a policy
    exposing ``draft_len(signals, default)`` (``LatencyAwarePolicy``)
    shrinks k under queue pressure, 0 disabling speculation for the
    step.  Fan-out blocks reserved for candidate positions stay on the
    slot when drafts are rejected (never freed mid-round — see
    ``PagedSlotManager.fanout_blocks``), so rollback cannot corrupt
    shared prefix-cached blocks.

    A request whose ``stop_tokens`` fires finishes that step: its slot —
    and, under paging, its blocks — frees immediately for queued
    arrivals instead of idling out the ``max_new_tokens`` budget.

    With a ``runtime``, every prefill/decode dispatches through
    ``XarTrekRuntime.call`` under the names ``{fn_prefix}_prefill`` /
    ``{fn_prefix}_decode`` so the scheduling policy picks the target per
    step; the engine registers DISTINCT builds per step via
    ``MultiTargetBinary``: HOST is the XLA reference math and ACCEL
    routes the same ABI through the Pallas kernels (flash prefill;
    flash-decoding / paged-streaming decode) — a migration is a real
    kernel swap, not a label change.  Both are compiled eagerly at
    ``prepare()`` (``eager_accel=True``, the default) so the first
    migration never pays compile time inside the timed region; pass
    ``eager_accel=False`` to keep the paper's asynchronous
    FPGA-pre-configuration behaviour instead.  Unless the caller
    pre-registered its own variants.

    **Placement is a ``SchedulingPolicy``** (``core/policy``): pass
    ``policy=`` a policy instance or alias string.  ``PinHost`` /
    ``PinAccel`` pin the direct (no-runtime) path to the XLA / Pallas
    build; every other policy (``XarTrekHeuristic``,
    ``LatencyAwarePolicy``, custom) needs a ``runtime`` — the engine
    installs the policy on the runtime's scheduler server.  Paged int8
    KV runs a real ACCEL build (the int8-dequantising paged kernel);
    only DENSE int8 still pins its ACCEL variant to XLA math.

    **Signals.**  Each loop iteration the engine publishes a
    ``LoadSignals`` snapshot (queue depth, active slots, free-KV
    fraction, per-target recent decode ms, TTFT/TPOT p50) to the
    scheduler server — the policy input is real telemetry, not the
    synthetic process counter (which remains one merged source).  In a
    multi-engine cluster (``serve/cluster.py``) the server aggregates
    snapshots across engines, so co-tenant pressure migrates this
    engine's steps.

    Deprecated escape hatches (warn once per process, absorbed by the
    policy API): ``backend="host"/"accel"`` maps to
    ``policy=PinHost()/PinAccel()``; ``on_step`` (fires with the engine
    after each decode step) is superseded by scripted policies that
    decide from ``LoadSignals`` / their own decision counters.

    Row-independent attention families only: ssm/hybrid caches cannot
    seek per-row, and moe routing couples rows through the shared
    expert-capacity budget.
    """

    def __init__(self, cfg: ModelConfig, max_slots: int = 8,
                 max_seq: int = 128,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 params=None, seed: int = 0,
                 runtime: Optional[XarTrekRuntime] = None,
                 fn_prefix: str = "cb", min_bucket: int = 8,
                 paged: bool = False, block_size: int = 32,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = False,
                 allow_lossy_prefix_cache: bool = False,
                 lane_align: Optional[bool] = None,
                 policy: Optional[SchedulingPolicy] = None,
                 backend: str = "auto", eager_accel: bool = True,
                 prefill_tokens_per_step: Optional[int] = None,
                 spec_decode: bool = False, spec_draft_len: int = 4,
                 spec_draft_layers: Optional[int] = None,
                 spec_draft_params=None, spec_draft_config=None,
                 on_step=None):
        global _BACKEND_DEPRECATION_WARNED, _ON_STEP_DEPRECATION_WARNED
        if cfg.family not in ("dense", "vlm"):
            # ssm/hybrid caches are position-synchronised; moe routing is
            # batch-coupled (capacity = f(batch tokens), so junk tokens
            # from inactive slots would steal expert capacity from real
            # requests and padded prefills would re-rank routing)
            raise NotImplementedError(
                f"continuous batching needs a per-row-seekable KV cache "
                f"and row-independent math; family {cfg.family!r} is not")
        if prefix_cache and not paged:
            raise ValueError("prefix_cache=True requires paged=True "
                             "(sharing happens at block granularity)")
        if prefix_cache and not allow_lossy_prefix_cache \
                and not kv_cache_lossless(cfg):
            raise ValueError(
                f"prefix_cache=True with lossy kv_cache_dtype="
                f"{cfg.kv_cache_dtype!r} (compute {cfg.dtype!r}) breaks "
                f"the byte-identity invariant: cache-on reads ROUNDED "
                f"prefix KV where cache-off attends full precision.  "
                f"Pass allow_lossy_prefix_cache=True to accept "
                f"tolerance-level (not bitwise) agreement — see "
                f"serve/README.md 'Prefix caching' for the int8 "
                f"tolerance story")
        if prefill_tokens_per_step is not None:
            if not paged:
                raise ValueError(
                    "prefill_tokens_per_step (chunked prefill) requires "
                    "paged=True: chunks scatter into pool blocks")
            if prefill_tokens_per_step < 1:
                raise ValueError("prefill_tokens_per_step must be >= 1")
        if spec_decode:
            if not paged:
                raise ValueError(
                    "spec_decode=True requires paged=True: the verify "
                    "step is a multi-token prefill-at-offset over the "
                    "paged pool, and the fan-out/rollback story lives "
                    "at block granularity")
            if spec_draft_len < 1:
                raise ValueError("spec_draft_len must be >= 1")
        if backend not in ("host", "accel", "auto"):
            raise ValueError(f"backend must be host|accel|auto: {backend!r}")
        if backend != "auto":
            if policy is not None:
                raise ValueError(
                    "pass either policy= or the deprecated backend=, "
                    "not both")
            if not _BACKEND_DEPRECATION_WARNED:
                _BACKEND_DEPRECATION_WARNED = True
                warnings.warn(
                    "ContinuousBatchingEngine(backend=...) is deprecated; "
                    "pass policy=PinHost()/PinAccel() (core.policy)",
                    DeprecationWarning, stacklevel=2)
            policy = PinHost() if backend == "host" else PinAccel()
        if on_step is not None and not _ON_STEP_DEPRECATION_WARNED:
            _ON_STEP_DEPRECATION_WARNED = True
            warnings.warn(
                "ContinuousBatchingEngine(on_step=...) is deprecated; "
                "use a scripted SchedulingPolicy (it sees LoadSignals "
                "every decision)", DeprecationWarning, stacklevel=2)
        self.cfg = cfg
        self.model = build_model(cfg, mesh)
        self.mesh = mesh
        self.runtime = runtime
        self.min_bucket = min_bucket
        self.paged = paged
        self.prefix_cache = prefix_cache
        self.policy = resolve_policy(policy) if policy is not None else None
        # chunked prefill: on when the engine knob is set, or when the
        # installed policy carries its own budget (LatencyAwarePolicy's
        # prefill_tokens_per_step field) — the policy's prefill_budget
        # hook then tunes the per-step budget from live LoadSignals
        self.prefill_tokens_per_step = prefill_tokens_per_step
        _pols = (self.policy,
                 getattr(getattr(runtime, "server", None), "policy", None))
        self._chunking = paged and (
            prefill_tokens_per_step is not None
            or any(getattr(p, "prefill_tokens_per_step", None) is not None
                   for p in _pols))
        if (self.policy is not None and runtime is None
                and not isinstance(self.policy, (PinHost, PinAccel))):
            raise ValueError(
                f"policy {getattr(self.policy, 'name', self.policy)!r} "
                f"decides per step and needs a runtime=; only "
                f"PinHost/PinAccel can drive the direct path")
        if runtime is not None and self.policy is not None:
            runtime.server.policy = self.policy
        self.on_step = on_step
        if params is None:
            params = self.model.init(jax.random.PRNGKey(seed))
        self.params = params
        # params committed to one device (a cluster replica on its own
        # chip) pin the engine there: its caches are allocated and
        # committed on that device and its AOT builds compile for it
        self.device = committed_device(params)
        self.queue = RequestQueue()
        if paged:
            # default pool = the dense engine's memory footprint
            # (max_slots full rows), but shared: short requests only take
            # the blocks they reach, so more of them fit concurrently
            self.block_size = block_size
            nb = num_blocks or max_slots * (-(-max_seq // block_size))
            self.slots: SlotManager = PagedSlotManager(
                max_slots, block_size, nb, max_seq=max_seq,
                prefix_cache=prefix_cache)
            self.cache = self._on_device(lambda: self.model.init_paged_cache(
                nb + 1, block_size, lane_align=lane_align))
            # scatter a prefill's KV blocks into the pool at the slot's
            # physical block ids (one fused donated update, like the
            # dense row write below); jit specializes per block count
            def scatter(pool, part, phys):
                out = {}
                for k in pool:
                    p = part[k][:, 0]               # (L, S_bucket, KV, hd)
                    tgt = phys.shape[0] * block_size
                    if p.shape[1] > tgt:            # bucket overhangs
                        p = p[:, :tgt]
                    elif p.shape[1] < tgt:          # junk tail: positions
                        p = jnp.pad(                # >= length are masked
                            p, ((0, 0), (0, tgt - p.shape[1])) +
                            ((0, 0),) * (p.ndim - 2))
                    if p.shape[-1] != pool[k].shape[-1]:
                        # lane-aligned pool: zero-pad the head_dim tail
                        p = jnp.pad(p, ((0, 0),) * (p.ndim - 1)
                                    + ((0, pool[k].shape[-1] - p.shape[-1]),))
                    p = p.reshape(p.shape[0], phys.shape[0], block_size,
                                  *p.shape[2:])
                    out[k] = pool[k].at[:, phys].set(p.astype(pool[k].dtype))
                return out
            self._scatter = jax.jit(scatter, donate_argnums=(0,))

            # prefix-cache helpers: a chunked scatter that can START
            # mid-block (the COW-forked tail keeps its cached prefix, the
            # re-fed token lands at start_off inside it) and a physical
            # block copy (the COW fork itself).  Positions >= n_real are
            # bucket junk, redirected to the reserved junk block 0.
            def scatter_chunk(pool, part, phys, start_off, n_real):
                out = {}
                for k in pool:
                    p = part[k][:, 0]           # (L, W_bucket, KV, hd)
                    w = p.shape[1]
                    intra = start_off + jnp.arange(w)
                    valid = jnp.arange(w) < n_real
                    blk = jnp.where(valid, phys[intra // block_size], 0)
                    off = jnp.where(valid, intra % block_size, 0)
                    if p.shape[-1] != pool[k].shape[-1]:
                        p = jnp.pad(p, ((0, 0),) * (p.ndim - 1)
                                    + ((0, pool[k].shape[-1]
                                        - p.shape[-1]),))
                    out[k] = pool[k].at[:, blk, off].set(
                        p.astype(pool[k].dtype))
                return out

            def copy_block(pool, dst, src):
                return {k: pool[k].at[:, dst].set(pool[k][:, src])
                        for k in pool}

            # disaggregated-span rehydration: ONE compile for any span
            # length.  The generic _scatter above specializes on
            # phys.shape — one executable (and one full donated pool
            # pass through the compiler) PER DISTINCT BLOCK COUNT, so a
            # decode-role engine admitting spans of many prompt lengths
            # recompiled the whole scatter for each.  Here the span KV
            # is padded host-side to the table-width worst case and the
            # pad rows are masked to the reserved junk block 0, so every
            # admission reuses the same donated executable.
            def scatter_span(pool, part, phys, n_blocks):
                out = {}
                valid = jnp.arange(phys.shape[0]) < n_blocks
                blk = jnp.where(valid, phys, 0)
                for k in pool:
                    p = part[k]        # (L, table_width, block_size, ...)
                    if p.shape[-1] != pool[k].shape[-1]:
                        p = jnp.pad(p, ((0, 0),) * (p.ndim - 1)
                                    + ((0, pool[k].shape[-1]
                                        - p.shape[-1]),))
                    out[k] = pool[k].at[:, blk].set(p.astype(pool[k].dtype))
                return out

            self._scatter_chunk = jax.jit(scatter_chunk, donate_argnums=(0,))
            self._copy_block = jax.jit(copy_block, donate_argnums=(0,))
            self._scatter_span = jax.jit(scatter_span, donate_argnums=(0,))
        else:
            self.slots = SlotManager(max_slots, max_seq)
            self.cache = self._on_device(
                lambda: self.model.init_cache(max_slots, max_seq))
        # direct-path (no-runtime) step functions honour the pinned
        # policy; no policy (or PinHost) serves on HOST math.  Both
        # steps sample IN-GRAPH and return tokens, not logits.
        direct = "pallas" if isinstance(self.policy, PinAccel) else "xla"
        self._direct_impl = direct
        self._prefill = jax.jit(
            lambda p, b: self.model.prefill_at_sampled(p, b, backend=direct))
        # donate the cache: without aliasing every token copies the full
        # (L, max_slots, max_seq, KV, hd) stack (see decode_attention)
        self._decode = jax.jit(
            lambda p, c, b: self.model.decode_sampled(p, c, b,
                                                      backend=direct),
            donate_argnums=(1,))
        self._needs_ctx = self.paged and (self.prefix_cache
                                          or self._chunking)
        if self.paged:
            # prefill-at-offset against the pool — the shared chunk path
            # of prefix-cache re-feed AND budgeted chunked prefill.  The
            # pool is NOT donated: matched blocks are shared, and the
            # chunk's KV is returned for an explicit scatter into the
            # slot's private blocks only.
            self._prefill_ctx = jax.jit(
                lambda p, c, b: self.model.prefill_ctx_sampled(
                    p, c, b, backend=direct))
        # one fused in-place write of a request's bucketed prefill KV into
        # its cache row (eager per-leaf updates would each materialize a
        # full copy of the whole batched cache)
        self._write_slot = jax.jit(
            lambda cache, part, row: {
                k: jax.lax.dynamic_update_slice(
                    cache[k], part[k].astype(cache[k].dtype),
                    (jnp.int32(0), row) + (jnp.int32(0),)
                    * (cache[k].ndim - 2))
                for k in cache},
            donate_argnums=(0,))
        # speculative decoding: the draft side is a layer-truncated
        # SHARE of the target (serve/spec.py) with its own dense
        # full-precision scratch cache, widened by draft_len so the
        # chain's last write never clamps at the row edge.  Draft chain
        # and verify are separate step functions — under a runtime they
        # register as DISTINCT migratable binaries ({prefix}_draft /
        # {prefix}_verify), so the policy can hold draft-on-HOST /
        # verify-on-ACCEL simultaneously and summary() accounts both.
        self.spec: Optional[spec_lib.SpecDecoder] = None
        if spec_decode:
            dcfg = (spec_draft_config if spec_draft_config is not None
                    else spec_lib.draft_model_config(cfg, spec_draft_layers))
            draft_model = build_model(dcfg, mesh)
            dparams = (spec_draft_params if spec_draft_params is not None
                       else spec_lib.share_draft_params(self.params,
                                                        dcfg.num_layers))
            # the row is widened by draft_len so the chain's last write
            # never clamps at the edge, then rounded up to whole
            # DRAFT_BLOCK-position blocks: the ACCEL draft decode views
            # the dense row as blocks of that size (a legal TPU tile)
            self._spec_width = -(-(max_seq + spec_draft_len)
                                 // DRAFT_BLOCK) * DRAFT_BLOCK
            dcache = self._on_device(lambda: draft_model.init_cache(
                max_slots, self._spec_width))
            self.spec = spec_lib.SpecDecoder(
                model=draft_model, cfg=dcfg, params=dparams, cache=dcache,
                draft_len=spec_draft_len)
            # content-addressed host->device cache for the small
            # per-round batch vectors that rarely change between rounds
            # (sampling leaves, block table, n_valid) — see _spec_put
            self._spec_dev_cache: dict = {}
            # lazy draft-row rehydration (fresh admission / resume /
            # fingerprint miss) always runs on the direct path: it is
            # rare and off the per-round dispatch cadence, so it is not
            # a migration surface
            self._draft_prefill = jax.jit(
                lambda p, b: draft_model.prefill_at_sampled(
                    p, b, backend=direct))
            self._draft_chain = jax.jit(
                lambda p, c, b: draft_model.decode_draft(
                    p, c, b, backend=direct, max_steps=spec_draft_len),
                donate_argnums=(1,))
            # verify consumes the chain's DEVICE-resident drafts and
            # assembles its token/offset/length batch in-graph
            # (spec_verify_batch), so a round has exactly one host sync
            # — after verify — instead of one per dispatch
            self._verify = jax.jit(
                lambda p, c, b: self.model.decode_verify(
                    p, c, spec_verify_batch(b), backend=direct),
                donate_argnums=(1,))
        self._prefill_name = f"{fn_prefix}_prefill"
        self._prefill_ctx_name = f"{fn_prefix}_prefill_ctx"
        self._decode_name = f"{fn_prefix}_decode"
        self._draft_name = f"{fn_prefix}_draft"
        self._verify_name = f"{fn_prefix}_verify"
        self.engine_id = fn_prefix
        self.results: dict[int, RequestOutput] = {}
        # req_id -> (tokens, logprobs) generated before preemption
        self._resume: dict[int, tuple[list[int], list[float]]] = {}
        # req_id -> KVSpan handed off by a prefill-role engine
        # (disaggregation); admission rehydrates instead of prefilling
        self._spans: dict[int, "KVSpan"] = {}
        self._step_budget: Optional[int] = None
        self._handles: dict[int, RequestHandle] = {}
        # fired from _finalize with (handle, RequestOutput) the moment a
        # request finishes (any reason, including aborts) — the process
        # worker's result-plane emitter; None = no observer
        self.on_finish = None
        self._abort_pending: set[int] = set()
        self._abort_lock = threading.Lock()
        self._clock0: Optional[float] = None
        # serve telemetry for LoadSignals: per-target EWMA of the direct
        # path's decode step ms (runtime-dispatched steps read the
        # binary's compile_stats instead) + a window of recent finished
        # requests' latency metrics
        self._direct_step_ms: dict[str, Optional[float]] = {
            "host": None, "accel": None}
        # EWMA of per-iteration decode stall (ms spent on chunk prefills
        # while decode-ready slots waited) — the feedback signal
        # LatencyAwarePolicy.prefill_budget contracts on.  Steps with no
        # chunk work blend in 0.0 so a past burst decays instead of
        # pinning the budget down forever.
        self._stall_ewma: Optional[float] = None
        self._latency_window: collections.deque = collections.deque(
            maxlen=64)
        self.reset_stats()
        if runtime is not None:
            self._prepare_runtime(runtime, fn_prefix, eager_accel)

    def _on_device(self, make):
        """Build an array tree (``make()``) on the engine's device and
        commit it there, so every step that touches it runs there too."""
        if self.device is None:
            return make()
        with jax.default_device(self.device):
            return jax.device_put(make(), self.device)

    def reset_stats(self) -> None:
        """Zero the per-serve counters (benchmarks call this after their
        warm-up pass so warm-up steps don't pollute measured stats).
        ``prefill_tokens`` counts tokens actually COMPUTED by prefill
        (real feed positions, not bucket padding); ``prefix_hit_tokens``
        counts prompt positions served from the prefix cache instead —
        their ratio is the cache hit rate.  ``prefill_chunks`` counts
        prefill-at-offset calls, ``chunk_hist`` their bucketed widths,
        ``decode_stall_ms`` wall time spent on chunk prefills while
        decode-ready slots waited, and ``spans_admitted`` requests
        rehydrated from a disaggregated KV handoff."""
        self.stats = {"prefills": 0, "decode_steps": 0,
                      "decode_row_util": 0.0,
                      "prefill_tokens": 0, "prefix_hit_tokens": 0,
                      "prefill_chunks": 0, "decode_stall_ms": 0.0,
                      "decode_stall_max_ms": 0.0,
                      "chunk_hist": {}, "spans_admitted": 0,
                      "spec_rounds": 0, "spec_proposed_tokens": 0,
                      "spec_accepted_tokens": 0, "spec_emitted_tokens": 0}

    def spec_stats(self) -> dict:
        """Speculative-decoding effectiveness counters (zeros when spec
        decode is off).  ``spec_proposed_tokens`` counts DRAFTED tokens
        actually put to the verifier (n_valid - 1 per row per round),
        ``spec_accepted_tokens`` how many of those verify confirmed —
        their ratio is the acceptance rate.  ``spec_emitted_tokens``
        counts tokens emitted by spec rounds (accepted + the one
        verify-sampled token each row always yields, truncated at
        stop tokens), so emitted/rounds is tokens-per-dispatch-pair."""
        s = self.stats
        return {"spec_rounds": s["spec_rounds"],
                "spec_proposed_tokens": s["spec_proposed_tokens"],
                "spec_accepted_tokens": s["spec_accepted_tokens"],
                "spec_emitted_tokens": s["spec_emitted_tokens"],
                "spec_acceptance_rate": (s["spec_accepted_tokens"]
                                         / max(s["spec_proposed_tokens"],
                                               1))}

    def prefix_stats(self) -> dict:
        """Prefix-cache effectiveness counters (zeros when caching is
        off): token hit rate plus the pool/manager sharing counters."""
        computed = self.stats["prefill_tokens"]
        hit = self.stats["prefix_hit_tokens"]
        out = {"prefill_tokens": computed, "prefix_hit_tokens": hit,
               "prefix_hit_rate": hit / max(hit + computed, 1),
               "prefill_chunks": self.stats["prefill_chunks"],
               "decode_stall_ms": self.stats["decode_stall_ms"],
               "decode_stall_max_ms": self.stats["decode_stall_max_ms"],
               "chunk_hist": dict(self.stats["chunk_hist"]),
               "spans_admitted": self.stats["spans_admitted"]}
        if self.paged:
            pool = self.slots.pool
            out.update(cow_forks=self.slots._stats["cow_forks"],
                       prefix_block_hits=self.slots._stats[
                           "prefix_block_hits"],
                       cache_hits=pool.stats["cache_hits"],
                       evicted=pool.stats["evicted"],
                       cached_blocks=pool.cached_blocks())
        return out

    def _now(self) -> float:
        """Engine-loop clock (seconds since the current run() started)."""
        if self._clock0 is None:
            return 0.0
        return time.perf_counter() - self._clock0

    # ---------------------------------------------------------- telemetry
    def signals(self) -> LoadSignals:
        """This engine's serve-telemetry snapshot — the real policy
        input that replaced the synthetic process counter: queue depth,
        in-flight rows, free KV capacity (block pool under paging, rows
        otherwise), per-target recent decode step ms (EWMA from the
        runtime binary's ``compile_stats``, or the direct path's own
        timer) and TTFT/TPOT p50 over recently finished requests."""
        if self.paged:
            free = (self.slots.pool.free_blocks()
                    / max(self.slots.pool.num_blocks, 1))
        else:
            cap = self.slots.max_slots
            free = (cap - len(self.slots.active)) / max(cap, 1)
        host_ms = self._direct_step_ms["host"]
        accel_ms = self._direct_step_ms["accel"]
        if self.runtime is not None:
            binary = self.runtime.binaries.get(self._decode_name)
            if binary is not None:
                cs = binary.compile_stats
                host_ms = cs.get(TargetKind.HOST, {}).get(
                    "recent_exec_ms", host_ms)
                accel_ms = cs.get(TargetKind.ACCEL, {}).get(
                    "recent_exec_ms", accel_ms)
        ttft = sorted(t for t, _ in self._latency_window)
        tpot = sorted(t for _, t in self._latency_window)
        # arrived_len, not len(): the heap also holds scheduled-but-
        # future arrivals (pre-submitted Poisson streams), which are not
        # load yet — counting them inflated x86_load and tripped
        # queue_depth_hi thresholds before any request existed
        return LoadSignals(
            queue_depth=self.queue.arrived_len(self._now()),
            active_slots=len(self.slots.active),
            free_kv_frac=free,
            host_decode_ms=host_ms,
            accel_decode_ms=accel_ms,
            ttft_p50_s=ttft[len(ttft) // 2] if ttft else None,
            tpot_p50_s=tpot[len(tpot) // 2] if tpot else None,
            decode_stall_ms=self._stall_ewma,
        )

    def _publish_signals(self) -> None:
        """Feed the snapshot to the scheduler (each loop iteration):
        with a shared/central server this is how one engine's pressure
        reaches every co-tenant's placement decision."""
        if self.runtime is not None:
            self.runtime.publish_signals(self.engine_id, self.signals())

    # ------------------------------------------------- runtime plumbing
    def _prepare_runtime(self, rt: XarTrekRuntime, fn_prefix: str,
                         eager_accel: bool) -> None:
        def step_fns(impl: str):
            def prefill_fn(params, batch):
                return self.model.prefill_at_sampled(params, batch,
                                                     backend=impl)

            def decode_fn(params, cache, batch):
                return self.model.decode_sampled(params, cache, batch,
                                                 backend=impl)

            return prefill_fn, decode_fn

        # HOST keeps the XLA reference; ACCEL is a genuinely different
        # build on the Pallas kernels (same ABI, checked at prepare) —
        # except DENSE int8 caches (the dequantising kernel is paged-
        # only) and PinHost, which pins both variants to XLA.  Paged
        # int8 gets the real kernel: blocks + scale planes streamed
        # through the block table, dequantised in VMEM.
        accel_impl = ("pallas" if (not isinstance(self.policy, PinHost)
                                   and (self.cfg.kv_cache_dtype != "int8"
                                        or self.paged))
                      else "xla")
        host_prefill, host_decode = step_fns("xla")
        if accel_impl == "pallas":
            accel_prefill, accel_decode = step_fns(accel_impl)
        else:
            # identical math: reuse the HOST functions and keep the
            # ACCEL pre-configuration asynchronous — a blocking eager
            # compile would buy zero kernel asymmetry
            accel_prefill, accel_decode = host_prefill, host_decode
            eager_accel = False
        # one app (= one threshold row) per step function, so Algorithm 1
        # doesn't mix prefill and decode timings in one row
        for name, host_fn, accel_fn in (
                (self._prefill_name, host_prefill, accel_prefill),
                (self._decode_name, host_decode, accel_decode)):
            if name not in rt.registry:
                rt.registry.register(MigratableFunction(
                    name, name,
                    {TargetKind.HOST: host_fn, TargetKind.ACCEL: accel_fn}))
        greedy = SamplingParams()
        ex_prefill = (self.params,
                      {"tokens": jnp.zeros((1, self.min_bucket), jnp.int32),
                       "length": jnp.ones((1,), jnp.int32),
                       **sampling_leaves(greedy, 1)})
        dec_batch = {"tokens": jnp.zeros((self.slots.max_slots, 1),
                                         jnp.int32),
                     "index": jnp.zeros((self.slots.max_slots,), jnp.int32),
                     **sampling_leaves(greedy, self.slots.max_slots)}
        if self.paged:
            # paged decode keys its compile on the block-table shape too;
            # steady state is one static signature (see binary.shape_key)
            dec_batch["block_table"] = jnp.zeros(
                (self.slots.max_slots, self.slots.table_width), jnp.int32)
        ex_decode = (self.params, self.cache, dec_batch)
        rt.prepare(self._prefill_name, *ex_prefill, eager_accel=eager_accel)
        rt.prepare(self._decode_name, *ex_decode, donate_argnums=(1,),
                   eager_accel=eager_accel)
        if self._needs_ctx:
            # prefill-at-offset (chunked prefill / prefix-cache re-feed):
            # HOST is the XLA gather reference, ACCEL the paged_gqa_prefill
            # Pallas kernel (chunk flash self-attention fused with the
            # masked [0, offset) pool read) — the same genuine kernel
            # asymmetry as decode, including the int8-dequantising paged
            # variant.
            def ctx_fn(impl):
                def fn(params, cache, batch):
                    return self.model.prefill_ctx_sampled(params, cache,
                                                          batch, backend=impl)
                return fn

            host_ctx = ctx_fn("xla")
            accel_ctx = ctx_fn("pallas") if accel_impl == "pallas" \
                else host_ctx
            if self._prefill_ctx_name not in rt.registry:
                rt.registry.register(MigratableFunction(
                    self._prefill_ctx_name, self._prefill_ctx_name,
                    {TargetKind.HOST: host_ctx,
                     TargetKind.ACCEL: accel_ctx}))
            ex_ctx = (self.params, self.cache,
                      {"tokens": jnp.zeros((1, self.min_bucket), jnp.int32),
                       "offset": jnp.zeros((1,), jnp.int32),
                       "length": jnp.ones((1,), jnp.int32),
                       "block_table": jnp.zeros(
                           (1, self.slots.table_width), jnp.int32),
                       **sampling_leaves(greedy, 1)})
            rt.prepare(self._prefill_ctx_name, *ex_ctx,
                       eager_accel=eager_accel)
        if self.spec is not None:
            # speculative decoding registers TWO MORE distinct binaries:
            # the fused k-step draft chain and the multi-token verify.
            # Each gets its own threshold row / call counters, so the
            # headline draft-on-HOST / verify-on-ACCEL split is a real
            # per-function placement the scheduler (and summary()) sees —
            # and migrating either is a kernel swap like any other step.
            draft_model, W = self.spec.model, self.spec.draft_len

            def draft_fn(impl):
                def fn(params, cache, batch):
                    return draft_model.decode_draft(params, cache, batch,
                                                    backend=impl,
                                                    max_steps=W)
                return fn

            def verify_fn(impl):
                def fn(params, cache, batch):
                    return self.model.decode_verify(
                        params, cache, spec_verify_batch(batch),
                        backend=impl)
                return fn

            host_draft = draft_fn("xla")
            # the draft cache is always full-precision dense (see
            # spec.draft_model_config), so its ACCEL build is the real
            # Pallas flash-decode even when the TARGET pool is int8
            accel_draft = (draft_fn("pallas")
                           if not isinstance(self.policy, PinHost)
                           else host_draft)
            host_verify = verify_fn("xla")
            accel_verify = (verify_fn("pallas") if accel_impl == "pallas"
                            else host_verify)
            for name, host_fn, accel_fn in (
                    (self._draft_name, host_draft, accel_draft),
                    (self._verify_name, host_verify, accel_verify)):
                if name not in rt.registry:
                    rt.registry.register(MigratableFunction(
                        name, name, {TargetKind.HOST: host_fn,
                                     TargetKind.ACCEL: accel_fn}))
            B = self.slots.max_slots
            ex_draft = (self.spec.params, self.spec.cache,
                        {"tokens": jnp.zeros((B, 1), jnp.int32),
                         "index": jnp.zeros((B,), jnp.int32),
                         "n_steps": jnp.int32(1),
                         **sampling_leaves(greedy, B)})
            ex_verify = (self.params, self.cache,
                         {"drafts": jnp.zeros((B, W), jnp.int32),
                          "last": jnp.zeros((B, 1), jnp.int32),
                          "pos": jnp.zeros((B,), jnp.int32),
                          "n_valid": jnp.zeros((B,), jnp.int32),
                          "block_table": jnp.zeros(
                              (B, self.slots.table_width), jnp.int32),
                          **sampling_leaves(greedy, B)})
            rt.prepare(self._draft_name, *ex_draft, donate_argnums=(1,),
                       eager_accel=eager_accel)
            rt.prepare(self._verify_name, *ex_verify, donate_argnums=(1,),
                       eager_accel=eager_accel)

    # -------------------------------------------------------- admission
    def submit(self, request, max_new_tokens: int = 16,
               arrival_s: float = 0.0, stop_tokens: tuple = (),
               sampling: Optional[SamplingParams] = None,
               on_token=None) -> RequestHandle:
        """Enqueue one request; returns its streaming ``RequestHandle``.

        ``request`` is a ``GenerationRequest`` (the remaining kwargs are
        ignored then) or a bare prompt array, in which case EVERY
        request field routes through — max_new_tokens, arrival time,
        stop_tokens AND the sampling spec (the v1 engine silently
        dropped ``stop_tokens`` here).  Validates at submission, not
        mid-serve: a request that cannot fit a cache row would otherwise
        fail only once a slot frees."""
        if not isinstance(request, GenerationRequest):
            request = GenerationRequest(
                np.asarray(request), max_new_tokens=max_new_tokens,
                arrival_s=arrival_s, stop_tokens=stop_tokens,
                sampling=sampling or SamplingParams())
        self.queue.submit(self.slots.validate(request))
        return self._handle_for(request, on_token=on_token)

    def submit_resume(self, request: GenerationRequest,
                      tokens=(), logprobs=None,
                      on_token=None) -> RequestHandle:
        """Enqueue a request that already generated ``tokens`` somewhere
        else (a worker that died mid-stream): admission re-prefills
        prompt + tokens[:-1] and replays the stash, exactly the
        preempt/resume path, so the continuation is byte-identical to
        never having moved — sampling keys depend only on
        (seed, position).  The replayed tokens re-emit through the
        handle; cross-process consumers dedup on absolute index.
        Empty ``tokens`` degrades to a plain ``submit``."""
        tokens = [int(t) for t in tokens]
        lps = [float(x) for x in (logprobs if logprobs is not None else ())]
        if tokens and len(lps) != len(tokens):
            lps = [0.0] * len(tokens)
        self.queue.submit(self.slots.validate(request))
        if tokens:
            self._resume[request.req_id] = (tokens, lps)
        return self._handle_for(request, on_token=on_token)

    def _handle_for(self, req: GenerationRequest,
                    on_token=None) -> RequestHandle:
        h = self._handles.get(req.req_id)
        if h is None:
            h = self._handles[req.req_id] = RequestHandle(
                req, engine=self, on_token=on_token)
        elif on_token is not None:
            h.on_token = on_token
        return h

    def abort(self, req_id: int) -> bool:
        """Cancel a queued or in-flight request.  Its handle finishes
        with ``finish_reason="aborted"`` and whatever tokens were
        generated; an in-flight slot — and, under paging, its KV blocks
        — frees at the next loop iteration.  Returns False if the
        request is unknown or already finished.

        Thread-safe: the caller only MARKS the abort; all engine state
        (queue, slots, results) is touched by the loop thread in
        ``_service_aborts`` — except when no loop is running, in which
        case the abort is serviced inline."""
        handle = self._handles.get(req_id)
        if handle is None or handle.finished or req_id in self.results:
            return False
        with self._abort_lock:
            self._abort_pending.add(req_id)
        if self._clock0 is None:       # no loop running: service inline
            self._service_aborts(self._now())
        return True

    def _service_aborts(self, now: float) -> None:
        """Loop-thread half of ``abort``: finish aborted requests
        wherever they currently live — an active slot (release it;
        paged: frees its blocks), the queue (remove it, covering both
        never-admitted and preempted-awaiting-resume requests), or
        already finished (drop the mark).  A request caught mid-admission
        stays pending and is serviced next iteration."""
        with self._abort_lock:
            pending = set(self._abort_pending)
        for req_id in pending:
            done = False
            for slot in list(self.slots.active.values()):
                if slot.request.req_id == req_id:
                    self._sync_handle(slot, now)
                    self._finalize(self._handle_for(slot.request),
                                   FINISH_ABORTED, now)
                    self.slots.release(slot)   # paged: frees blocks too
                    done = True
                    break
            if not done:
                req = self.queue.remove(req_id)
                if req is not None:
                    self._resume.pop(req_id, None)
                    self._spans.pop(req_id, None)
                    self._finalize(self._handle_for(req), FINISH_ABORTED,
                                   now)
                    done = True
            if done or req_id in self.results:
                with self._abort_lock:
                    self._abort_pending.discard(req_id)

    def _can_admit(self, req: GenerationRequest) -> bool:
        """Admission capacity beyond a free row: the paged pool must hold
        the prefill's blocks plus a growth watermark (block-exhaustion
        backpressure replaces the dense engine's slot-count-only gate)."""
        if not self.paged:
            return True
        if req.req_id in self._spans:
            # handed-off KV rehydrates into exactly the prompt's blocks
            return self.slots.can_admit(req.prompt_len, req)
        resume = self._resume.get(req.req_id)
        plen = req.prompt_len + (len(resume[0]) - 1 if resume else 0)
        if self.prefix_cache:
            # admission must see the actual feed: cached blocks cost
            # nothing, so only the uncached span (+ the COW fork when
            # fully cached) gates admission
            feed = req.prompt if resume is None else np.concatenate(
                [req.prompt, np.asarray(resume[0][:-1], np.int32)])
            return self.slots.can_admit(plen, req, feed=feed)
        return self.slots.can_admit(plen, req)

    def _admit(self, req: GenerationRequest, now: float = 0.0) -> None:
        # resume of a preempted request: the cache must again hold
        # prompt + generated-so-far, so re-prefill over both; the replayed
        # tokens were already sampled (and streamed), so the recomputation
        # is bit-compatible with the original KV regardless of the
        # request's sampling spec (same math, same weights, same tokens)
        span = self._spans.pop(req.req_id, None)
        if span is not None:
            with TraceAnnotation("engine.admit", req=req.req_id, feed=0):
                self._admit_span(req, span, now)
            return
        resume = self._resume.pop(req.req_id, None)
        if resume is None:
            feed = req.prompt
        else:
            feed = np.concatenate(
                [req.prompt, np.asarray(resume[0][:-1], np.int32)])
        with TraceAnnotation("engine.admit", req=req.req_id, feed=len(feed)):
            self._admit_feed(req, feed, resume, now)

    def _admit_feed(self, req: GenerationRequest, feed: np.ndarray, resume,
                    now: float) -> None:
        """Admit ``req`` by prefilling ``feed`` (its prompt, plus the
        replayed tokens of a preempted request being resumed)."""
        S = len(feed)
        if self.paged and self._step_budget is not None:
            # chunked prefill: admit the slot with its blocks but NO
            # model call — _advance_prefills spends the per-step budget
            # on it between decode steps.  Short feeds (net of any
            # cached prefix) stay monolithic: one call is cheaper than
            # the chunk machinery.
            cached = (self.slots.matchable_blocks(feed) * self.block_size
                      if self.prefix_cache else 0)
            if S - min(cached, S - 1) > self._step_budget:
                try:
                    slot = self._admit_chunked(req, feed, S, resume)
                except RuntimeError:
                    if resume is not None:
                        self._resume[req.req_id] = resume
                    raise
                self._post_admit(slot, req, now)
                return
        if self.paged and self.prefix_cache:
            try:
                slot = self._admit_cached(req, feed, S, resume)
            except RuntimeError:         # pool raced dry: undo the pop
                if resume is not None:
                    self._resume[req.req_id] = resume
                raise
            self._post_admit(slot, req, now)
            return
        Sb = prompt_bucket(S, self.min_bucket)
        toks = np.zeros((1, Sb), np.int32)
        toks[0, :S] = feed
        batch = {"tokens": jnp.asarray(toks),
                 "length": jnp.full((1,), S, jnp.int32),
                 **sampling_leaves(req.sampling, 1)}
        if self.runtime is not None:
            tok0, lp0, pc = self.runtime.call(self._prefill_name,
                                              self.params, batch)
        else:
            tok0, lp0, pc = self._prefill(self.params, batch)
        self.stats["prefills"] += 1
        self.stats["prefill_tokens"] += S
        if resume is None:
            # first token sampled IN-GRAPH at position = prompt length
            first, tokens, logprobs = int(np.asarray(tok0)[0]), None, None
            first_lp = float(np.asarray(lp0)[0])
        else:
            # the pending token was already sampled before preemption;
            # the resume prefill only rebuilds the KV (its token unused,
            # and the stashed logprobs replay alongside the tokens)
            first, (tokens, logprobs) = resume[0][-1], resume
            first_lp = 0.0                       # overridden by logprobs
        if self.paged:
            blocks = self.slots.pool.alloc(self.slots.blocks_for(S))
            slot = self.slots.admit(req, first, blocks=blocks,
                                    tokens=tokens, logprobs=logprobs,
                                    first_logprob=first_lp, pos=S)
            # scatter the bucketed prefill KV (leaves (L,1,S_bucket,KV,hd),
            # seq axis 2) into the slot's physical blocks; the tail of the
            # last block carries junk KV, which write-then-attend decode
            # always overwrites before reading (see batch.py docstring)
            self.cache = self._scatter(self.cache, pc,
                                       jnp.asarray(blocks, jnp.int32))
        else:
            slot = self.slots.admit(req, first, tokens=tokens,
                                    logprobs=logprobs,
                                    first_logprob=first_lp, pos=S)
            # write the request's bucketed KV into its cache row (leaves
            # are (L, 1, S_bucket, KV, hd|1); seq is axis 2).  Positions
            # [S, S_bucket) carry pad KV, overwritten before any read
            if Sb > self.slots.max_seq:    # bucket overhangs the row
                pc = {k: jax.lax.slice_in_dim(pc[k], 0, self.slots.max_seq,
                                              axis=2) for k in pc}
            self.cache = self._write_slot(self.cache, pc,
                                          jnp.int32(slot.index))
        self._post_admit(slot, req, now)

    def _post_admit(self, slot: Slot, req: GenerationRequest,
                    now: float) -> None:
        slot.t_admit = now
        handle = self._handle_for(req)
        if handle.t_admit is None:     # first admission only (not resume)
            handle.t_admit = now
        # the first token was just forced out of the prefill: timestamp it
        # AFTER the prefill so TTFT includes prefill latency
        t_tok = self._now()
        slot.t_last_token = t_tok
        self._sync_handle(slot, t_tok)
        if slot.done:            # max_new_tokens reached or stop token
            self._finish(slot, t_tok)

    def _admit_cached(self, req: GenerationRequest, feed: np.ndarray,
                      S: int, resume) -> Slot:
        """Admission with prefix caching: match the feed's full-block
        prefix against the pool's hash index, allocate only the uncached
        span's blocks, and prefill the CHUNK ``feed[offset:]`` against
        the cached context.

        When the whole feed is cached (C >= S, a block-aligned repeat),
        the last feed token is re-fed as a one-token chunk at offset
        S - 1: its logits reproduce the uncached first sample, and its
        KV write targets the last MATCHED block — which ``ensure_writable``
        forks copy-on-write first, so sharers are untouched.  Otherwise
        the chunk starts at the block-aligned offset C and writes land
        only in freshly-allocated private blocks."""
        bs = self.slots.block_size
        # match BEFORE alloc: references on matched blocks keep the LRU
        # eviction inside alloc() from reclaiming them
        matched, hashes = self.slots.match_prefix(feed)
        C = len(matched) * bs
        offset = C if C < S else S - 1
        n_chunk = S - offset
        n_total = self.slots.blocks_for(S)
        try:
            fresh = (self.slots.pool.alloc(n_total - len(matched))
                     if n_total > len(matched) else [])
        except RuntimeError:
            # pool raced dry between can_admit and here: hand back the
            # matched references before re-raising
            self.slots.pool.free(matched)
            raise
        blocks = matched + fresh
        tail = offset // bs
        copy = None
        if tail < len(matched):          # chunk writes into a matched block
            try:
                blocks, copy = self.slots.ensure_writable(blocks, tail)
            except RuntimeError:         # no block left for the COW fork
                self.slots.pool.free(matched + fresh)
                raise
            hashes = hashes[:tail]
        if copy is not None:
            src, dst = copy
            self.cache = self._copy_block(self.cache, jnp.int32(dst),
                                          jnp.int32(src))
        self.stats["prefix_hit_tokens"] += offset
        tok0, lp0 = self._ctx_chunk(feed, offset, n_chunk, blocks,
                                    req.sampling)
        self.stats["prefills"] += 1
        if resume is None:
            first, tokens, logprobs = int(np.asarray(tok0)[0]), None, None
            first_lp = float(np.asarray(lp0)[0])
        else:
            first, (tokens, logprobs) = resume[0][-1], resume
            first_lp = 0.0
        slot = self.slots.admit(req, first, blocks=blocks, tokens=tokens,
                                logprobs=logprobs, first_logprob=first_lp,
                                pos=S)
        slot.block_hashes = hashes
        self.slots.register_full_blocks(slot, feed)
        return slot

    def _ctx_chunk(self, feed, offset: int, n_chunk: int,
                   blocks: list[int], sampling) -> tuple:
        """One prefill-at-offset call — the SINGLE chunk path shared by
        prefix-cache re-feed, budgeted chunked prefill, and the
        disaggregated prefill-to-span loop, so every chunk width routes
        through the same ``prompt_bucket`` policy and the compile
        signatures coincide.

        Computes KV for ``feed[offset:offset + n_chunk]`` attending the
        pool context ``[0, offset)`` through ``blocks`` and scatters it
        into the blocks covering those positions (physical ids padded
        with junk block 0 to a static per-bucket width).  Returns the
        in-graph sample ``(token, logprob)`` drawn at position
        ``offset + n_chunk`` — meaningful only for the FINAL chunk,
        where it equals the monolithic prefill's first draw."""
        bs = self.slots.block_size
        Cb = prompt_bucket(n_chunk, self.min_bucket)
        with TraceAnnotation("engine.prefill_chunk", offset=offset,
                             width=n_chunk, bucket=Cb):
            toks = np.zeros((1, Cb), np.int32)
            toks[0, :n_chunk] = feed[offset:offset + n_chunk]
            table = np.zeros((1, self.slots.table_width), np.int32)
            table[0, :len(blocks)] = blocks
            batch = {"tokens": jnp.asarray(toks),
                     "offset": jnp.full((1,), offset, jnp.int32),
                     "length": jnp.full((1,), offset + n_chunk, jnp.int32),
                     "block_table": jnp.asarray(table),
                     **sampling_leaves(sampling, 1)}
            if (self.runtime is not None
                    and self._prefill_ctx_name in self.runtime.registry):
                tok0, lp0, pc = self.runtime.call(
                    self._prefill_ctx_name, self.params, self.cache, batch)
            else:
                # no migratable build registered (e.g. prefill_to_span on
                # an engine prepared without prefix cache or chunking):
                # the direct jit serves the chunk on the engine's backend
                tok0, lp0, pc = self._prefill_ctx(self.params, self.cache,
                                                  batch)
            nphys = (Cb + 2 * bs - 2) // bs
            span = blocks[offset // bs:][:nphys]
            phys = np.zeros((nphys,), np.int32)
            phys[:len(span)] = span
            self.cache = self._scatter_chunk(self.cache, pc,
                                             jnp.asarray(phys),
                                             jnp.int32(offset % bs),
                                             jnp.int32(n_chunk))
            self.stats["prefill_tokens"] += n_chunk
            self.stats["prefill_chunks"] += 1
            hist = self.stats["chunk_hist"]
            hist[Cb] = hist.get(Cb, 0) + 1
        return tok0, lp0

    # ------------------------------------------------- chunked prefill
    def _prefill_budget(self) -> Optional[int]:
        """Prompt tokens the chunk path may compute this step: the
        policy's ``prefill_budget`` hook (fed live signals) when it has
        one, else the engine's static knob.  None = monolithic."""
        policy = self.policy
        if policy is None and self.runtime is not None:
            policy = self.runtime.server.policy
        hook = getattr(policy, "prefill_budget", None)
        if hook is not None:
            b = hook(self.signals(), self.prefill_tokens_per_step)
        else:
            b = self.prefill_tokens_per_step
        return None if b is None else max(int(b), 1)

    def _admit_chunked(self, req: GenerationRequest, feed: np.ndarray,
                       S: int, resume) -> Slot:
        """Admit a long feed WITHOUT prefilling it: claim any cached
        prefix (shared, refcounted), allocate every remaining block up
        front (the chunk loop then never races the pool), and mark the
        slot partially prefilled at the block-aligned cached offset.
        ``_advance_prefills`` computes the rest under the budget."""
        bs = self.slots.block_size
        matched, hashes = self.slots.match_prefix(feed)   # [] if cache off
        offset = len(matched) * bs
        n_total = self.slots.blocks_for(S)
        try:
            fresh = (self.slots.pool.alloc(n_total - len(matched))
                     if n_total > len(matched) else [])
        except RuntimeError:
            self.slots.pool.free(matched)
            raise
        self.stats["prefix_hit_tokens"] += offset
        slot = self.slots.admit(req, 0, blocks=matched + fresh, tokens=[],
                                logprobs=[], pos=offset)
        slot.block_hashes = hashes
        slot.prefill = PartialPrefill(feed=np.asarray(feed, np.int32),
                                      resume=resume)
        return slot

    def _advance_prefills(self, budget: Optional[int]) -> None:
        """Spend this step's chunk budget on partially-prefilled slots,
        oldest first (a None budget — chunking disabled for the step —
        finishes each in one chunk).  Time spent here while decode-ready
        slots sit waiting is the decode stall the budget knob bounds."""
        pending = self.slots.prefilling_slots()
        if not pending:
            # no chunk work: decay the stall signal toward zero so the
            # policy's stall-feedback contraction releases once the
            # prefill burst that caused it has drained
            self._stall_ewma = ewma(self._stall_ewma, 0.0)
            return
        t0 = time.perf_counter()
        stalled = bool(self.slots.active_slots())
        remaining = float("inf") if budget is None else budget
        for slot in pending:
            if remaining < 1:
                break
            w = int(min(remaining, len(slot.prefill.feed) - slot.pos))
            self._prefill_chunk(slot, w)
            remaining -= w
        if stalled:
            ms = (time.perf_counter() - t0) * 1e3
            self.stats["decode_stall_ms"] += ms
            # worst single-step stall: the SLO number the budget bounds
            self.stats["decode_stall_max_ms"] = max(
                self.stats["decode_stall_max_ms"], ms)
            self._stall_ewma = ewma(self._stall_ewma, ms)
        else:
            self._stall_ewma = ewma(self._stall_ewma, 0.0)

    def _prefill_chunk(self, slot: Slot, n_chunk: int) -> None:
        """Advance one slot's prefill by ``n_chunk`` feed tokens.  Full
        blocks register in the prefix index as they fill, so a
        preemption right after this keeps them warm (cached set) and
        resume restarts from the completed offset.  The final chunk's
        in-graph sample is the request's first token — intermediate
        chunks' draws are discarded (their position is not S)."""
        feed = slot.prefill.feed
        offset, S = slot.pos, len(feed)
        tok0, lp0 = self._ctx_chunk(feed, offset, n_chunk, slot.blocks,
                                    slot.request.sampling)
        slot.pos = offset + n_chunk
        self.slots.register_full_blocks(slot, feed[:slot.pos])
        if slot.pos < S:
            return
        resume = slot.prefill.resume
        slot.prefill = None
        self.stats["prefills"] += 1
        if resume is None:
            slot.tokens = [int(np.asarray(tok0)[0])]
            slot.logprobs = [float(np.asarray(lp0)[0])]
        else:
            slot.tokens = list(resume[0])
            slot.logprobs = list(resume[1])
        slot.last_token = slot.tokens[-1]
        t_tok = self._now()
        slot.t_last_token = t_tok
        self._sync_handle(slot, t_tok)
        if slot.done:
            self._finish(slot, t_tok)

    # --------------------------------------------------- disaggregation
    def prefill_to_span(self, request: GenerationRequest,
                        budget: Optional[int] = None) -> KVSpan:
        """Prefill-role entry: run ``request``'s prefill into scratch
        pool blocks (chunked under ``budget``, or this engine's own
        per-step budget), lift the KV out as a ``KVSpan``, and free the
        blocks.  The span carries the prompt KV in POOL dtype plus the
        first sampled token/logprob, so a decode-role engine admits the
        request via ``submit_span`` without recomputing anything."""
        if not self.paged:
            raise ValueError("prefill_to_span requires paged=True")
        feed = np.asarray(request.prompt, np.int32)
        S = len(feed)
        blocks = self.slots.pool.alloc(self.slots.blocks_for(S))
        try:
            offset, tok0, lp0 = 0, None, None
            step = budget if budget is not None else self._prefill_budget()
            while offset < S:
                w = S - offset if step is None else min(step, S - offset)
                tok0, lp0 = self._ctx_chunk(feed, offset, w, blocks,
                                            request.sampling)
                offset += w
            self.stats["prefills"] += 1
            bl = np.asarray(blocks)
            kv = {k: np.asarray(self.cache[k][:, bl]) for k in self.cache}
        finally:
            self.slots.pool.free(blocks)
        return KVSpan(prompt=feed, first_token=int(np.asarray(tok0)[0]),
                      first_logprob=float(np.asarray(lp0)[0]),
                      block_size=self.block_size, kv=kv)

    def submit_span(self, request: GenerationRequest, span: KVSpan,
                    on_token=None) -> RequestHandle:
        """Decode-role entry: queue a request whose prefill already ran
        on another engine; admission rehydrates the span's blocks into
        the local pool instead of prefilling."""
        if not self.paged:
            raise ValueError("submit_span requires paged=True")
        if span.block_size != self.block_size:
            raise ValueError(
                f"span block_size {span.block_size} != engine "
                f"block_size {self.block_size}")
        self._spans[request.req_id] = span
        self.queue.submit(self.slots.validate(request))
        return self._handle_for(request, on_token=on_token)

    def _admit_span(self, req: GenerationRequest, span: KVSpan,
                    now: float) -> None:
        """Rehydrate a handed-off prefill: scatter the span's block KV
        (already pool-dtype) into freshly allocated local blocks and
        admit the slot decode-ready at pos = prompt length.

        The scatter is the fused static-signature ``_scatter_span``:
        the span KV is padded host-side to the table-width worst case
        (pad blocks route to junk block 0), so spans of EVERY length
        share one compiled donate-in-place executable — the per-block-
        count specializing ``_scatter`` recompiled (and re-traversed
        the whole pool for) each distinct span size."""
        S = len(span.prompt)
        blocks = self.slots.pool.alloc(self.slots.blocks_for(S))
        W = self.slots.table_width
        phys = np.zeros((W,), np.int32)
        phys[:len(blocks)] = blocks
        part = {}
        for k, v in span.kv.items():   # (L, n_blocks, block_size, ...)
            pad = np.zeros((v.shape[0], W - v.shape[1]) + v.shape[2:],
                           v.dtype)
            part[k] = jnp.asarray(np.concatenate([v, pad], axis=1))
        self.cache = self._scatter_span(self.cache, part,
                                        jnp.asarray(phys),
                                        jnp.int32(len(blocks)))
        slot = self.slots.admit(req, span.first_token, blocks=blocks,
                                first_logprob=span.first_logprob, pos=S)
        if self.prefix_cache:
            self.slots.register_full_blocks(slot, span.prompt)
        self.stats["spans_admitted"] += 1
        self._post_admit(slot, req, now)

    def _sync_handle(self, slot: Slot, now: float) -> None:
        """Stream any not-yet-emitted tokens to the request's handle.
        Resume replays stashed tokens into the slot; the handle's
        already-pushed count keeps them from re-emitting."""
        handle = self._handles.get(slot.request.req_id)
        if handle is None:
            return
        start = len(handle.tokens)
        for tok, lp in zip(slot.tokens[start:], slot.logprobs[start:]):
            handle._push(int(tok), now, lp)

    def _finalize(self, handle: RequestHandle, reason: str,
                  now: float) -> None:
        out = handle._finish(reason, now)
        self.results[handle.req_id] = out
        self._latency_window.append((out.ttft_s, out.tpot_s))
        if self.on_finish is not None:
            self.on_finish(handle, out)

    def _finish(self, slot: Slot, now: float = 0.0) -> None:
        self._sync_handle(slot, now)
        reason = (FINISH_STOP
                  if slot.tokens and slot.request.stops(slot.tokens[-1])
                  and len(slot.tokens) <= slot.request.max_new_tokens
                  else FINISH_LENGTH)
        self._finalize(self._handle_for(slot.request), reason, now)
        self.slots.release(slot)

    # ----------------------------------------------------------- decode
    def _preempt(self, slot: Slot) -> None:
        """Evict a live slot to relieve pool pressure: stash its generated
        tokens (+ logprobs), free its blocks, requeue the request at the
        front.  The resume path re-prefills prompt+generated, so output
        is unchanged (sampled tokens replay from the stash; sampling
        keys depend only on (seed, position), so post-resume draws are
        unchanged too).

        A slot caught MID-CHUNKED-PREFILL has no generated tokens to
        stash — stashing its empty token list would corrupt resume.
        Instead, re-stash only the original decode-preemption replay it
        was carrying (if any).  Its private blocks free; its REGISTERED
        full blocks park in the pool's cached set (prefix-matched
        shared ones just drop our reference), so resume re-matches them
        and restarts from the completed-chunk offset, not token 0."""
        if slot.prefilling:
            if slot.prefill.resume is not None:
                self._resume[slot.request.req_id] = slot.prefill.resume
            else:
                self._resume.pop(slot.request.req_id, None)
        else:
            self._resume[slot.request.req_id] = (list(slot.tokens),
                                                 list(slot.logprobs))
        self.slots.preempt(slot)
        self.queue.requeue(slot.request)

    def _ensure_decode_blocks(self) -> None:
        """Before a paged decode step, every active slot whose next write
        crosses into a new block must hold one.  Oldest slots allocate
        first; if the pool runs dry the YOUNGEST other slot is preempted
        (least work lost).  Forward progress is guaranteed: a lone slot's
        worst-case block count fits the pool (validate()), so its growth
        can always be satisfied once neighbours are evicted."""
        for slot in sorted(self.slots.active.values(), key=lambda s: s.seq):
            if self.slots.active.get(slot.index) is not slot:
                continue                   # preempted earlier this pass
            if slot.prefilling:
                continue   # holds every block up front; still a victim
            if self.slots.needs_block(slot):
                while not self.slots.pool.free_blocks():
                    victims = [s for s in self.slots.active.values()
                               if s is not slot]
                    assert victims, "validate() bounds a lone slot to the pool"
                    self._preempt(max(victims, key=lambda s: s.seq))
                slot.blocks.extend(self.slots.pool.alloc(1))
            elif self.prefix_cache:
                # defense in depth: decode normally only ever writes its
                # own private tail block (admission forks the re-fed
                # tail), but if the write target is somehow shared, fork
                # it copy-on-write rather than corrupt the sharers
                blk_idx = slot.pos // self.slots.block_size
                if self.slots.pool.refcount.get(slot.blocks[blk_idx],
                                                0) > 1:
                    while not self.slots.pool.free_blocks():
                        victims = [s for s in self.slots.active.values()
                                   if s is not slot]
                        assert victims, "a lone slot shares with no one"
                        self._preempt(max(victims, key=lambda s: s.seq))
                blocks, copy = self.slots.ensure_writable(slot.blocks,
                                                          blk_idx)
                if copy is not None:
                    src, dst = copy
                    self.cache = self._copy_block(self.cache,
                                                  jnp.int32(dst),
                                                  jnp.int32(src))
                    slot.blocks = blocks
                    slot.block_hashes = slot.block_hashes[:blk_idx]

    def _decode_step(self) -> None:
        with TraceAnnotation("engine.decode",
                             step=self.stats["decode_steps"]) as span:
            if self.paged:
                with TraceAnnotation("engine.blocks"):
                    self._ensure_decode_blocks()
            active = self.slots.active_slots()
            if not active:                     # everything was preempted
                return
            with TraceAnnotation("engine.upload"):
                index = self.slots.index_vector()
                batch = {"tokens": jnp.asarray(self.slots.token_vector()),
                         "index": jnp.asarray(index),
                         **self.slots.sampling_vectors()}
                if self.paged:
                    batch["block_table"] = jnp.asarray(
                        self.slots.block_table())
            # rows decoded, the pool positions they read and (paged) the
            # pool blocks those positions fill
            meta = {"rows": len(active), "ctx": int(index.sum())}
            if self.paged:
                bs = self.slots.block_size
                meta["blocks"] = int((-(-index // bs)).sum())
            span.set_metadata(**meta)
            if self.runtime is not None:
                toks, logps, self.cache = self.runtime.call(
                    self._decode_name, self.params, self.cache, batch)
            else:
                t0 = time.perf_counter()
                toks, logps, self.cache = self._decode(self.params,
                                                       self.cache, batch)
            with TraceAnnotation("engine.readback"):
                toks = np.asarray(toks)        # (B,) sampled in-graph
                if self.runtime is None:       # the read forced completion
                    ms = (time.perf_counter() - t0) * 1e3
                    tgt = "accel" if self._direct_impl == "pallas" else "host"
                    self._direct_step_ms[tgt] = ewma(
                        self._direct_step_ms[tgt], ms)
                logps = np.asarray(logps)
            self.stats["decode_steps"] += 1
            self.stats["decode_row_util"] += (len(active)
                                              / self.slots.max_slots)
            with TraceAnnotation("engine.emit"):
                now = self._now()
                for slot in active:
                    t = int(toks[slot.index])
                    slot.tokens.append(t)
                    slot.logprobs.append(float(logps[slot.index]))
                    slot.last_token = t
                    slot.pos += 1
                    slot.t_last_token = now
                    if (self.prefix_cache
                            and slot.pos % self.slots.block_size == 0):
                        # a block just filled: key it in the prefix index
                        # so a follow-up request sharing prompt+generated
                        # matches it
                        self.slots.register_full_blocks(
                            slot, self._kv_tokens(slot))
                    self._sync_handle(slot, now)
                    if slot.done:
                        self._finish(slot, now)

    # ------------------------------------------------ speculative decode
    def _draft_len(self) -> int:
        """Draft length k for this round: the policy's ``draft_len``
        hook (fed live signals) when it has one, else the engine's
        configured ``spec_draft_len``.  0 disables speculation for the
        step (the loop falls back to plain decode); the result is
        clamped to the compiled verify width."""
        if self.spec is None:
            return 0
        policy = self.policy
        if policy is None and self.runtime is not None:
            policy = self.runtime.server.policy
        hook = getattr(policy, "draft_len", None)
        k = (hook(self.signals(), self.spec.draft_len)
             if hook is not None else self.spec.draft_len)
        return max(0, min(int(k), self.spec.draft_len))

    def _ensure_spec_blocks(self, k: int) -> Optional[dict[int, int]]:
        """Pre-reserve the speculative fan-out: every decode-ready slot
        must hold blocks backing positions ``[pos, pos + n)`` BEFORE the
        round writes up to n candidate positions of KV.  Returns
        {slot.index: n} with per-slot n = min(k, remaining token
        budget); when the pool cannot cover a slot's fan-out, its n
        shrinks to the capacity it already holds rather than preempting
        mid-round — and if any slot ends at n < 1, returns None so the
        caller falls back to ``_decode_step`` (whose preempt-youngest
        loop guarantees progress).  Rejection later never frees these
        blocks (see ``PagedSlotManager.fanout_blocks``); shared blocks
        in the write range fork copy-on-write first, exactly like the
        plain decode path."""
        bs = self.slots.block_size
        plan: dict[int, int] = {}
        for slot in self.slots.active_slots():
            rem = slot.request.max_new_tokens - len(slot.tokens)
            n = min(k, max(rem, 0))
            need = self.slots.fanout_blocks(slot, n)
            if need > self.slots.pool.free_blocks():
                # pool short: spend only the capacity already held
                n = min(n, len(slot.blocks) * bs - slot.pos)
                need = 0
            if n < 1:
                return None
            if need:
                slot.blocks.extend(self.slots.pool.alloc(need))
            if self.prefix_cache:
                # a spec round writes a RANGE of blocks, not just the
                # tail — COW-fork any shared one before the scatter
                # touches it (fresh fan-out blocks are private already)
                for bi in range(slot.pos // bs, (slot.pos + n - 1) // bs
                                + 1):
                    if self.slots.pool.refcount.get(slot.blocks[bi],
                                                    0) <= 1:
                        continue
                    try:
                        blocks, copy = self.slots.ensure_writable(
                            slot.blocks, bi)
                    except RuntimeError:
                        return None    # no block for the fork: fall back
                    if copy is not None:
                        src, dst = copy
                        self.cache = self._copy_block(
                            self.cache, jnp.int32(dst), jnp.int32(src))
                        slot.blocks = blocks
                        slot.block_hashes = slot.block_hashes[:bi]
            plan[slot.index] = n
        return plan or None

    def _refresh_draft(self, slot: Slot) -> None:
        """Rebuild one slot's draft-cache row from its committed tokens
        (fresh admission, preempt/resume, or any round the fingerprint
        misses): a bucketed dense draft prefill over ``_kv_tokens``,
        written into the row — after which positions < pos are valid
        and the chain may extend from there."""
        toks = self._kv_tokens(slot)
        S = len(toks)
        Sb = prompt_bucket(S, self.min_bucket)
        arr = np.zeros((1, Sb), np.int32)
        arr[0, :S] = toks
        batch = {"tokens": jnp.asarray(arr),
                 "length": jnp.full((1,), S, jnp.int32),
                 **sampling_leaves(SamplingParams(), 1)}
        _, _, pc = self._draft_prefill(self.spec.params, batch)
        if Sb > self._spec_width:      # bucket overhangs the row
            pc = {k: jax.lax.slice_in_dim(pc[k], 0, self._spec_width,
                                          axis=2) for k in pc}
        self.spec.cache = self._write_slot(self.spec.cache, pc,
                                           jnp.int32(slot.index))
        self.spec.mark(slot.index, slot.request.req_id, slot.pos)

    def _spec_put(self, key: str, arr: np.ndarray):
        """Device copy of a small per-round host vector, reused across
        rounds while its CONTENT is unchanged (content-addressed, so it
        can never serve stale values): sampling leaves only change on
        admission/finish, the block table every block_size tokens,
        n_valid on plan changes — re-uploading them every round was a
        measurable slice of the round's host overhead."""
        ent = self._spec_dev_cache.get(key)
        b = arr.tobytes()
        if ent is not None and ent[0] == b:
            return ent[1]
        dev = jnp.asarray(arr)
        self._spec_dev_cache[key] = (b, dev)
        return dev

    def _spec_step(self, k: int) -> bool:
        """One speculative round: fused k-step draft chain, then ONE
        multi-token verify, then host-side longest-accepted-prefix
        acceptance.  Emits 1..k tokens per slot — every emitted token
        is VERIFY'S OWN sample at its position, so output is
        byte-identical to sequential decode on any target split.
        Returns False (round not run) when the fan-out cannot be
        reserved; the caller then takes the plain decode path."""
        plan = self._ensure_spec_blocks(k)
        if plan is None:
            return False
        active = [s for s in self.slots.active_slots()
                  if s.index in plan]
        if not active:
            return False
        for slot in active:
            if not self.spec.valid_for(slot.index, slot.request.req_id,
                                       slot.pos):
                self._refresh_draft(slot)
        B, W = self.slots.max_slots, self.spec.draft_len
        n_valid = np.zeros((B,), np.int32)
        for slot in active:
            n_valid[slot.index] = plan[slot.index]
        n_steps = int(n_valid.max())
        # 1 fused dispatch: the chain runs n_steps draft decodes,
        # feeding each sample back in and writing draft KV as it goes.
        # Convert the host vectors ONCE — tokens/index double as the
        # verify batch's last/pos (index_vector IS slot.pos), and the
        # sampling leaves are shared by both dispatches.
        tokvec = jnp.asarray(self.slots.token_vector())
        idxvec = jnp.asarray(self.slots.index_vector())
        sv = {k: self._spec_put("sv_" + k, v)
              for k, v in self.slots.sampling_vectors().items()}
        dbatch = {"tokens": tokvec, "index": idxvec,
                  "n_steps": jnp.int32(n_steps), **sv}
        if self.runtime is not None:
            drafts, _, self.spec.cache = self.runtime.call(
                self._draft_name, self.spec.params, self.spec.cache,
                dbatch)
        else:
            drafts, _, self.spec.cache = self._draft_chain(
                self.spec.params, self.spec.cache, dbatch)
        # 1 fused dispatch: verify feeds [t0, d_1..d_{n-1}] at offset
        # pos and samples the target's token at EVERY position.  The
        # drafts stay ON DEVICE — spec_verify_batch assembles the
        # token/offset/length feed in-graph, so the chain->verify edge
        # never round-trips through the host and the only sync in the
        # round is pulling verify's samples below.
        vbatch = {"drafts": drafts, "last": tokvec, "pos": idxvec,
                  "n_valid": self._spec_put("n_valid", n_valid),
                  "block_table": self._spec_put(
                      "bt", self.slots.block_table()),
                  **sv}
        if self.runtime is not None:
            vtoks, vlogps, self.cache = self.runtime.call(
                self._verify_name, self.params, self.cache, vbatch)
            vtoks = np.asarray(vtoks)
        else:
            t0 = time.perf_counter()
            vtoks, vlogps, self.cache = self._verify(self.params,
                                                     self.cache, vbatch)
            vtoks = np.asarray(vtoks)      # forces chain + verify
            ms = (time.perf_counter() - t0) * 1e3
            tgt = "accel" if self._direct_impl == "pallas" else "host"
            self._direct_step_ms[tgt] = ewma(self._direct_step_ms[tgt],
                                             ms)
        drafts = np.asarray(drafts)        # (B, W): col i = d_{i+1}
        vlogps = np.asarray(vlogps)
        emit = spec_lib.acceptance_lengths(drafts[:, :max(W - 1, 0)],
                                           vtoks, n_valid)
        now = self._now()
        self.stats["spec_rounds"] += 1
        for slot in active:
            i, n, e = slot.index, int(n_valid[slot.index]), 0
            self.stats["spec_proposed_tokens"] += n - 1
            self.stats["spec_accepted_tokens"] += emit[i] - 1
            for j in range(emit[i]):
                t = int(vtoks[i, j])
                slot.tokens.append(t)
                slot.logprobs.append(float(vlogps[i, j]))
                slot.last_token = t
                slot.pos += 1
                e += 1
                if (self.prefix_cache
                        and slot.pos % self.slots.block_size == 0):
                    self.slots.register_full_blocks(
                        slot, self._kv_tokens(slot))
                if slot.request.stops(t):
                    # sequential decode would have finished HERE: the
                    # accepted tail past a stop token must not emit
                    break
            self.stats["spec_emitted_tokens"] += e
            slot.t_last_token = now
            # draft KV through the new pos holds exactly the committed
            # tokens' keys (accepted drafts == verify samples), so the
            # next round extends without re-prefilling
            self.spec.mark(i, slot.request.req_id, slot.pos)
            self._sync_handle(slot, now)
            if slot.done:
                self._finish(slot, now)
        return True

    def _kv_tokens(self, slot: Slot) -> list[int]:
        """Tokens whose KV the slot's blocks hold, in position order:
        prompt then generated (the decode at step k writes token k's KV
        at its position before sampling token k+1), truncated to the
        written span.  Holds across resume too — the resume feed is
        prompt + replayed[:-1], a prefix of prompt + tokens."""
        return (list(slot.request.prompt) + slot.tokens)[:slot.pos]

    # ------------------------------------------------------- serve loop
    def run(self, requests: Iterable[GenerationRequest] = (),
            poll_s: float = 0.002) -> dict[int, RequestOutput]:
        """Drain ``requests`` plus anything already submitted; returns
        {req_id: RequestOutput} for the requests completed by THIS call
        (``self.results`` is drained, so a long-lived engine doesn't
        accumulate finished outputs; aborts serviced between calls are
        included).  Arrival times are relative to this call's start.

        If the loop raises, every unfinished handle is finished as
        ``aborted`` before re-raising, so streaming consumers blocked on
        another thread never hang on a dead engine loop."""
        try:
            for r in requests:
                self.queue.submit(self.slots.validate(r))
                self._handle_for(r)
            self._clock0 = time.perf_counter()
            while len(self.queue) or self.slots.active:
                with TraceAnnotation("engine.iteration",
                                     queued=len(self.queue),
                                     active=len(self.slots.active)):
                    now = self._now()
                    self._service_aborts(now)
                    # publish BEFORE admission: the policy deciding this
                    # iteration's steps sees the arrived-but-unadmitted
                    # pressure, and a central scheduler sees it cross-engine
                    self._publish_signals()
                    self._step_budget = (self._prefill_budget()
                                         if self._chunking else None)
                    while self.slots.has_free():
                        req = self.queue.pop_arrived(now)
                        if req is None:
                            break
                        if not self._can_admit(req):
                            # block-exhaustion backpressure: head-of-queue
                            # waits (front of its arrival cohort) for blocks
                            self.queue.requeue(req)
                            break
                        self._admit(req, now)
                    if self._chunking:
                        self._advance_prefills(self._step_budget)
                    if self.slots.active:
                        # speculative round when enabled and the policy's
                        # draft_len allows it (k=0 = plain decode); a round
                        # that cannot reserve its fan-out also falls back —
                        # _decode_step's preempt loop guarantees progress
                        stepped = False
                        if self.spec is not None:
                            k = self._draft_len()
                            if k >= 1:
                                stepped = self._spec_step(k)
                        if not stepped:
                            self._decode_step()
                        if self.on_step is not None:
                            self.on_step(self)
                    else:
                        nxt = self.queue.next_arrival()
                        if nxt is None:
                            break
                        with TraceAnnotation("engine.sleep"):
                            time.sleep(min(max(nxt - now, 0.0), 0.05)
                                       + poll_s)
        except BaseException:
            for h in list(self._handles.values()):
                if not h.finished:
                    self._finalize(h, FINISH_ABORTED, self._now())
            raise
        finally:
            self._clock0 = None
            # retract this run's pressure: without a final snapshot the
            # scheduler would keep aggregating the last IN-RUN publish
            # (nonzero queue/slots) long after this engine went idle,
            # and co-tenants would migrate against phantom load
            self._publish_signals()
        out, self.results = self.results, {}
        for rid in out:
            self._handles.pop(rid, None)
        return out

    def serve(self, requests: Iterable[GenerationRequest] = (),
              poll_s: float = 0.002):
        """Removed v1 surface (was a deprecation shim until PR 5)."""
        raise RuntimeError(
            "ContinuousBatchingEngine.serve() was removed; use run() — "
            "it returns {req_id: RequestOutput} (RequestOutput.tokens "
            "is the old bare array)")

    def generate(self, prompts, max_new_tokens: int = 16,
                 sampling: Optional[SamplingParams] = None) -> np.ndarray:
        """ServeEngine.generate-compatible convenience: all prompts
        arrive at t=0; returns (B, max_new_tokens) tokens in order.
        (Stop-token requests can return ragged lengths — use run().)"""
        reqs = [GenerationRequest(np.asarray(p), max_new_tokens,
                                  sampling=sampling or SamplingParams())
                for p in np.asarray(prompts)]
        out = self.run(reqs)
        return np.stack([out[r.req_id].tokens for r in reqs])
