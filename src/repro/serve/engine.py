"""ServeEngine: continuous-batching NVFP4 serving.

The engine owns a fixed set of decode SLOTS (the batch dimension of every
jitted step), a request queue with admission control, a paged KV pool, and a
quantize-once weight cache. The scheduler loop interleaves:

  1. ADMIT   — move queued requests into free slots in the order the
               scheduler POLICY dictates (serve/scheduler.py; the default
               FifoPolicy is exactly the original FIFO). Admission checks
               the pool can back prompt + max_new tokens; placement is
               shard-occupancy-aware, and with the prefix cache enabled
               (serve/prefix_cache.py) the longest cached prompt prefix is
               aliased read-only into the slot — its prefill is skipped.
  2. PREFILL — one chunk of ONE prefilling slot per iteration (bounded work
               per tick keeps decode latency flat while prompts stream in);
               the policy picks WHICH slot (latency preemption point).
               Chunks run through the same decode-mode forward as decoding
               (S=chunk tokens, per-sequence start position); other slots are
               masked inactive, so their caches are untouched bit-for-bit.
  3. DECODE  — one batched step over all slots in DECODE state; new requests
               join as finished ones retire, never restarting the batch.

Slot states: FREE -> PREFILL -> DECODE -> FREE. Exactly two compiled step
shapes exist per engine: (n_slots, prefill_chunk) and (n_slots, 1); a
trailing partial prompt chunk is fed token-by-token through the (n_slots, 1)
step so recurrent-state archs (rwkv / griffin) never consume pad tokens.

Everything the forward needs about raggedness travels as data (per-slot
position vector, active mask, block table), so one compilation serves every
admission pattern.
"""

from __future__ import annotations

import itertools
import time
from collections import deque
from dataclasses import dataclass, field

from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.obs.instrumentation import NULL, legacy_stats_dict, span_fn
from repro.serve import decode as serve_decode
from repro.serve import spec_decode
from repro.serve.kv_pool import KVPool, OutOfBlocks
from repro.serve.prequant import prequantize
from repro.serve.sampling import (SamplingParams, sample_tokens,
                                  speculative_resample)

FREE, PREFILL, DECODE = "free", "prefill", "decode"


class QueueFull(RuntimeError):
    """Admission control: the request queue is at capacity.

    Structured rejection: carries machine-readable fields (`reason`,
    `queue_depth`, `retry_after_s`) so the HTTP frontend can build a 429 +
    Retry-After — and the obs layer a reason-labelled rejection counter —
    from the exception itself instead of parsing a message string.
    `info()` is the JSON-safe dict both consume."""

    reason = "queue_full"

    def __init__(self, msg: str = "", *, reason: str | None = None,
                 queue_depth: int = 0, retry_after_s: float | None = None):
        super().__init__(msg)
        if reason is not None:
            self.reason = reason
        self.queue_depth = queue_depth
        self.retry_after_s = retry_after_s

    def info(self) -> dict:
        return {"reason": self.reason, "queue_depth": self.queue_depth,
                "retry_after_s": self.retry_after_s}


class Unservable(QueueFull, ValueError):
    """A request no pool state can ever back (rejected at submit so it never
    head-of-line blocks). ValueError-compatible for legacy callers; carries
    the same structured fields as QueueFull with `retry_after_s=None` —
    retrying an unservable request is pointless by definition. (QueueFull
    leads the MRO so its keyword-aware __init__ wins over ValueError's
    C-level one.)"""

    reason = "unservable"


@dataclass
class Request:
    prompt: list[int]
    max_new: int
    sampling: SamplingParams = field(default_factory=SamplingParams)
    req_id: int = -1  # assigned by submit()
    arrival_s: float = 0.0        # stamped by submit()
    # latency-aware scheduling (serve/scheduler.py LatencyPolicy; the
    # default FifoPolicy ignores all of these)
    priority: int = 0             # higher = more urgent
    deadline_s: float | None = None  # seconds after arrival
    queued_ticks: int = 0         # scheduler aging counter (engine-owned)
    cached_hint: int = 0          # prefix-cache matched tokens (engine-owned)


@dataclass
class RequestResult:
    req_id: int
    prompt: list[int]
    tokens: list[int]
    arrival_s: float = 0.0
    finish_s: float = 0.0
    deadline_s: float | None = None
    # filled from the request's trace when observability is enabled
    # (EngineConfig.obs); None otherwise
    queue_wait_s: float | None = None   # submit -> slot admission
    ttft_s: float | None = None         # submit -> first sampled token
    decode_tok_s: float | None = None   # mean per-token decode latency

    @property
    def latency_s(self) -> float:
        return self.finish_s - self.arrival_s

    @property
    def deadline_met(self) -> bool | None:
        if self.deadline_s is None:
            return None
        return self.latency_s <= self.deadline_s


@dataclass
class Handoff:
    """Finished prefill leaving a prefill-role engine for a decode-role one.

    Carries the ORIGINAL Request object (req_id intact — the frontend
    bridge keeps routing streamed tokens by id across the role boundary),
    the tokens generated so far (the first sampled token — its logits came
    from the prompt's last position on the prefill worker), and the
    prompt's KV as host-tier payloads: `(logical_block, payload)` pairs in
    `KVPool.read_block_host` format. Payloads are immutable snapshots
    (docs/CONVENTIONS.md §9); a partial tail block rides along whole —
    bytes past `length` are stale-behind-the-position-mask, exactly like
    any other partially filled block. bf16 payloads import bit-exactly, so
    a disaggregated greedy stream equals the single-engine stream."""

    req: Request
    generated: list[int]
    length: int                       # prompt tokens backed by the payloads
    blocks: list                      # [(logical_idx, payload), ...]


@dataclass(frozen=True)
class EngineConfig:
    n_slots: int = 4
    max_len: int = 256            # per-sequence capacity (prompt + generated)
    block_size: int = 16
    n_blocks: int | None = None   # pool size; default n_slots * max_len / bs
    prefill_chunk: int = 16
    paged: bool = True
    prequant: bool = True
    scheme: str = "quartet2"
    max_queue: int = 256
    base_seed: int = 0
    # self-speculative decoding (serve/spec_decode.py): propose spec_k tokens
    # per round with the first draft_layers blocks, verify in one chunk
    spec_k: int = 0               # 0 disables speculation
    draft_layers: int = 0         # truncated-stack draft depth
    # block-table flash-decode kernel (kernels/paged_attention.py): None
    # resolves per backend — the Pallas kernel on TPU (the body is
    # pltpu-specific), the gather_view reference path everywhere else
    # (where the kernel would only ever run interpreted). Explicit True
    # forces the kernel (interpret mode off-TPU — how the parity tests
    # drive it); requires paged=True.
    paged_kernel: bool | None = None
    # NVFP4-quantized KV cache (serve/kv_pool.py PackedKV): token-kind pool
    # leaves stored as packed e2m1 codes + e4m3 group scales (0.28125x the
    # bf16 HBM bytes), quantized per token at scatter time with
    # deterministic RTN and dequantized in-kernel (paged_kernel) or exactly
    # on the gather path. Requires paged=True; the bf16 pool stays the
    # bitwise reference mode. Incompatible with spec_k > 0: exact
    # speculative verification is specified against the bf16 cache image,
    # and a quantized target cache would silently change acceptance.
    kv_quant: bool = False
    # mesh-sharded serving (launch.mesh.make_serve_mesh): decode slots + the
    # slot-affine KV pool shard over the mesh's "data" axis (manual
    # shard_map — no pool collectives), packed weights + LM head over
    # "model" (GSPMD auto). None = single-host (all steps unwrapped).
    # Requires n_slots and the pool's n_blocks divisible by the "data" size.
    mesh: Any = None
    # radix-tree prefix cache (serve/prefix_cache.py): retired sequences'
    # blocks stay cached; a new request aliases its longest cached prefix
    # read-only (COW at the divergence) and skips that prefill entirely.
    # Silently inactive where sharing is unsound — dense caches, sliding-
    # window (pure-lattn) pools, recurrent-state archs; `engine.cache` is
    # None there.
    prefix_cache: bool = False
    # hierarchical prefix cache (requires prefix_cache=True): eviction under
    # pool pressure spills block bytes to a host-RAM tier instead of
    # dropping them, and a later match swaps them back in asynchronously —
    # a spill-hot request still skips every prefill forward over its
    # matched prefix, bitwise-equal to cold under bf16. Also lifts the
    # shard-affinity limit: spilled/hot prefixes become reachable from any
    # shard via host-tier copies (serve/prefix_cache.py module docstring).
    prefix_spill: bool = False
    # optional cap on host-tier bytes (LRU snapshot trim); None = unbounded
    host_budget_bytes: int | None = None
    # proactive cross-shard replication: nodes matched this many times get
    # their blocks copied into peer shards' pools through the host tier
    # (bounded to one block per engine tick; free blocks only — replication
    # never evicts). None disables; meaningless with n_shards == 1.
    replicate_hits: int | None = None
    # disaggregated prefill/decode (serve/frontend.py EnginePair): "both"
    # is the classic single engine; a "prefill" worker runs admission +
    # chunked prefill only and exports finished KV as host-tier Handoffs;
    # a "decode" worker admits Handoffs into DECODE slots (zero prefill
    # forwards) and runs only decode ticks — prefill chunks never steal
    # decode ticks. Split roles require a paged pool without sliding-window
    # reclamation or recurrent state (whole resident blocks must travel)
    # and spec_k == 0 (the draft pool does not travel with the handoff).
    role: str = "both"
    # scheduler policy object (serve/scheduler.py). None -> FifoPolicy,
    # which reproduces the pre-policy engine exactly; LatencyPolicy adds
    # priority/deadline admission, prefill preemption, and aging.
    scheduler: Any = None
    # observability hook (obs/instrumentation.py Instrumentation). None
    # disables ALL instrumentation beyond the legacy stats dict — the
    # engine hot path then costs one `.enabled` attribute read per hook
    # site and token streams are bitwise identical to the uninstrumented
    # engine (tests/test_obs.py).
    obs: Any = None
    # injectable monotonic clock (() -> float seconds). None resolves to
    # time.perf_counter. EVERY engine timestamp flows through it — arrival
    # stamps, scheduler `now`, trace span boundaries, step timers — so
    # deadline-slack, aging-bound, and the frontend's visibility-timeout
    # logic are testable with a fake clock instead of real sleeps.
    clock: Any = None
    # per-token emission hook: callable(req, new_tokens, result) invoked
    # from host tick boundaries whenever a slot's generated stream grows
    # (result is the RequestResult at retirement, None otherwise). This is
    # what the streaming frontend (serve/frontend.py) rides — without it
    # tokens only surface at retirement. Reassignable post-construction via
    # `engine.token_hook`; called on whichever thread steps the engine.
    token_hook: Any = None

    def resolved_paged_kernel(self) -> bool:
        if self.paged_kernel is None:
            return self.paged and jax.default_backend() == "tpu"
        return self.paged_kernel


@dataclass
class _Slot:
    state: str = FREE
    req: Request | None = None
    cursor: int = 0               # prompt tokens already prefilled
    length: int = 0               # tokens currently in the cache
    last_tok: int = 0
    generated: list[int] = field(default_factory=list)
    emitted: int = 0              # generated tokens already flushed through
    #                               the per-token hook (engine.token_hook)
    draft_len: int = 0            # tokens the spec draft has consumed
    prefix_len: int = 0           # prompt tokens adopted from the prefix
    #                               cache (cursor starts here; their prefill
    #                               is skipped)
    cache_nodes: list = field(default_factory=list)  # pinned radix nodes


class ServeEngine:
    def __init__(self, cfg: ArchConfig, params, econf: EngineConfig | None = None):
        if cfg.enc_dec:
            raise NotImplementedError("enc-dec serving: use the explicit "
                                      "encoder path (examples)")
        self.cfg = cfg
        self.econf = econf or EngineConfig()
        e = self.econf
        # one monotonic clock for every engine timestamp (EngineConfig.clock;
        # the frontend bridge shares it for visibility-timeout bookkeeping)
        self.clock = e.clock if e.clock is not None else time.perf_counter
        self.token_hook = e.token_hook
        # observability: resolved FIRST so prequantization can report its
        # weight-quantization health through the probe
        self.obs = e.obs if e.obs is not None else NULL
        # host spans around the tick's phases (`serve.*`), bound once:
        # disabled, each is one call returning a shared no-op context
        self._span = span_fn(self.obs)
        probe = self.obs.quant_probe if self.obs.enabled else None
        self.params = (prequantize(params, cfg, e.scheme, probe=probe)
                       if e.prequant else params)
        self.paged_kernel = e.resolved_paged_kernel()
        if self.paged_kernel and not e.paged:
            raise ValueError("paged_kernel=True requires paged=True (the "
                             "kernel consumes pool-shaped leaves + a block "
                             "table; dense caches have neither)")
        self.mesh = e.mesh
        self.data_shards = 1
        if self.mesh is not None:
            self.data_shards = dict(self.mesh.shape).get("data", 1)
            if e.n_slots % self.data_shards:
                raise ValueError(
                    f"n_slots={e.n_slots} must divide over the mesh 'data' "
                    f"axis ({self.data_shards}): shard_map splits the slot "
                    "batch evenly")
        if e.kv_quant and not e.paged:
            raise ValueError("kv_quant=True requires paged=True: the NVFP4 "
                             "cache format is a property of pool blocks "
                             "(the dense cache is the bitwise reference)")
        if e.kv_quant and e.spec_k > 0:
            raise ValueError("kv_quant=True is incompatible with spec_k > 0: "
                             "exact speculative verification is defined "
                             "against the bf16 cache image")
        self.pool = KVPool(cfg, e.n_slots, e.max_len, paged=e.paged,
                           block_size=e.block_size, n_blocks=e.n_blocks,
                           n_shards=self.data_shards, quantized=e.kv_quant)
        if e.role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be 'both', 'prefill' or 'decode', got {e.role!r}")
        if e.role != "both":
            if (not e.paged or self.pool.window is not None
                    or self.pool.has_state_kinds):
                raise ValueError(
                    "disaggregated roles require a paged pool without "
                    "sliding-window reclamation or recurrent state kinds: "
                    "the KV handoff moves whole resident blocks")
            if e.spec_k > 0:
                raise ValueError(
                    "disaggregated roles are incompatible with spec_k > 0 "
                    "(the draft pool does not travel with the handoff)")
        self.role = e.role
        self.handoffs: deque[Handoff] = deque()       # prefill: exported
        self.handoff_queue: deque[Handoff] = deque()  # decode: awaiting slot
        if self.mesh is not None:
            # commit the hot state to its serving layout up front: packed
            # weights + head over "model", cache block/slot homes over
            # "data" — the jitted steps then never reshard
            from repro.dist import sharding as SH
            self.params = jax.device_put(
                self.params, SH.serve_param_shardings(self.params, self.mesh))
            self.pool.caches = jax.device_put(
                self.pool.caches,
                SH.serve_cache_shardings(self.pool.caches, self.mesh))
        if e.spec_k > 0:
            if e.draft_layers <= 0:
                raise ValueError("spec_k > 0 requires draft_layers >= 1")
            if cfg.rwkv is not None and e.spec_k + 1 >= cfg.rwkv.chunk:
                # the (n_slots, spec_k+1) verify chunk must stay on the
                # per-token WKV tail path — the chunk-parallel form's
                # accumulation order differs from S=1 steps, which would
                # silently break bitwise equality with the non-spec engine
                raise ValueError(
                    f"spec_k={e.spec_k} needs spec_k + 1 < rwkv.chunk "
                    f"({cfg.rwkv.chunk}) for exact verification")
            self.draft = spec_decode.DraftStack(cfg, self.params, e)
            # one compiled resampler serves every stochastic slot (shapes
            # are fixed per engine: (spec_k,) drafts, (spec_k+1, V) logits;
            # temperature/top_k are traced scalars, so no per-value
            # recompiles) — spec_round would otherwise dispatch the whole
            # sort/softmax/categorical chain eagerly per slot per round
            self._resample = jax.jit(
                lambda drafts, target_logits, key, temp, tk:
                speculative_resample(drafts, None, target_logits, key,
                                     temperature=temp, top_k=tk))
        else:
            self.draft = None
        # a verify chunk writes up to spec_k positions past a sequence's
        # final token; admission reserves that overshoot margin up front
        self._margin = e.spec_k
        # largest per-ensure growth any engine path performs (prefill chunk,
        # spec verify chunk, single decode token) — lets window-reclaimed
        # pools admit sequences against their LIVE-block bound instead of
        # blocks_for(total), so long lattn requests fit O(window) pools
        self._max_growth = max(e.prefill_chunk, e.spec_k + 1)
        self.slots = [_Slot() for _ in range(e.n_slots)]
        self.queue: deque[Request] = deque()
        self._ids = itertools.count()
        self._step_fns: dict[int, object] = {}
        self._sampler = jax.jit(sample_tokens)
        self._key = jax.random.PRNGKey(e.base_seed)
        self._tick = 0
        # radix prefix cache: only where sharing is exact (paged layout, no
        # sliding-window reclamation, no recurrent state) — excluded
        # configurations run with cache=None, bit-identically to
        # prefix_cache=False (serve/prefix_cache.py module docstring)
        self.cache = None
        self._matches: dict[int, tuple[int, Any]] = {}  # req_id -> (epoch, Match)
        if e.prefix_spill and not e.prefix_cache:
            raise ValueError("prefix_spill=True requires prefix_cache=True "
                             "(the host tier is a property of the cache)")
        if e.prefix_cache:
            from repro.serve.prefix_cache import PrefixCache
            if PrefixCache.supported(self.pool):
                self.cache = PrefixCache(
                    self.pool, spill=e.prefix_spill,
                    host_budget_bytes=e.host_budget_bytes,
                    replicate_hits=e.replicate_hits, clock=self.clock)
        from repro.serve.scheduler import FifoPolicy
        self.sched = e.scheduler if e.scheduler is not None else FifoPolicy()
        # stats store: a plain dict when observability is off (the legacy
        # layout, zero overhead), registry-backed counters behind the same
        # MutableMapping surface when on — `engine.stats` is a property so
        # every existing caller (`stats[k] += n`, bench reset loops,
        # snapshot comparisons) works against either
        if self.obs.enabled:
            self._stats = self.obs.stats_view()
            self.pool.obs = self.obs
            if self.cache is not None:
                self.cache.obs = self.obs
        else:
            self._stats = legacy_stats_dict()

    @property
    def stats(self):
        """Engine counters (legacy dict surface; see __init__)."""
        return self._stats

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def submit(self, request: Request) -> int:
        """Queue a request; raises QueueFull (structured: reason / queue
        depth / suggested retry_after_s) at capacity, Unservable (a
        QueueFull AND ValueError) when no pool state can ever back it."""
        if self.role == "decode":
            # a decode worker never prefills: plain submissions would wedge
            # in PREFILL forever. Work arrives as Handoffs (submit_handoff);
            # the EnginePair facade routes submits to the prefill worker.
            self.stats["rejected"] += 1
            exc = Unservable("decode-role engine takes Handoffs, not "
                             "prompts (submit to the prefill worker)",
                             reason="wrong_role",
                             queue_depth=len(self.handoff_queue))
            if self.obs.enabled:
                self.obs.on_reject(request, exc.reason, self.clock())
            raise exc
        total = len(request.prompt) + request.max_new + self._margin
        if not self.pool.can_ever_admit(total, self._max_growth):
            # reject now: an unservable request would head-of-line block the
            # FIFO forever (can_admit never becomes true)
            self.stats["rejected"] += 1
            bound = (f"{self.pool.blocks_per_shard} blocks per shard "
                     f"(slot-affine, {self.pool.n_shards} shards)"
                     if self.pool.n_shards > 1
                     else f"{self.pool.n_blocks} blocks")
            exc = Unservable(
                f"request needs {total} positions "
                f"({self.pool.max_live_blocks(total, self._max_growth)} live "
                f"blocks) but the pool serves at most "
                f"max_len={self.econf.max_len} / {bound}",
                queue_depth=len(self.queue))
            if self.obs.enabled:
                self.obs.on_reject(request, exc.reason, self.clock())
            raise exc
        if len(self.queue) >= self.econf.max_queue:
            # checked AFTER unservability: a permanent rejection must not
            # masquerade as a transient queue-full when the queue happens
            # to be saturated (clients would retry forever)
            self.stats["rejected"] += 1
            exc = QueueFull(
                f"queue at capacity ({self.econf.max_queue})",
                queue_depth=len(self.queue),
                retry_after_s=self.suggested_retry_after_s())
            if self.obs.enabled:
                self.obs.on_reject(request, exc.reason, self.clock())
            raise exc
        request.req_id = next(self._ids)
        request.arrival_s = self.clock()
        self.queue.append(request)
        if self.obs.enabled:
            self.obs.on_submit(request, request.arrival_s)
        return request.req_id

    def cancel(self, req_id: int, reason: str = "cancelled") -> bool:
        """Best-effort cancellation: remove a QUEUED request, or free the
        slot of an in-flight one (its committed KV prefix is inserted into
        the prefix cache first — the tokens were paid for; a resubmission
        reuses them). `reason` labels the trace span / metrics
        ("cancelled" | "disconnected" | "requeued" — the frontend's
        lifecycle states all funnel through this one reclaim path).
        Returns False when `req_id` is unknown (already retired, rejected,
        or never submitted)."""
        t = self.clock()
        for r in self.queue:
            if r.req_id == req_id:
                self.queue.remove(r)
                self._matches.pop(req_id, None)
                self.stats["cancelled"] += 1
                if self.obs.enabled:
                    self.obs.on_cancel(r, t, reason=reason)
                return True
        for h in self.handoff_queue:
            if h.req.req_id == req_id:
                # received but not yet admitted: the prefill worker already
                # released its blocks at export, and this engine never
                # allocated — dropping the host payloads reclaims everything
                self.handoff_queue.remove(h)
                self.stats["cancelled"] += 1
                if self.obs.enabled:
                    self.obs.on_cancel(h.req, t, reason=reason)
                return True
        for i, s in enumerate(self.slots):
            if s.req is not None and s.req.req_id == req_id:
                if self.cache is not None:
                    # same order as retirement: insert while the blocks are
                    # still referenced, then drop this slot's pins
                    stream = (s.req.prompt + s.generated)[:self.pool.length(i)]
                    self.cache.insert(stream, i)
                    if s.cache_nodes:
                        self.cache.release(s.cache_nodes)
                self.pool.release(i)
                if self.draft is not None:
                    self.draft.pool.release(i)
                self.slots[i] = _Slot()
                self.stats["cancelled"] += 1
                if self.obs.enabled:
                    self.obs.on_cancel(s.req, t, reason=reason)
                return True
        return False

    def submit_handoff(self, handoff: Handoff) -> None:
        """Hand a finished prefill to this decode-role engine. The payloads
        are host memory — nothing is allocated until `_admit` finds a slot,
        so a queued Handoff cancels by simply dropping it."""
        if self.role != "decode":
            raise ValueError("submit_handoff on a non-decode-role engine")
        self.handoff_queue.append(handoff)

    def suggested_retry_after_s(self) -> float:
        """Backpressure hint for rejected clients: seconds until the engine
        has plausibly worked the backlog down. Estimated as the queued +
        in-flight generated-token backlog over the decode rate observed so
        far, clamped to [0.5, 60]; 1.0 before any decode step has run."""
        if self.stats["decode_tokens"] <= 0:
            return 1.0
        backlog = sum(r.max_new for r in self.queue)
        for s in self.slots:
            if s.req is not None:
                backlog += max(s.req.max_new - len(s.generated), 0)
        rate = self.stats["decode_tokens"] / max(self.stats["decode_s"], 1e-9)
        return float(min(max(backlog / max(rate, 1e-9), 0.5), 60.0))

    def has_work(self) -> bool:
        return (bool(self.queue) or bool(self.handoff_queue)
                or any(s.state != FREE for s in self.slots))

    def run(self) -> list[RequestResult]:
        """Drain queue + slots; returns results in completion order."""
        out: list[RequestResult] = []
        while self.has_work():
            out.extend(self.step())
        return out

    @property
    def free_slots(self) -> int:
        return sum(s.state == FREE for s in self.slots)

    # ------------------------------------------------------------------
    # scheduler iteration
    # ------------------------------------------------------------------

    def step(self) -> list[RequestResult]:
        """One scheduler tick: admit, one prefill chunk, one decode step.

        Role-split engines run half a tick each: a prefill worker never
        decodes (finished prompts leave as Handoffs instead), a decode
        worker never prefills (Handoffs admit straight into DECODE)."""
        with self._span("serve.tick"):
            self.stats["ticks"] += 1
            with self._span("serve.admit", queued=len(self.queue)):
                self._admit()
            if self.role != "decode":
                self._prefill_tick()
            finished = (self._handoff_tick() if self.role == "prefill"
                        else self._decode_tick())
            if self.cache is not None and self.cache.spill:
                # tick-boundary host-tier work: bounded proactive replication
                # of hot prefixes, then fold this tick's dispatched swap-ins
                # into the plain cached-block accounting (the writes are
                # ordered before any dependent step read — no sync here)
                self.cache.replicate_hot()
                self.cache.complete_swaps()
            if self.obs.enabled:
                with self._span("serve.gauges"):
                    self.obs.on_tick(self)  # occupancy / pool / cache gauges
            return finished

    def _admit(self) -> None:
        for r in self.queue:
            r.queued_ticks += 1  # scheduler aging (LatencyPolicy)
        if self.obs.enabled:
            # queue depth / aging / slack gauges — the policy object knows
            # its own urgency model, so IT reports (scheduler.py observe)
            with self._span("serve.gauges"):
                self.sched.observe(self.obs, self.queue, self.clock())
        if self.role == "decode":
            # Handoffs admit FIFO straight into DECODE: the KV is already
            # computed, so "admission" is commit + allocate + import the
            # host payloads (zero prefill forwards on this engine)
            while (self.handoff_queue
                   and any(s.state == FREE for s in self.slots)):
                if not self._try_admit_handoff(self.handoff_queue[0]):
                    return
                self.handoff_queue.popleft()
            return
        if not self.queue:
            return
        now = self.clock()
        if self.cache is not None and not self.sched.head_of_line:
            # cache-aware admission ordering: a large cached prefix makes a
            # request cheap to admit (its prefill is mostly skipped).
            # Matches are memoized per request against the cache EPOCH
            # (prompts are immutable; the tree only changes on
            # insert/evict), so a deferred request costs one radix walk per
            # tree change, not one per tick. Tiered caches weight the hint
            # by residency (spilled tokens count half — a swap-in is far
            # cheaper than prefill but not free; scheduler.py ordering law)
            for r in self.queue:
                r.cached_hint = self.cache.hint_tokens(self._match(r))
        while self.queue and any(s.state == FREE for s in self.slots):
            admitted = False
            for req in self.sched.admission_order(self.queue, now):
                if self._try_admit(req):
                    self.queue.remove(req)
                    admitted = True
                    break
                if self.sched.head_of_line:
                    return  # FIFO: don't overtake the head request
            if not admitted:
                return

    def _match(self, req: Request):
        """Epoch-memoized cache match for a queued request (shared by the
        hint pass and `_try_admit` — the same prompt is never re-walked
        while the tree is unchanged)."""
        hit = self._matches.get(req.req_id)
        if hit is None or hit[0] != self.cache.epoch:
            hit = (self.cache.epoch, self.cache.match(req.prompt))
            self._matches[req.req_id] = hit
        return hit[1]

    def _try_admit(self, req: Request) -> bool:
        """Place `req` in a FREE slot (prefix-cache aliasing + shard-
        occupancy placement); False when no slot/shard can back it now."""
        total = len(req.prompt) + req.max_new + self._margin
        plan = pinned = None
        if self.cache is not None:
            m = self._match(req)
            # the last prompt token is always computed (its logits seed the
            # first generated token), so cap the usable match below it
            mtoks, adopt, tail = m.plan(len(req.prompt) - 1,
                                        self.pool.block_size)
            if mtoks > 0:
                # without the host tier the plan is usable only on its home
                # shard (slot affinity); with it any shard works — spilled
                # or off-shard blocks materialize on the placed shard
                plan = (mtoks, adopt, tail,
                        None if self.cache.spill else m.shard)
                # pin BEFORE any eviction below can see these nodes unpinned
                pinned = adopt + ([tail] if tail is not None else [])
                self.cache.acquire(pinned)
        i = self._place(total, plan)
        if i is None and plan is not None:
            # the prefix's home shard has no usable slot: admit cold on the
            # occupancy-best shard instead (slot affinity makes the cached
            # blocks unreachable from other shards)
            self.cache.release(pinned)
            plan = pinned = None
            i = self._place(total, None)
        if i is None:
            if pinned:
                self.cache.release(pinned)
            return False
        if plan is not None and self.cache.spill:
            # swap spilled/off-shard planned blocks onto the placed shard
            # (dispatched host->device copies overlapping later ticks); on
            # shortage fall back to a cold admission of the same slot
            try:
                self.cache.materialize(pinned, self.pool.shard_of_slot(i))
            except OutOfBlocks:
                self.cache.release(pinned)
                plan = pinned = None
        self.pool.reset_slot(i)
        self.pool.commit(i, total, self._max_growth)
        prefix_len = 0
        nodes: list = []
        if plan is not None:
            mtoks, adopt, tail, _ = plan
            sh = self.pool.shard_of_slot(i)
            if adopt:
                self.pool.adopt_prefix(i, [n.blocks[sh] for n in adopt],
                                       len(adopt) * self.pool.block_size)
            if tail is not None:
                self.pool.cow_block(i, tail.blocks[sh])
                self.cache.release([tail])  # private copy made; unpin
            self.pool.ensure(i, mtoks)
            prefix_len = mtoks
            nodes = adopt
            self.stats["prefill_skipped_tokens"] += mtoks
            self.stats["prefix_hits"] += 1
        if self.draft is not None:
            # the draft pool never aliases (serve/spec_decode.py owns no
            # cache); _prefill_tick catches its cursor up over the skipped
            # prefix with truncated-stack chunks
            self.draft.pool.reset_slot(i)
            self.draft.pool.commit(i, total, self._max_growth)
        self.slots[i] = _Slot(state=PREFILL, req=req, cursor=prefix_len,
                              prefix_len=prefix_len, cache_nodes=nodes)
        self.stats["admitted"] += 1
        if self.obs.enabled:
            self.obs.on_admit(req, i, prefix_len, self.clock())
        if self.cache is not None:
            # hit-rate stats book exactly once per ADMITTED request (a
            # deferred request re-matches every tick; recording those
            # retries would inflate the rate), and only ADOPTED matches
            # count as hits (a cross-shard match that admitted cold is a
            # miss in every way that matters)
            self.cache.record(m if plan is not None else None)
            self._matches.pop(req.req_id, None)  # left the queue
        return True

    def _place(self, total: int, plan) -> int | None:
        """Pick a FREE slot for a request needing `total` positions.

        With a prefix-cache `plan` pinned to a home shard (plan[3] set —
        the non-spill mode), only the matched shard's slots can use the
        cached blocks (slot affinity). A host-tier plan (plan[3] None)
        ranks EVERY free shard by replicated-prefix availability — how many
        planned blocks are already resident there — before effective free
        blocks, so a replica-holding shard wins over a merely-empty one and
        only the remainder swaps in. Cold placement is shard-occupancy-
        aware: shards are tried by free-block count (descending, slot id
        breaking ties) instead of first-fit — single-shard pools reduce to
        the original first-free-slot behavior exactly. When a shard is
        short, unpinned cached prefixes on it are evicted before giving
        up."""
        free_by_shard: dict[int, list[int]] = {}
        for i, s in enumerate(self.slots):
            if s.state == FREE:
                free_by_shard.setdefault(self.pool.shard_of_slot(i),
                                         []).append(i)

        def resident(sh):
            n = sum(1 for node in plan[1] if sh in node.blocks)
            if plan[2] is not None and sh in plan[2].blocks:
                n += 1
            return n

        if plan is not None and plan[3] is not None:
            shard_cached = {plan[3]: len(plan[1])} if plan[3] in free_by_shard \
                else {}
        elif plan is not None:
            # admission credit counts only blocks ALREADY resident on the
            # shard: the rest are swapped in from the host tier and draw on
            # the free list exactly like a cold allocation would
            shard_cached = {sh: sum(1 for node in plan[1]
                                    if sh in node.blocks)
                            for sh in free_by_shard}
        else:
            shard_cached = {sh: 0 for sh in free_by_shard}
        if plan is not None and plan[3] is None:
            shards = sorted(free_by_shard,
                            key=lambda sh: (-resident(sh),
                                            -self.pool.effective_free_blocks(sh),
                                            sh))
        else:
            shards = sorted(shard_cached,
                            key=lambda sh: (-self.pool.effective_free_blocks(sh)
                                            if self.pool.paged else 0, sh))
        for sh in shards:
            i = free_by_shard[sh][0]
            if self._admissible(i, total, shard_cached[sh]):
                return i
        return None

    def _admissible(self, slot: int, total: int, cached: int) -> bool:
        if self.draft is not None and not self.draft.pool.can_admit(
                total, self._max_growth, slot=slot):
            return False
        if self.pool.can_admit(total, self._max_growth, slot=slot,
                               cached_blocks=cached):
            return True
        if self.cache is not None:
            short = self.pool.admission_shortfall(
                total, self._max_growth, slot=slot, cached_blocks=cached)
            if short and self.cache.evict(self.pool.shard_of_slot(slot),
                                          short) >= short:
                return self.pool.can_admit(total, self._max_growth,
                                           slot=slot, cached_blocks=cached)
        return False

    def _prefill_tick(self) -> None:
        e = self.econf
        cands = [(i, s) for i, s in enumerate(self.slots)
                 if s.state == PREFILL]
        if not cands:
            return
        # scheduler preemption point: the policy picks WHICH prefilling
        # slot advances this tick (Fifo: lowest index, the original
        # behavior; LatencyPolicy: most urgent request first). Slots NOT
        # picked keep aging, so preemption is starvation-free too: a
        # low-priority prompt passed over by a stream of critical arrivals
        # grows its effective priority until it wins the pick.
        i = self.sched.pick_prefill(cands, self.clock())
        for j, s in cands:
            if j != i:
                s.req.queued_ticks += 1
        slot = self.slots[i]
        prompt = slot.req.prompt
        if self.draft is not None and slot.draft_len < slot.cursor:
            # prefix-cache skip left the DRAFT behind (its pool never
            # aliases): catch it up over the skipped tokens with truncated-
            # stack chunks — draft_layers/L of a full forward, still one
            # bounded chunk per tick
            gap = slot.cursor - slot.draft_len
            size = e.prefill_chunk if gap >= e.prefill_chunk else 1
            tokens = np.zeros((e.n_slots, size), np.int32)
            tokens[i] = prompt[slot.draft_len: slot.draft_len + size]
            pos = np.zeros((e.n_slots,), np.int32)
            pos[i] = slot.draft_len
            active = np.zeros((e.n_slots,), bool)
            active[i] = True
            t0 = self.clock()
            self.draft.pool.ensure(i, slot.draft_len + size)
            out = self.draft.forward(size, tokens, pos, active)
            t_disp = self.clock() - t0
            # sync the draft CACHE writes too, not just the logits — an
            # async cache write landing after the timer stops would be
            # billed to whatever step happens to sync next
            with self._span("serve.sync"):
                jax.block_until_ready((out, self.draft.pool.caches))
            t_sync = self.clock() - t0
            self.stats["prefill_s"] += t_sync
            if self.obs.enabled:
                self.obs.on_prefill_step(t_disp, t_sync)
            slot.draft_len += size
            return  # bounded work: one chunk per tick
        remaining = len(prompt) - slot.cursor
        size = e.prefill_chunk if remaining >= e.prefill_chunk else 1
        with self._span("serve.prefill", tokens=size):
            with self._span("serve.inputs"):
                chunk = prompt[slot.cursor: slot.cursor + size]
                self.pool.ensure(i, slot.cursor + size)
                tokens = np.zeros((e.n_slots, size), np.int32)
                tokens[i] = chunk
                pos = np.zeros((e.n_slots,), np.int32)
                pos[i] = slot.cursor
                active = np.zeros((e.n_slots,), bool)
                active[i] = True
            t0 = self.clock()
            logits = self._forward(size, tokens, pos, active)
            if self.draft is not None:
                # the draft cache covers the prompt too: same chunk through
                # the prefix stack (its layers recompute what the first
                # draft_layers of the full forward just computed)
                self.draft.pool.ensure(i, slot.cursor + size)
                self.draft.forward(size, tokens, pos, active)
            t_disp = self.clock() - t0
            # sync logits AND the cache pytrees: blocking on logits alone
            # lets the (donated, in-place) KV scatter complete
            # asynchronously AFTER the timer stops, under-reporting
            # prefill_s and leaking device time into whichever step syncs
            # next
            sync = [logits, self.pool.caches]
            if self.draft is not None:
                sync.append(self.draft.pool.caches)
            with self._span("serve.sync"):
                jax.block_until_ready(sync)
            t_sync = self.clock() - t0
            self.stats["prefill_s"] += t_sync
            self.stats["prefill_tokens"] += size
            self.stats["prefill_steps"] += 1
            if self.obs.enabled:
                self.obs.on_prefill_step(t_disp, t_sync)
            slot.cursor += size
            slot.draft_len = slot.cursor
            if slot.cursor == len(prompt):
                # prompt fully cached: sample the first generated token
                # from the logits of the prompt's last position
                with self._span("serve.sample"):
                    toks = self._sample(logits[:, -1])
                with self._span("serve.readback"):
                    tok = int(toks[i])
                slot.state = DECODE
                slot.length = len(prompt)
                slot.last_tok = tok
                slot.generated.append(tok)
                if self.obs.enabled:
                    self.obs.on_first_token(slot.req, self.clock())
                self._flush(i)
        return  # bounded work: one chunk per tick

    def _retire_slot(self, i: int) -> RequestResult:
        """Complete slot `i`: emit the result, cache the stream's blocks,
        release pins and pool state (cache-insert-then-release ordering:
        insertion adds the cache's own ref while the blocks are still
        referenced; release only ever decrefs)."""
        with self._span("serve.retire"):
            slot = self.slots[i]
            res = RequestResult(
                slot.req.req_id, list(slot.req.prompt),
                list(slot.generated), arrival_s=slot.req.arrival_s,
                finish_s=self.clock(),
                deadline_s=slot.req.deadline_s)
            if self.obs.enabled:
                # closes the trace and surfaces queue-wait / TTFT /
                # per-token decode latency on the result
                self.obs.on_retire(slot.req, res, len(slot.generated),
                                   res.finish_s)
            self._flush(i, res)
            if self.cache is not None:
                self.cache.insert(slot.req.prompt + slot.generated, i)
                if slot.cache_nodes:
                    self.cache.release(slot.cache_nodes)
            self.pool.release(i)
            if self.draft is not None:
                self.draft.pool.release(i)
            self.slots[i] = _Slot()
            self.stats["finished"] += 1
            return res

    def _handoff_tick(self) -> list[RequestResult]:
        """Prefill-role half-tick: every slot whose prompt just finished
        (DECODE state = prompt cached + first token sampled) leaves as a
        Handoff — its KV snapshotted block-by-block to host payloads, its
        prompt's full blocks inserted into this worker's prefix cache
        (future shared prompts skip prefill HERE too), its pool state
        released. A request its first token already completed (max_new=1)
        retires locally; there is nothing left to decode."""
        out: list[RequestResult] = []
        for i, s in enumerate(self.slots):
            if s.state != DECODE:
                continue
            if len(s.generated) >= s.req.max_new:
                out.append(self._retire_slot(i))
                continue
            blocks = []
            for j in range(self.pool._alloc_upto[i]):
                blk = int(self.pool._table[i, j])
                if blk == self.pool.sentinel:
                    continue
                payload, _ = self.pool.read_block_host(blk)
                blocks.append((j, payload))
            h = Handoff(req=s.req, generated=list(s.generated),
                        length=s.length, blocks=blocks)
            if self.cache is not None:
                # only the PROMPT is cached: the first generated token was
                # sampled but its KV was never written on this engine
                self.cache.insert(s.req.prompt, i)
                if s.cache_nodes:
                    self.cache.release(s.cache_nodes)
            self.pool.release(i)
            self.slots[i] = _Slot()
            self.handoffs.append(h)
            self.stats["handoffs"] += 1
        return out

    def _try_admit_handoff(self, h: Handoff) -> bool:
        """Import a Handoff into a FREE slot, straight into DECODE state:
        commit, allocate the prompt's blocks, dispatch the host payload
        writes (they overlap this tick's decode step — the next step's
        reads are ordered after them by the cache data dependence). The
        emitted counter starts past the handed-off tokens: the prefill
        worker already flushed them through the token hook."""
        req = h.req
        total = len(req.prompt) + req.max_new + self._margin
        i = self._place(total, None)
        if i is None:
            return False
        self.pool.reset_slot(i)
        self.pool.commit(i, total, self._max_growth)
        try:
            self.pool.ensure(i, h.length)
        except OutOfBlocks:
            self.pool.release(i)
            return False
        for j, payload in h.blocks:
            self.pool.write_block_host(int(self.pool._table[i, j]), payload)
        self.slots[i] = _Slot(state=DECODE, req=req, cursor=len(req.prompt),
                              length=h.length, last_tok=h.generated[-1],
                              generated=list(h.generated),
                              emitted=len(h.generated))
        self.stats["admitted"] += 1
        if self.obs.enabled:
            self.obs.on_admit(req, i, 0, self.clock())
        return True

    def _decode_tick(self) -> list[RequestResult]:
        e = self.econf
        dec = [i for i, s in enumerate(self.slots) if s.state == DECODE]
        finished: list[RequestResult] = []
        # retire before stepping: a slot whose request is already complete
        # (max_new reached) frees its blocks for the next admission
        for i in list(dec):
            if len(self.slots[i].generated) >= self.slots[i].req.max_new:
                finished.append(self._retire_slot(i))
                dec.remove(i)
        if not dec:
            return finished

        if e.spec_k > 0:
            with self._span("serve.decode", rows=len(dec)):
                t0 = self.clock()
                emitted = spec_decode.spec_round(self, dec)
                t_disp = self.clock() - t0
                # the whole cache pytree, not just the first leaf: truncate
                # rewrites tables but layer caches past leaf 0 may still
                # have in-flight scatters when the timer stops
                with self._span("serve.sync"):
                    jax.block_until_ready(self.pool.caches)
                t_sync = self.clock() - t0
                self.stats["decode_s"] += t_sync
                self.stats["decode_tokens"] += emitted
                self.stats["decode_steps"] += 1
                if self.obs.enabled:
                    self.obs.on_decode_step(t_disp, t_sync)
                for i in dec:
                    self._flush(i)
            return finished

        with self._span("serve.decode", rows=len(dec)):
            with self._span("serve.inputs"):
                tokens = np.zeros((e.n_slots, 1), np.int32)
                pos = np.zeros((e.n_slots,), np.int32)
                active = np.zeros((e.n_slots,), bool)
                for i in dec:
                    slot = self.slots[i]
                    self.pool.ensure(i, slot.length + 1)
                    tokens[i, 0] = slot.last_tok
                    pos[i] = slot.length
                    active[i] = True
            t0 = self.clock()
            logits = self._forward(1, tokens, pos, active)
            with self._span("serve.sample"):
                toks = self._sample(logits[:, -1])
            t_disp = self.clock() - t0
            # sync tokens AND cache writes (same leak as prefill: the
            # donated cache scatter may outlive the token fetch)
            with self._span("serve.sync"):
                jax.block_until_ready((toks, self.pool.caches))
            t_sync = self.clock() - t0
            self.stats["decode_s"] += t_sync
            self.stats["decode_tokens"] += len(dec)
            self.stats["decode_steps"] += 1
            if self.obs.enabled:
                self.obs.on_decode_step(t_disp, t_sync)
            for i in dec:
                slot = self.slots[i]
                slot.length += 1
                with self._span("serve.readback"):
                    slot.last_tok = int(toks[i])
                slot.generated.append(slot.last_tok)
                self._flush(i)
        return finished

    def _flush(self, i: int, result: RequestResult | None = None) -> None:
        """Push a slot's un-emitted generated tokens through the per-token
        hook (EngineConfig.token_hook / engine.token_hook). Called ONLY at
        host tick boundaries — after the prefill-completion sample, after a
        decode/spec round's appends, and at retirement (`result` then
        carries the final RequestResult alongside any remaining tokens) —
        so between ticks `emitted == len(generated)` always holds and a
        cancel landing between ticks never strands tokens."""
        if self.token_hook is None:
            return
        s = self.slots[i]
        new = s.generated[s.emitted:]
        if new or result is not None:
            s.emitted = len(s.generated)
            with self._span("serve.flush"):
                self.token_hook(s.req, new, result)

    # ------------------------------------------------------------------
    # jitted steps
    # ------------------------------------------------------------------

    def _forward(self, size: int, tokens, pos, active):
        fn = self._step_fns.get(size)
        if fn is None:
            # one engine-step builder serves both layouts (block_table=None
            # is the dense path); under a mesh the step is shard_map-wrapped:
            # manual over "data" (slots/pool/table/inputs pre-split,
            # shard-local gather/scatter), auto over "model" (GSPMD weights)
            if self.mesh is not None:
                step_fn = serve_decode.make_sharded_serve_step(
                    self.cfg, self.econf.scheme, self.mesh,
                    paged_kernel=self.paged_kernel)
            else:
                step_fn = serve_decode.make_paged_serve_step(
                    self.cfg, self.econf.scheme,
                    paged_kernel=self.paged_kernel)
            # donate the cache pytree: the pool is the dominant serving
            # allocation and the step rebinds it, so XLA may update in place
            # instead of double-buffering it
            fn = self._step_fns[size] = jax.jit(step_fn, donate_argnums=(1,))
        # stacked (n_slots, 2, max_blocks) read/write tables: scatters go
        # through the write view, whose prefix-cache-aliased entries hold
        # the sentinel — shared blocks are never written (kv_pool.py)
        with self._span("serve.tables", rebuilt=self.pool.tables_stale):
            tables = self.pool.tables_device()
        with self._span("serve.dispatch"):
            logits, self.pool.caches = fn(
                self.params, self.pool.caches, tables,
                jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(active))
        return logits

    def _spec_key(self, slot: int):
        """Per-(round, slot) key for stochastic speculative acceptance
        (sampling.speculative_resample). Shares the engine's tick counter
        with `_sample`, so streams stay deterministic run-to-run for a
        fixed base_seed and submission order."""
        self._tick += 1
        return jax.random.fold_in(
            jax.random.fold_in(self._key, self._tick), 10_000 + slot)

    def _sample(self, last_logits):
        temps = np.zeros((self.econf.n_slots,), np.float32)
        topks = np.zeros((self.econf.n_slots,), np.int32)
        for i, slot in enumerate(self.slots):
            if slot.req is not None:
                temps[i] = slot.req.sampling.temperature
                topks[i] = slot.req.sampling.top_k
        self._tick += 1
        key = jax.random.fold_in(self._key, self._tick)
        return self._sampler(last_logits, jnp.asarray(temps),
                             jnp.asarray(topks), key)
