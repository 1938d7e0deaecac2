"""Continuous-batching serving engine: batched prefill + mixed-depth decode.

A fixed set of ``max_batch`` sequence slots.  New requests are bucketed by
padded prompt length and prefilled in ONE jit call per bucket (rows are
written into the slab caches with a single batched scatter); every decode
tick advances all active slots one token **at their own position** — a
``(max_batch,)`` int32 position array is threaded through
``model.decode_step`` so rows of different depths attend over exactly their
own prefix (static shapes: jit caches one decode program plus one prefill
program per bucket shape).

**Serving API v2.**  All knobs live in one validated
:class:`repro.serve.config.EngineConfig` (``Engine(cfg, params,
EngineConfig(...))``; the legacy ``Engine(cfg, params, **knobs)`` shim was
removed after its one-release deprecation window).  ``submit()`` returns a
:class:`RequestHandle` — incremental token streaming (generator and
on-token callback), ``cancel()`` that releases blocks and staged state
mid-admission, and truthiness preserving the legacy admitted-now contract.
Queued admission order is no longer FIFO: a :class:`Scheduler` orders by
priority class with deadline-aware tie-breaks and a one-bucket aging rule
(starvation bound), and owns the head-of-line stall state so paged
backpressure survives across ``serve()`` calls.

**Background serve loop.**  The engine is no longer caller-pumped only:
``engine.start()`` runs the tick on a daemon thread and ``engine.stop()``
drains it, so ``RequestHandle.tokens()`` blocks on a per-handle queue and
streams to real clients without anyone hand-ticking ``serve()``.  The
locking discipline is ONE re-entrant lock around all scheduler + slot +
backend state: every public mutator (``submit``/``cancel``/``preempt``/
``step``/``serve``) takes it, the whole tick runs under it, and the cache
backend asserts it is held before mutating pool state — there is exactly
one writer at any instant, the jit calls themselves are single-threaded,
and the synchronous ``serve(requests)`` path is a thin wrapper over the
same ``_tick()`` so loop-mode output is token-identical to sync output
(pinned).  All timestamps (``submit_ts``/``token_ts``/``deadline``) share
one time base: the injected ``clock`` callable (default
``time.perf_counter``), so a virtual clock makes latency and
deadline-miss accounting fully deterministic (see
``benchmarks/load_harness.py``).

The cache substrate is fully owned by :mod:`repro.serve.backend`: the
engine holds ONE :class:`~repro.serve.backend.CacheBackend` and never
branches on family or substrate — dense slabs, paged block pools, dense
recurrent state, and the hybrid's split substrate are all the same code
path here.  Substrate semantics, in backend terms:

* **dense** — per-slot (max_batch, max_seq, ...) cache rows; a slot
  reserves a full ``max_seq`` row for its whole lifetime.
* **paged** (``EngineConfig(paged=True)``) — admission reserves only
  ``ceil(min(len(prompt) + max_new, max_seq) / block_size)`` blocks (so
  decode can never run out mid-request), freeing a slot just returns its
  blocks to the pool.  When the pool is short, admission backpressures
  until blocks free.
* **split substrate** (hybrid, ``paged=True``) — attention KV leaves in
  the block pool, O(1) SSM state dense, routed structurally per leaf.

**Chunked prefill** (``prefill_chunk=N``): prompts longer than N tokens are
admitted in N-token pieces interleaved with decode ticks — each tick runs
at most ONE chunk of prefill work before the decode step.  Attention
chunks continue the staged KV cache at the write offset; the recurrent
families resume the mamba2 SSD scan from the carried (conv, state), so
chunked and length-bucketed prefill are both token-identical to
whole-prompt prefill.

**Prefix cache** (``prefix_cache=True``): a radix tree over prompt tokens
(``repro.serve.prefix_cache``) remembers what prefill already computed.
Admission matches the longest cached prefix and re-prefills only the
uncached tail — LUNA's capacity-for-computation bet applied to serving.
Warm admissions ride the same staged machinery as chunked prefill — whose
token-identity to whole-prompt prefill is already pinned — so warm output
is token-identical to cold for every family and both scheduler paths.

Sampling draws from per-request PRNG streams (``fold_in(seed_key, rid)``
then per-token step) — a request's sampled tokens are independent of its
slot index, co-tenants, and scheduling, for every sampling mode.

Serving the paper's technique = run with ``--quant luna_*`` so every
projection goes through the LUNA integer path.
"""
from __future__ import annotations

import math
import queue
import threading
import time
from dataclasses import dataclass, field, fields

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.moe import held_load
from repro.models.registry import get_model
from repro.obs import (EXPERT_LOAD_BUCKETS, ITL_BUCKETS, PHASE_BUCKETS,
                       SPEC_REQUEST_BUCKETS, SPEC_WINDOW_BUCKETS,
                       TTFT_BUCKETS, MetricsRegistry,
                       Tracer)
from repro.obs.trace import PHASE_EVENTS
from repro.serve.backend import make_backend
from repro.serve.config import EngineConfig
from repro.serve.paged import ceil_div
from repro.serve.prefix_cache import PrefixCache
from repro.serve.sampling import SamplingConfig, sample
from repro.serve.spec import accept_length


@dataclass(eq=False)
class Request:
    """One generation request.

    ``priority``: scheduler class — higher admits first (e.g. 0 = batch,
    1 = interactive).  ``deadline``: a stamp on the ENGINE CLOCK (the
    ``clock`` callable injected at construction, default
    ``time.perf_counter`` — compute deadlines as ``engine.clock() +
    budget``, NOT ``time.time()``) used as the within-class tie-break
    (earlier = sooner; None = no deadline) and for first-token
    deadline-miss accounting.  ``submit_ts``/``token_ts`` are stamped by
    the engine on the same clock — TTFT is ``token_ts[0] - submit_ts``,
    ITL the consecutive ``token_ts`` gaps; one time base means every
    latency and deadline quantity is directly comparable (and
    deterministic under a virtual clock).
    ``eq=False``: a request is an identity (the engine keys streaming
    callbacks on the object itself, so rid reuse can never cross streams).
    """
    rid: int
    prompt: list[int]
    max_new: int = 16
    priority: int = 0
    deadline: float | None = None
    out: list[int] = field(default_factory=list)
    done: bool = False
    cancelled: bool = False
    submit_ts: float | None = field(default=None, repr=False)
    token_ts: list[float] = field(default_factory=list, repr=False)
    # engine-internal: the submit trace event / submitted counter fired
    # (submit_ts alone can't carry this — harnesses pre-pin arrival
    # stamps, and a backpressured submit() retry must not double-count)
    _submit_seen: bool = field(default=False, repr=False)
    # engine-internal speculative-decoding tallies, observed into the
    # per-request histograms at retirement (spec mode only)
    _spec_accepted: int = field(default=0, repr=False)
    _spec_rejected: int = field(default=0, repr=False)


#: end-of-stream sentinel pushed onto every subscribed token queue at
#: retirement (completion OR cancellation) — queue consumers never poll.
_STREAM_DONE = object()


class RequestHandle:
    """Live view of one submitted request.

    * truthiness — ``bool(handle)`` is the legacy ``submit()`` contract:
      True iff the request was admitted immediately.  False = backpressure:
      with the background loop running the request IS left queued on the
      scheduler (the loop admits it when capacity frees); without the loop
      it is NOT queued — retry, or hand it to ``serve()``.
    * streaming — :meth:`tokens` yields tokens incrementally.  While the
      background loop runs it BLOCKS on a per-handle queue (each emitted
      token is pushed under the engine lock, so no token is missed or
      duplicated); without the loop it drives the engine one tick at a
      time between yields, exactly as before.  An ``on_token`` callback
      registered at ``submit()`` fires synchronously per emitted token.
      The streamed sequence is exactly ``req.out`` (pinned in tests).
    * :meth:`cancel` — releases the request's slot, blocks and staged
      state wherever it currently is in the lifecycle; safe from any
      thread.
    """

    def __init__(self, engine: "Engine", req: Request, on_token=None):
        self._engine = engine
        self.req = req
        self._on_token = on_token
        self._admitted = False

    def __bool__(self) -> bool:
        return self._admitted

    @property
    def rid(self) -> int:
        return self.req.rid

    @property
    def out(self) -> list[int]:
        return self.req.out

    @property
    def done(self) -> bool:
        return self.req.done

    @property
    def cancelled(self) -> bool:
        return self.req.cancelled

    def cancel(self) -> bool:
        """Stop the request and release its resources; True unless it had
        already finished.  Covers every lifecycle stage — queued, staged
        mid-chunked-prefill, actively decoding, or never admitted (the
        engine then just closes the request out)."""
        return self._engine.cancel(self.req)

    def tokens(self):
        """Generator of this request's tokens, in emission order, ending
        when the request completes (or is cancelled).

        With the background loop running this blocks on the handle's
        stream queue — the loop thread does all engine work and each
        ``get`` wakes exactly when the next token (or the end-of-stream
        sentinel) lands.  Without the loop it drives the engine one tick
        at a time while waiting, and an un-admitted handle re-attempts
        admission between ticks (the legacy contract).  The two modes
        compose: the generator re-checks ``engine.running`` on every wait
        so a loop started or stopped mid-stream is picked up."""
        eng = self._engine
        q = eng._subscribe(self.req)
        while True:
            try:
                tok = q.get_nowait()
            except queue.Empty:
                tok = None
            if tok is _STREAM_DONE:
                return
            if tok is not None:
                yield tok
                continue
            if eng.running:
                # loop mode: block until the loop delivers (bounded wait so
                # a stop(drain=False) mid-stream falls back to sync mode)
                try:
                    tok = q.get(timeout=0.1)
                except queue.Empty:
                    continue
                if tok is _STREAM_DONE:
                    return
                yield tok
                continue
            # sync mode: the caller's thread is the engine
            if self.req.done:
                continue        # sentinel is already in the queue
            if not self._admitted:
                self._admitted = eng._admit_handle(self)
                if not self._admitted and eng.idle:
                    raise RuntimeError(
                        f"request {self.req.rid} cannot be admitted on an "
                        "idle engine (capacity permanently short?)")
            if not self.req.done:
                eng.step()


@dataclass(eq=False)
class _QueueEntry:
    """Scheduler bookkeeping for one queued request.  ``passed`` counts
    admissions that went to OTHER requests while this one waited;
    ``enqueue_ts`` is stamped on the scheduler's clock (the engine's
    injected clock) so queue-wait time shares the single time base."""
    req: Request
    arrival: int
    passed: int = 0
    enqueue_ts: float = 0.0


class Scheduler:
    """Priority-class admission queue with deadline tie-breaks, one-bucket
    aging, and the persistent head-of-line stall state.

    Ordering: highest *effective* priority class first; within a class,
    aged entries first (by arrival), then earliest deadline, then arrival.
    Effective priority = ``req.priority``, plus ONE bucket once the entry
    has been passed over ``starvation_bound`` times.

    Documented bounds (pinned by the scheduler property tests):

    * **priority inversion <= one bucket** — at every admission, any
      still-queued request's ``priority`` exceeds the admitted request's
      ``priority`` by at most 1 (aging adds at most one bucket, and the
      scheduler always picks a maximal effective class).
    * **starvation bound** — under priorities spanning two adjacent
      classes, a queued request is passed over at most ``starvation_bound``
      times by higher-priority work plus once per earlier-arrived request
      (aged entries outrank every unaged and every later-arrived aged
      entry of their class, so new arrivals can never leapfrog them).

    The stall state (per-rid ``free_capacity`` at the last failed
    reservation) lives HERE, not in ``serve()``'s locals, so paged
    backpressure survives across ``serve()`` calls and ``submit()`` uses
    the same logic — a backpressured request retries only after capacity
    actually grew, instead of re-walking the radix tree (and churning
    shared-block refcounts) on every attempt; stalls are tracked per rid
    so concurrently backpressured pollers cannot thrash each other's
    record.
    """

    def __init__(self, starvation_bound: int = 8, clock=None):
        self.starvation_bound = starvation_bound
        self.clock = clock if clock is not None else time.perf_counter
        self._queue: list[_QueueEntry] = []
        self._arrivals = 0
        self._stalls: dict[int, int] = {}

    @property
    def pending(self) -> int:
        return len(self._queue)

    def push(self, req: Request) -> None:
        self._queue.append(_QueueEntry(req, self._arrivals,
                                       enqueue_ts=self.clock()))
        self._arrivals += 1

    def queued(self, req: Request) -> bool:
        """True if ``req`` (by object identity) is currently queued."""
        return any(e.req is req for e in self._queue)

    def aged(self, e: _QueueEntry) -> bool:
        return e.passed >= self.starvation_bound

    def effective_priority(self, e: _QueueEntry) -> int:
        """Base priority, plus at most ONE aging bucket (this cap is what
        bounds priority inversion to one bucket)."""
        return e.req.priority + (1 if self.aged(e) else 0)

    def _key(self, e: _QueueEntry):
        if self.aged(e):
            return (-self.effective_priority(e), 0, float(e.arrival),
                    e.arrival)
        dl = e.req.deadline if e.req.deadline is not None else math.inf
        return (-self.effective_priority(e), 1, dl, e.arrival)

    def select(self) -> _QueueEntry | None:
        """The entry the next admission should take (queue unchanged)."""
        if not self._queue:
            return None
        return min(self._queue, key=self._key)

    def commit(self, entry: _QueueEntry) -> None:
        """``entry`` was admitted: remove it and age everyone it passed."""
        self._queue.remove(entry)
        self.age_all()

    def age_all(self) -> None:
        """An admission went to someone not in the queue (or just removed
        from it): every waiting entry was passed over once.  Direct
        ``submit()`` admissions call this too, so the starvation bound
        holds engine-wide, not just for queue-internal admissions."""
        for e in self._queue:
            e.passed += 1

    def remove(self, req: Request) -> bool:
        """Drop a queued request BY OBJECT IDENTITY (cancellation before
        admission, or a direct admission claiming its own stale entry) —
        rid matching could tear down an unrelated request reusing the
        number."""
        for e in self._queue:
            if e.req is req:
                self._queue.remove(e)
                return True
        return False

    def drop(self, entry: _QueueEntry) -> None:
        """Evict one entry without aging anyone (no admission happened)."""
        self._queue.remove(entry)

    # --- head-of-line stall bookkeeping ---------------------------------
    _MAX_STALLS = 128          # bound on abandoned-rid stall records

    def stalled(self, rid: int, capacity: int, need: int) -> bool:
        """True while ``rid``'s last reservation failure still stands: the
        retry wants at least as much capacity as the failed attempt and
        capacity has not grown past what it failed at.  A smaller request
        reusing the rid is NOT gated — the record is per failed demand,
        not per name."""
        rec = self._stalls.get(rid)
        return rec is not None and need >= rec[1] and capacity <= rec[0]

    def note_stall(self, rid: int, capacity: int, need: int) -> None:
        self._stalls[rid] = (capacity, need)
        while len(self._stalls) > self._MAX_STALLS:
            self._stalls.pop(next(iter(self._stalls)))

    def clear_stall(self, rid: int | None = None) -> None:
        if rid is None:
            self._stalls.clear()
        else:
            self._stalls.pop(rid, None)


@dataclass(eq=False)
class _ChunkedPrefill:
    """A staged admission in flight: its reserved slot + staged cache rows
    (long chunked prompts, warm prefix-cache hits, and cold recurrent
    admissions that capture a mid-prompt state snapshot all ride this).
    ``eq=False``: identity semantics — field-wise ``==`` on staged jax
    pytrees is both meaningless and a crash."""
    req: Request
    slot: int
    staging: object        # dense (1, stage_len) cache tree
    consumed: int = 0      # prompt tokens already prefilled (or reused)
    capture_at: int | None = None   # grid boundary to snapshot state at
    captured: object | None = None  # the snapshot, once captured
    scatter_table: object | None = None  # COW redirect for the final scatter


@dataclass
class EngineMetrics:
    """Wall-clock + token accounting split by phase: the VALUE type.

    A plain snapshot — ``Engine.metrics`` is an :class:`EngineMetricsView`
    over the engine's :class:`~repro.obs.registry.MetricsRegistry` that
    reads and writes these same fields live; ``view.snapshot()`` (and
    ``since()``) return instances of this dataclass.  The field set,
    ``since()``, and ``summary()`` contracts are pinned in
    ``tests/test_obs.py``."""
    prefill_s: float = 0.0
    decode_s: float = 0.0
    prefill_tokens: int = 0      # prompt tokens pushed through prefill
    decode_tokens: int = 0       # tokens emitted by decode ticks
    prefill_calls: int = 0       # jit prefill invocations (bucket or chunk)
    prefill_chunks: int = 0      # chunked-admission pieces among those
    ticks: int = 0
    occupancy_sum: int = 0       # sum over ticks of active slots
    prefix_hits: int = 0         # admissions seeded from the prefix cache
    prefix_tokens_reused: int = 0   # prompt tokens NOT re-prefilled
    cache_evictions: int = 0     # prefix-cache nodes evicted (LRU)
    cancelled: int = 0           # requests cancelled mid-lifecycle
    preemptions: int = 0         # active requests kicked back to the queue
    deadline_hits: int = 0       # first token on or before req.deadline
    deadline_misses: int = 0     # first token after req.deadline
    spec_ticks: int = 0          # speculative draft->verify ticks run
    spec_drafted: int = 0        # draft tokens proposed across spec ticks
    spec_accepted: int = 0       # draft tokens the verifier accepted
    spec_rejected: int = 0       # draft tokens the verifier rejected

    def since(self, start: "EngineMetrics") -> "EngineMetrics":
        """Per-call delta: these counters minus a ``start`` snapshot (the
        engine-lifetime metrics keep accumulating across serve() calls)."""
        return EngineMetrics(**{
            f.name: getattr(self, f.name) - getattr(start, f.name)
            for f in fields(self)})

    def summary(self, max_batch: int) -> dict:
        d = {
            "prefill_s": self.prefill_s,
            "decode_s": self.decode_s,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "prefill_calls": self.prefill_calls,
            "prefill_chunks": self.prefill_chunks,
            "ticks": self.ticks,
            # tok/s is 0.0 when NO tokens moved: an empty run divides 0
            # tokens by near-zero wall time, and 0/eps reporting absurd
            # throughputs is worse than an honest zero
            "prefill_tok_s": (self.prefill_tokens
                              / max(self.prefill_s, 1e-9)
                              if self.prefill_tokens else 0.0),
            "decode_tok_s": (self.decode_tokens / max(self.decode_s, 1e-9)
                             if self.decode_tokens else 0.0),
            "occupancy": (self.occupancy_sum / (self.ticks * max_batch)
                          if self.ticks else 0.0),
            "prefix_hits": self.prefix_hits,
            "prefix_tokens_reused": self.prefix_tokens_reused,
            "cache_evictions": self.cache_evictions,
            "cancelled": self.cancelled,
            "preemptions": self.preemptions,
            "deadline_hits": self.deadline_hits,
            "deadline_misses": self.deadline_misses,
            "spec_ticks": self.spec_ticks,
            "spec_drafted": self.spec_drafted,
            "spec_accepted": self.spec_accepted,
            "spec_rejected": self.spec_rejected,
            "spec_acceptance": (self.spec_accepted / self.spec_drafted
                                if self.spec_drafted else 0.0),
        }
        return d


#: EngineMetrics field -> (registry metric name, help).  The registry is
#: the single source of truth; the view below is the dataclass-shaped
#: facade engine code and tests read/write.
_ENGINE_COUNTERS = {
    "prefill_s": ("engine_prefill_seconds_total",
                  "wall seconds inside prefill jit calls"),
    "decode_s": ("engine_decode_seconds_total",
                 "wall seconds inside decode jit calls"),
    "prefill_tokens": ("engine_prefill_tokens_total",
                       "prompt tokens pushed through prefill"),
    "decode_tokens": ("engine_decode_tokens_total",
                      "tokens emitted by decode ticks"),
    "prefill_calls": ("engine_prefill_calls_total",
                      "jit prefill invocations (bucket or chunk)"),
    "prefill_chunks": ("engine_prefill_chunks_total",
                       "chunked-admission prefill pieces"),
    "ticks": ("engine_ticks_total", "engine ticks run"),
    "occupancy_sum": ("engine_occupancy_slots_total",
                      "sum over ticks of active slots"),
    "prefix_hits": ("engine_prefix_hits_total",
                    "admissions seeded from the prefix cache"),
    "prefix_tokens_reused": ("engine_prefix_tokens_reused_total",
                             "prompt tokens not re-prefilled"),
    "cache_evictions": ("engine_prefix_cache_evictions_total",
                        "prefix-cache nodes evicted (LRU)"),
    "cancelled": ("engine_requests_cancelled_total",
                  "requests cancelled mid-lifecycle"),
    "preemptions": ("engine_preemptions_total",
                    "active requests kicked back to the queue"),
    "deadline_hits": ("engine_deadline_hits_total",
                      "first token on or before the request deadline"),
    "deadline_misses": ("engine_deadline_misses_total",
                        "first token after the request deadline"),
    "spec_ticks": ("engine_spec_ticks_total",
                   "speculative draft->verify ticks run"),
    "spec_drafted": ("engine_spec_drafted_tokens_total",
                     "draft tokens proposed across speculative ticks"),
    "spec_accepted": ("engine_spec_accepted_tokens_total",
                      "draft tokens the verifier accepted"),
    "spec_rejected": ("engine_spec_rejected_tokens_total",
                      "draft tokens the verifier rejected"),
}


class EngineMetricsView:
    """Live :class:`EngineMetrics` facade over a metrics registry.

    Attribute reads return the registry counter's current value and
    attribute writes set it (``engine.metrics.ticks += 1`` and the
    bench's counter resets both work unchanged), so the registry is the
    single source of truth while every historical ``engine.metrics``
    call site keeps its contract.  ``snapshot()`` materializes a plain
    :class:`EngineMetrics`; ``since()``/``summary()`` delegate to it.
    """

    __slots__ = ("_counters",)

    def __init__(self, registry):
        object.__setattr__(self, "_counters", {
            f: registry.counter(name, help)
            for f, (name, help) in _ENGINE_COUNTERS.items()})

    def __getattr__(self, name):
        try:
            c = object.__getattribute__(self, "_counters")[name]
        except KeyError:
            raise AttributeError(name) from None
        return c.value()

    def __setattr__(self, name, value):
        counters = object.__getattribute__(self, "_counters")
        if name not in counters:
            raise AttributeError(
                f"EngineMetricsView has no metric field {name!r}")
        counters[name].set(value)

    def snapshot(self) -> EngineMetrics:
        return EngineMetrics(**{f.name: getattr(self, f.name)
                                for f in fields(EngineMetrics)})

    def since(self, start: EngineMetrics) -> EngineMetrics:
        return self.snapshot().since(start)

    def summary(self, max_batch: int) -> dict:
        return self.snapshot().summary(max_batch)


class _Program:
    """A jitted engine program that counts the calls after which its
    compiled cache grew (``engine_compiles_total{program}``) and marks
    the span open around such a call ``compiled=True``."""
    __slots__ = ("fn", "name", "_engine")

    def __init__(self, engine: "Engine", name: str, impl):
        self.fn = jax.jit(impl)
        self.name = name
        self._engine = engine

    def __call__(self, *args):
        n = self.fn._cache_size()
        out = self.fn(*args)
        if self.fn._cache_size() > n:
            self._engine._c_compiles.add(program=self.name)
            span = self._engine.tracer.current()
            if span is not None:
                span.args["compiled"] = True
        return out


class Engine:
    def __init__(self, cfg, params, config: EngineConfig | None = None,
                 *, clock=None):
        """``clock``: the engine's single time base — a zero-arg callable
        returning monotonic seconds (default ``time.perf_counter``).
        Every ``submit_ts``/``token_ts`` stamp, metrics wall-clock
        interval, and deadline comparison goes through it, so injecting a
        virtual clock makes latency + deadline accounting deterministic
        (the load harness does exactly that)."""
        if config is None:
            config = EngineConfig()
        config.validate(cfg.family)
        if config.quant is not None and getattr(cfg, "quant", None) is not \
                None and cfg.quant.mode != "bf16":
            raise ValueError(
                f"EngineConfig(quant={config.quant!r}) freezes decode "
                f"weights to 4-bit; combining it with model-level "
                f"quant mode {cfg.quant.mode!r} would quantize twice — "
                "pick one")
        self.cfg = cfg
        self.model = get_model(cfg)
        self.params = params
        self.config = config
        self.max_batch = config.max_batch
        self.max_seq = config.max_seq
        self.sampling = config.sampling or SamplingConfig()
        self.prefill_bucket = config.prefill_bucket
        self.prefill_chunk = config.prefill_chunk
        self.backend = make_backend(self.model, cfg.family, config)
        self.caches = self.backend.caches
        self.clock = clock if clock is not None else time.perf_counter
        # observability: ONE registry per engine is the source of truth
        # for every counter (self.metrics is a live view over it); the
        # tracer shares self.clock so virtual-clock runs trace
        # deterministically.  Both exist even when tracing is off —
        # disabled tracer events are a cheap early-return.  While on,
        # every span also lands on the profiler's host timeline as
        # ``engine.<phase>``.
        self.registry = MetricsRegistry()
        self.metrics = EngineMetricsView(self.registry)
        self.tracer = Tracer(clock=self.clock,
                             capacity=config.trace_buffer,
                             enabled=config.trace,
                             annotate=jax.profiler.TraceAnnotation)
        # decode weights are backend-owned state: the full-precision tree
        # itself under quant=None (token-identity), a frozen 4-bit tree
        # under quant="lut4"/"int4" (affine) or "nf4"/"nf4p" (NF4 codebook
        # + D&C residual correction) — prefill always uses self.params.
        # Traced, the freeze span ends once the tree is on the device.
        with self.tracer.span("freeze", quant=config.quant or "bf16"):
            self.decode_params = self.backend.prepare_decode_params(
                params, config.quant)
            if self.tracer.enabled:
                jax.block_until_ready(self.decode_params)
        self.prefix_cache = None
        if config.prefix_cache:
            self.prefix_cache = PrefixCache(
                max_nodes=config.prefix_cache_nodes,
                **self.backend.prefix_cache_kwargs())
            # recurrent snapshots are captured on this boundary grid;
            # paged payloads must land on whole blocks
            self._capture_grid = self.backend.capture_grid(
                config.prefill_bucket)
        self._evictions_seen = 0
        self.positions = np.zeros(config.max_batch, np.int32)
        self.key = jax.random.PRNGKey(config.seed)
        self.active: dict[int, Request] = {}
        self.slots: list[Request | None] = [None] * config.max_batch
        self._chunked: list[_ChunkedPrefill] = []
        self._admitting = False        # _admit in flight (emit window)
        self._callbacks: dict[Request, list] = {}
        self._streams: dict[Request, list[queue.SimpleQueue]] = {}
        # ONE re-entrant lock guards scheduler + slot + backend state:
        # every public mutator and the whole tick run under it
        self._lock = threading.RLock()
        self.backend.bind_lock(self._lock)
        self._loop_thread: threading.Thread | None = None
        self._loop_stop = threading.Event()
        self._loop_wake = threading.Event()
        self._drain_on_stop = True
        self.scheduler = Scheduler(config.starvation_bound,
                                   clock=self.clock)
        self._obs_init(cfg.family, config)
        self._prefill = _Program(self, "prefill", self._prefill_impl)
        self._decode = _Program(self, "decode", self._decode_impl)
        self._chunk_step = _Program(self, "chunk_step", self._chunk_step_impl)
        self._chunk_finish = _Program(self, "chunk_finish",
                                      self._chunk_finish_impl)
        self._seed_gather = _Program(self, "seed_gather",
                                     self.backend.gather_staging)
        # speculative decoding: the proposer drafts, _verify scores the
        # whole (B, spec_k+1) window at the DECODE precision in one call,
        # _spec_commit re-runs a partial-accept window on recurrent
        # substrates, _draft is the self-speculation step over the pruned
        # nf4p LUT tree (see repro.serve.spec)
        self._spec = None
        if config.spec is not None:
            from repro.core.quant import (SPEC_DRAFT_QUANT,
                                          quantize_draft_params)
            from repro.serve.spec import make_proposer
            if config.spec == "self_lut":
                self.draft_params = (
                    self.decode_params
                    if config.quant == SPEC_DRAFT_QUANT
                    else quantize_draft_params(params))
                self._draft = _Program(self, "draft", self._draft_impl)
            self._spec = make_proposer(config.spec, self)
            self._verify = _Program(self, "verify", self._verify_impl)
            self._spec_commit = _Program(self, "spec_commit",
                                         self._spec_commit_impl)

    # --- observability ---------------------------------------------------
    def _obs_init(self, family: str, config: EngineConfig):
        """Register the engine's non-EngineMetrics instruments: latency
        histograms, lifecycle counters, level gauges, and the static
        ``engine_info`` identity series."""
        reg = self.registry
        self._h_ttft = reg.histogram(
            "engine_ttft_seconds",
            "time from submit to first emitted token",
            ("priority",), buckets=TTFT_BUCKETS)
        self._h_itl = reg.histogram(
            "engine_itl_seconds",
            "latency between consecutive emitted tokens",
            ("priority",), buckets=ITL_BUCKETS)
        self._h_phase = reg.histogram(
            "engine_tick_phase_seconds",
            "wall seconds per engine phase per tick",
            ("phase",), buckets=PHASE_BUCKETS)
        self._phase_observe = {
            p: (lambda dt, p=p: self._h_phase.observe(dt, phase=p))
            for p in PHASE_EVENTS}
        self._c_compiles = reg.counter(
            "engine_compiles_total",
            "calls of a jitted engine program after which its compiled "
            "cache grew", ("program",))
        self._h_spec_window = reg.histogram(
            "engine_spec_accepted_per_window",
            "accepted draft tokens per speculative verify window",
            ("proposer",), buckets=SPEC_WINDOW_BUCKETS)
        self._h_spec_request = reg.histogram(
            "engine_spec_tokens_per_request",
            "accepted/rejected draft tokens per retired request",
            ("kind",), buckets=SPEC_REQUEST_BUCKETS)
        self._c_submitted = reg.counter(
            "engine_requests_submitted_total",
            "requests submitted (first submission only)", ("priority",))
        self._c_finished = reg.counter(
            "engine_requests_finished_total",
            "requests retired (completed or cancelled)")
        self._c_prefix_lookups = reg.counter(
            "engine_prefix_lookups_total",
            "prefix-cache lookups by result", ("result",))
        self._g_queue = reg.gauge(
            "engine_queue_depth", "requests queued on the scheduler")
        self._g_active = reg.gauge(
            "engine_active_slots", "slots actively decoding")
        self._g_staged = reg.gauge(
            "engine_staged_admissions",
            "staged (chunked / warm-prefix) admissions in flight")
        self._g_free = reg.gauge(
            "engine_pool_free_capacity",
            "backend free capacity (dense: slots; paged: blocks)")
        if self.cfg.moe is not None:
            # routed-expert load, from the (MoE layers, held) counts the
            # decode program returns beside its tokens
            self._c_pairs_held = reg.counter(
                "engine_expert_pairs_held_total",
                "decode token-expert pairs routed to an expert held here")
            self._c_pairs_routed = reg.counter(
                "engine_expert_pairs_routed_total",
                "decode token-expert pairs routed (top_k per token per "
                "MoE layer)")
            self._h_expert_busiest = reg.histogram(
                "engine_expert_busiest_tokens",
                "tokens of the busiest held expert of any MoE layer in "
                "one decode step", buckets=EXPERT_LOAD_BUCKETS)
        self._c_kv_read = self._c_kv_table = None
        if self.paged and self.cfg.mla is None and self.cfg.attn_f32:
            # the paged GQA decode whose attention, on the TPU, reads only
            # the live blocks (models.attention): live blocks vs the table
            self._c_kv_read = reg.counter(
                "engine_attn_kv_blocks_read_total",
                "KV pool blocks that hold the active rows' live positions "
                "(the new token's included), summed over decode ticks")
            self._c_kv_table = reg.counter(
                "engine_attn_kv_blocks_table_total",
                "block-table entries of the active rows, summed over decode "
                "ticks")
        reg.gauge(
            "engine_info",
            "static engine identity (value is always 1)",
            ("family", "quant", "paged", "spec"),
        ).set(1, family=family, quant=config.quant or "bf16",
              paged=str(bool(config.paged)).lower(),
              spec=config.spec or "off")
        self._update_gauges()

    def _update_gauges(self):
        """Refresh the level gauges; called at every queue/slot/pool
        transition (all under the engine lock)."""
        self._g_queue.set(self.scheduler.pending)
        self._g_active.set(len(self.active))
        self._g_staged.set(len(self._chunked))
        self._g_free.set(self.backend.free_capacity)

    def _phase(self, name: str, **args):
        """Time one engine phase once, as ``with self._phase(...) as p:``;
        ``p.dur`` holds its seconds after the block.  Always observed into
        ``engine_tick_phase_seconds{phase=name}``; while tracing, also a
        complete span in the tracer's ring and the profiler annotation
        ``engine.<name>`` around the block."""
        return self.tracer.span(name, observe=self._phase_observe[name],
                                **args)

    def _note_submit(self, req: Request):
        """Once-only submit accounting: the counter bumps and the trace
        event fires the FIRST time the engine sees the request, stamped
        at its (possibly harness-pinned) ``submit_ts``."""
        if not req._submit_seen:
            req._submit_seen = True
            self._c_submitted.add(priority=str(req.priority))
            self.tracer.event("submit", rid=req.rid, ts=req.submit_ts,
                              priority=req.priority)

    # --- substrate views (compat surface; the logic lives in backend) ---
    @property
    def paged(self) -> bool:
        return self.backend.paged

    @property
    def allocator(self):
        return getattr(self.backend, "allocator", None)

    @property
    def block_tables(self):
        return getattr(self.backend, "block_tables", None)

    @property
    def idle(self) -> bool:
        """Nothing queued, staged, or decoding."""
        return not (self.active or self._chunked or self.scheduler.pending)

    # --- jit bodies -----------------------------------------------------
    def _prefill_impl(self, params, tokens, slab, last_pos, slots, tables,
                      rids, key):
        """Prefill a (k, L) token bucket against fresh caches, scatter the
        rows into the slab (dense leaves: at slot ids; pool leaves: at
        block tables), sample each row's first token from its own stream."""
        k = tokens.shape[0]
        fresh = self.backend.fresh(k)
        logits, rows = self.model.prefill(params, tokens, fresh,
                                          last_pos=last_pos)
        new_slab = self.backend.scatter(slab, rows, slots, tables)
        toks = sample(logits[:, 0], key, self.sampling, rids=rids,
                      steps=jnp.zeros_like(rids))
        return toks, new_slab

    def _decode_impl(self, params, tokens, caches, positions, tables, rids,
                     steps, key):
        """One decode step; an MoE model's also returns the (MoE layers,
        held experts) int32 counts of the active rows' routed tokens."""
        moe = self.cfg.moe
        if moe is None:
            logits, new_caches = self.model.decode_step(
                params, tokens, caches, positions, tables=tables)
        else:
            logits, new_caches, routes = self.model.decode_step(
                params, tokens, caches, positions, tables=tables,
                routes=True)
        toks = sample(logits[:, 0], key, self.sampling, rids=rids,
                      steps=steps)
        if moe is None:
            return toks, new_caches
        return toks, new_caches, held_load(routes, rids >= 0, moe)

    def _verify_impl(self, params, tokens, caches, positions, tables,
                     n_valid, last_pos):
        """Speculative verify: score the whole (B, W) window in ONE call.
        ``argmax(logits[:, i])`` is the greedy token after column ``i`` —
        bitwise the same reduction the non-speculative tick applies to its
        1-wide logits (spec mode is greedy-only by config), which is what
        pins spec output token-identical to plain decode."""
        logits, new_caches = self.model.decode_window(
            params, tokens, caches, positions, tables=tables,
            n_valid=n_valid, last_pos=last_pos)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32), new_caches

    def _spec_commit_impl(self, params, tokens, caches, positions, tables,
                          n_valid, last_pos):
        """Partial-accept commit on recurrent substrates: re-run the SAME
        window from the PRE-verify cache tree with the SSD scan masked at
        the accept boundary (``last_pos`` = accepted count, -1 for
        inactive rows) so the carried state ingests exactly the accepted
        tokens and nothing after them.  Logits are discarded — the
        verifier already fixed the emitted tokens."""
        _, new_caches = self.model.decode_window(
            params, tokens, caches, positions, tables=tables,
            n_valid=n_valid, last_pos=last_pos)
        return new_caches

    def _draft_impl(self, params, tokens, caches, positions, tables):
        """One greedy self-speculation step over the pruned-LUT draft
        weights against a throwaway functional cache copy."""
        logits, new_caches = self.model.decode_step(
            params, tokens, caches, positions, tables=tables)
        return (jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32),
                new_caches)

    def _chunk_step_impl(self, params, tokens, staging, offset):
        """One mid-prompt chunk: continue the staged (1, stage_len) cache
        at ``offset`` (the trailing-logits matmul is 1 row — negligible)."""
        _, staging = self.model.prefill(params, tokens, staging,
                                        cache_index=offset)
        return staging

    def _chunk_finish_impl(self, params, tokens, staging, offset, last_pos,
                           slab, slots, tables, rid, key):
        """Final chunk: finish the staged row, sample its first token, and
        scatter the whole staged cache into the slab/pool in one go.  The
        finished staging tree is also returned — the prefix cache snapshots
        its recurrent leaves (state at the full prompt boundary)."""
        logits, staging = self.model.prefill(params, tokens, staging,
                                             last_pos=last_pos,
                                             cache_index=offset)
        new_slab = self.backend.scatter(slab, staging, slots, tables)
        tok = sample(logits[:, 0], key, self.sampling, rids=rid,
                     steps=jnp.zeros_like(rid))
        return tok, new_slab, staging

    # --- admission ------------------------------------------------------
    def _validate(self, req: Request):
        if req.max_new < 1:
            raise ValueError(
                f"request {req.rid}: max_new must be >= 1 (prefill always "
                f"samples one token), got {req.max_new}")
        if not (0 < len(req.prompt) <= self.max_seq - 1):
            raise ValueError(
                f"request {req.rid}: prompt length {len(req.prompt)} not in "
                f"[1, max_seq-1={self.max_seq - 1}]")
        self.backend.validate_request(req.rid, len(req.prompt), req.max_new)

    def _reserve(self, req: Request, slot: int, hit=None) -> bool:
        """Claim the request's lifetime substrate capacity up front (paged:
        its block budget; a prefix hit's shared blocks are ref'd
        copy-on-write and only the tail is allocated privately).  False =
        backpressure; dense substrates always succeed."""
        shared = list(hit.blocks) if hit is not None else None
        return self.backend.reserve(slot, len(req.prompt), req.max_new,
                                    shared, on_short=self._on_pool_short)

    def _on_pool_short(self, need: int):
        """Pool pressure hook: let the prefix cache evict LRU unreferenced
        nodes before the reservation backpressures."""
        if self.prefix_cache is not None:
            self.prefix_cache.evict_for(need)
            self._note_evictions()

    def _note_evictions(self):
        """Fold the prefix cache's lifetime eviction count into the
        monotonic engine metrics."""
        if self.prefix_cache is not None:
            d = self.prefix_cache.evictions - self._evictions_seen
            self._evictions_seen = self.prefix_cache.evictions
            self.metrics.cache_evictions += d

    def _free_slot(self, slot: int):
        self.slots[slot] = None
        self.positions[slot] = 0
        self.backend.free_slot(slot)

    def _chunkable(self, prompt_len: int) -> bool:
        return (self.prefill_chunk is not None
                and prompt_len > self.prefill_chunk)

    # --- token emission / retirement ------------------------------------
    def _emit(self, req: Request, tok: int):
        """Append one generated token: the single emission point — output
        list, latency stamp, deadline accounting, stream queues, and
        streaming callbacks all fan out from here."""
        req.out.append(tok)
        ts = self.clock()
        req.token_ts.append(ts)
        if len(req.out) == 1:
            if req.submit_ts is not None:
                self._h_ttft.observe(ts - req.submit_ts,
                                     priority=str(req.priority))
            self.tracer.event("first_token", rid=req.rid, ts=ts)
            if req.deadline is not None:
                if ts > req.deadline:
                    self.metrics.deadline_misses += 1
                else:
                    self.metrics.deadline_hits += 1
        else:
            self._h_itl.observe(ts - req.token_ts[-2],
                                priority=str(req.priority))
            self.tracer.event("token", rid=req.rid, ts=ts)
        for q in self._streams.get(req, ()):
            q.put(tok)
        for cb in tuple(self._callbacks.get(req, ())):
            cb(tok)

    def _retire(self, req: Request):
        if not req.done:
            # _retire can run twice for a request cancelled mid-admission
            # (cancel() retires it, then the admission path retires again
            # on seeing req.done) — the guard keeps finish single-shot
            self.tracer.event("finish", rid=req.rid, tokens=len(req.out),
                              cancelled=req.cancelled)
            self._c_finished.add()
            if self._spec is not None:
                self._h_spec_request.observe(float(req._spec_accepted),
                                             kind="accepted")
                self._h_spec_request.observe(float(req._spec_rejected),
                                             kind="rejected")
        req.done = True
        self._callbacks.pop(req, None)
        for q in self._streams.pop(req, ()):
            q.put(_STREAM_DONE)

    def _subscribe(self, req: Request) -> queue.SimpleQueue:
        """Open a token stream over ``req``: a fresh queue preloaded (under
        the lock) with everything already emitted, then fed by ``_emit``
        and closed with the sentinel by ``_retire`` — so a late subscriber
        replays the backlog and no token is ever missed or duplicated."""
        with self._lock:
            q = queue.SimpleQueue()
            for tok in req.out:
                q.put(tok)
            if req.done:
                q.put(_STREAM_DONE)
            else:
                self._streams.setdefault(req, []).append(q)
            return q

    # --- prefix cache ---------------------------------------------------
    def _match_prefix(self, req: Request):
        """Longest cached prefix usable for this admission (None = cold).
        At least one tail token must still run through prefill to produce
        the last-position logits, hence the ``len - 1`` cap."""
        if self.prefix_cache is None:
            return None
        hit = self.prefix_cache.match(req.prompt,
                                      max_len=len(req.prompt) - 1,
                                      need_state=self.backend.needs_state)
        self._c_prefix_lookups.add(
            result="hit" if hit is not None else "miss")
        return hit

    def _capture_boundary(self, prompt_len: int) -> int:
        """Grid boundary to snapshot recurrent state at (0 = none)."""
        return (prompt_len // self._capture_grid) * self._capture_grid

    def _route_staged(self, req: Request, hit, lone: bool = True) -> bool:
        """True when the admission must ride the staged path: chunked long
        prompts, every warm hit (the staging row is seeded from the cache),
        and LONE cold recurrent admissions that want a mid-prompt state
        snapshot (the prefill is split at the grid boundary to capture it).
        ``lone=False`` — other cold requests are being admitted this tick —
        keeps cold recurrent prompts on the batched bucket path: concurrent
        cold prefill throughput beats an extra capture boundary (the cache
        still populates from their full-prompt inserts and from warm /
        chunked admissions)."""
        if hit is not None or self._chunkable(len(req.prompt)):
            return True
        if not lone or self.prefix_cache is None \
                or not self.backend.needs_state:
            return False
        cap = self._capture_boundary(len(req.prompt))
        return 0 < cap < len(req.prompt)

    def _seed_staging(self, hit):
        """Build the warm admission's staging row: gather the shared
        blocks' KV into the dense staging leaves (one jit call, compiled
        once) and swap in the recurrent state snapshot.  The tail prefill
        then continues at ``hit.length`` as if the first chunks had just
        run."""
        if hit.blocks:
            tbl = jnp.asarray(self.backend.staging_table(hit.blocks))
            staging = self._seed_gather(self.caches, tbl)
        else:
            staging = self.backend.fresh(1)
        if hit.state is not None:
            staging = self.backend.seed_snapshot(staging, hit.state)
        return staging

    def _insert_boundary(self, prompt: list[int], slot: int, state):
        """Cache one finished-prefill boundary: the backend's
        ``prefix_payload`` is THE per-family storage policy (ssm: state
        snapshot only; attention: whole pool blocks; hybrid: both halves at
        a block-aligned boundary)."""
        payload = self.backend.prefix_payload(prompt, slot, state)
        if payload is None:
            return
        tokens, blocks, state = payload
        self.prefix_cache.insert(tokens, blocks=blocks, state=state)

    def _prefix_insert_from_slot(self, req: Request, slot: int):
        """Cold batched admission: cache the freshly-prefilled prefix —
        state (if the substrate carries one) sliced from the slot's cache
        row at the full prompt boundary."""
        if self.prefix_cache is None:
            return
        state = self.backend.snapshot(self.caches, slot)
        self._insert_boundary(req.prompt, slot, state)
        self._note_evictions()

    def _finish_prefix_insert(self, cp: _ChunkedPrefill, staged_out):
        """Staged admission done: insert the mid-prompt capture (if one was
        taken) and the full-prompt boundary into the radix tree."""
        if self.prefix_cache is None:
            return
        req, slot = cp.req, cp.slot
        if cp.captured is not None:
            self._insert_boundary(req.prompt[:cp.capture_at], slot,
                                  cp.captured)
        state = self.backend.snapshot(staged_out, 0)
        self._insert_boundary(req.prompt, slot, state)
        self._note_evictions()

    # --- public API -----------------------------------------------------
    def submit(self, req: Request, *, on_token=None) -> RequestHandle:
        """Submit one request; thread-safe.  The returned handle is truthy
        iff the request was admitted immediately.  On backpressure (no
        free slot, or — paged — the block pool is short): with the
        background loop running the request is left QUEUED on the
        scheduler and the loop admits it in priority order as capacity
        frees (the falsy handle still streams); without the loop it is
        NOT queued — retry, or hand it to ``serve()``.  Long prompts under
        ``prefill_chunk`` start a chunked admission that ``step()``
        advances one chunk per tick.  ``on_token`` fires synchronously
        for every emitted token."""
        with self._lock:
            self._validate(req)
            if req.submit_ts is None:
                req.submit_ts = self.clock()
            self._note_submit(req)
            handle = RequestHandle(self, req, on_token=on_token)
            if self.running:
                # loop mode: register the callback for the whole queued
                # lifetime (the loop admits later, off this thread) and
                # fall back to the scheduler instead of dropping the
                # request on backpressure
                if on_token is not None:
                    cbs = self._callbacks.setdefault(req, [])
                    if on_token not in cbs:
                        cbs.append(on_token)
                handle._admitted = self._try_admit(req)
                if not handle._admitted and not req.done \
                        and not self.scheduler.queued(req):
                    self.scheduler.push(req)
                    self.tracer.event("queue", rid=req.rid)
            else:
                handle._admitted = self._admit_handle(handle)
            self._update_gauges()
        self._loop_wake.set()
        return handle

    def _admit_handle(self, handle: RequestHandle) -> bool:
        """Admission attempt for a handle: the streaming callback is live
        exactly while the request is admitted — registered before the
        attempt (the prefill emits the first token synchronously) and
        unregistered again on failure, so an abandoned falsy handle leaks
        nothing onto later requests."""
        with self._lock:
            req, cb = handle.req, handle._on_token
            if req.done:
                return False              # finished/cancelled: nothing to
            if cb is not None:            # admit, nothing to register
                cbs = self._callbacks.setdefault(req, [])
                if cb not in cbs:         # idempotent: a backpressured
                    cbs.append(cb)        # submit retried with the same
                # callback must not double-fire per token
            admitted = self._try_admit(req)
            if not admitted and cb is not None:
                cbs = self._callbacks.get(req, [])
                if cb in cbs:
                    cbs.remove(cb)
                if not cbs:
                    self._callbacks.pop(req, None)
            return admitted

    def _try_admit(self, req: Request) -> bool:
        """One admission attempt, sharing the scheduler's state.

        * Stall bookkeeping: a request whose reservation already failed
          retries only once capacity has actually grown (no radix-tree
          re-walk, no refcount churn on every poll).
        * Queue fairness: a direct admission must not leapfrog queued work
          of equal-or-higher effective priority (the scheduler's
          starvation/inversion bounds hold engine-wide), ages the queue
          when it does win, and claims the request's own stale queue entry
          so a request can never be admitted twice."""
        if req.done:
            return False
        if self.active.get(req.rid) is req or \
                any(cp.req is req for cp in self._chunked):
            return True                       # already admitted
        self._check_rid_free(req)
        if self._admitting:
            # re-entrant submit from an on_token callback while _admit is
            # mid-flight: the in-flight request's slot is not recorded yet
            # and must not be stolen — report backpressure instead
            return False
        free = [s for s, r in enumerate(self.slots) if r is None]
        if not free:
            return False
        head = self.scheduler.select()
        if head is not None and head.req is not req and \
                self.scheduler.effective_priority(head) >= req.priority:
            # queued work outranks (or ties) this direct submit: let the
            # next tick's _admit_pending serve the queue first
            return False
        need = self.backend.reservation_need(len(req.prompt), req.max_new)
        if self.scheduler.stalled(req.rid, self.backend.free_capacity,
                                  need):
            return False
        hit = self._match_prefix(req)
        if not self._reserve(req, free[0], hit):
            self.scheduler.note_stall(req.rid, self.backend.free_capacity,
                                      need)
            return False
        self.scheduler.clear_stall(req.rid)
        self.scheduler.remove(req)            # claim our own stale entry
        self.scheduler.age_all()
        if self._route_staged(req, hit):
            self._start_staged(req, free[0], hit)
        else:
            self._admit([req], free[:1])
        return True

    def _check_rid_free(self, req: Request):
        """Rids must be unique among LIVE requests (the active dict, the
        sampling streams, and the metrics all key on them): admitting a
        different object under a live rid corrupts both streams."""
        if req.rid in self.active or \
                any(cp.req.rid == req.rid for cp in self._chunked):
            raise ValueError(
                f"rid {req.rid} is already in flight for a different "
                "request — rids must be unique among live requests")

    def cancel(self, req: Request) -> bool:
        """Cancel wherever the request is in its lifecycle: drop it from
        the scheduler queue, abort a mid-flight staged admission (staged
        cache rows and snapshot dropped, reserved blocks — including
        copy-on-write shared prefix refs — released, pool accounting
        exact), or stop an active decode and free its slot.  A request
        found nowhere (mid-admission emit — e.g. an ``on_token`` callback
        cancelling its own request — or never admitted) is marked done;
        the admission paths check ``req.done`` after every emit and
        release the slot themselves.  False if already finished.  Safe
        from any thread: the whole teardown runs under the engine lock,
        atomically with respect to the loop's tick."""
        with self._lock:
            if req.done:
                return False
            if self.scheduler.remove(req):
                self._finish_cancel(req)
                return True
            for cp in self._chunked:
                if cp.req is req:
                    self._chunked.remove(cp)
                    self._free_slot(cp.slot)
                    self._finish_cancel(req)
                    return True
            if self.active.get(req.rid) is req:
                del self.active[req.rid]
                for s, r in enumerate(self.slots):
                    if r is req:
                        self._free_slot(s)
                        break
                self._finish_cancel(req)
                return True
            self._finish_cancel(req)
            return True

    def preempt(self, req: Request) -> bool:
        """Kick an ACTIVE request off its slot and requeue it: the slot
        and its reservation are released through the same exact-accounting
        teardown as :meth:`cancel`, the tokens emitted so far are folded
        into the prompt, and the request goes back on the scheduler — its
        re-admission re-prefills the extended prompt, so the continued
        greedy stream is token-identical to never having been preempted
        (pinned; sampled streams restart their per-token step counter at
        the new prefill boundary).  False if the request is not actively
        decoding (queued/staged requests hold no decode slot worth
        stealing) or the extended prompt would not fit ``max_seq``."""
        with self._lock:
            if req.done or self.active.get(req.rid) is not req:
                return False
            if len(req.prompt) + len(req.out) > self.max_seq - 1:
                return False       # nothing left to decode after requeue
            del self.active[req.rid]
            for s, r in enumerate(self.slots):
                if r is req:
                    self._free_slot(s)
                    break
            self.scheduler.clear_stall(req.rid)
            req.prompt = list(req.prompt) + list(req.out)
            self.scheduler.push(req)
            self.metrics.preemptions += 1
            self.tracer.event("preempt", rid=req.rid)
            self.tracer.event("queue", rid=req.rid)
            self._update_gauges()
        self._loop_wake.set()
        return True

    def _finish_cancel(self, req: Request):
        req.cancelled = True
        self.scheduler.clear_stall(req.rid)
        self.tracer.event("cancel", rid=req.rid)
        self._retire(req)
        self.metrics.cancelled += 1
        self._update_gauges()

    def _bucket_len(self, n: int) -> int:
        return min(ceil_div(n, self.prefill_bucket) * self.prefill_bucket,
                   self.max_seq)

    def _admit(self, reqs: list[Request], slots: list[int]):
        """Prefill ``reqs`` into ``slots`` — one jit call per length bucket,
        one cache scatter per bucket (no per-row update round-trips).
        Callers must have ``_validate``d (and ``_reserve``d) each request
        first."""
        assert len(reqs) == len(slots)
        for r, s in zip(reqs, slots):
            self.tracer.event("admit", rid=r.rid, slot=s, staged=False)
        prev_admitting = self._admitting
        self._admitting = True
        try:
            self._admit_buckets(reqs, slots)
        finally:
            self._admitting = prev_admitting

    def _admit_buckets(self, reqs: list[Request], slots: list[int]):
        buckets: dict[int, list[int]] = {}
        for i, r in enumerate(reqs):
            buckets.setdefault(self._bucket_len(len(r.prompt)), []).append(i)
        for blen, idxs in buckets.items():
            k = len(idxs)
            toks = np.zeros((k, blen), np.int32)
            last = np.zeros(k, np.int32)
            for j, i in enumerate(idxs):
                p = reqs[i].prompt
                toks[j, :len(p)] = p
                last[j] = len(p) - 1
            slot_ids = jnp.asarray([slots[i] for i in idxs])
            tables = self.backend.admission_tables([slots[i] for i in idxs])
            rids = jnp.asarray([reqs[i].rid for i in idxs], jnp.int32)
            with self._phase("prefill", batch=k) as ph:
                nxt, self.caches = self._prefill(
                    self.params, jnp.asarray(toks), self.caches,
                    jnp.asarray(last), slot_ids, tables, rids, self.key)
                nxt = np.asarray(nxt)      # sync for honest wall-clock
            self.metrics.prefill_s += ph.dur
            self.metrics.prefill_calls += 1
            for j, i in enumerate(idxs):
                req, slot = reqs[i], slots[i]
                self._emit(req, int(nxt[j]))
                self.metrics.prefill_tokens += len(req.prompt)
                self._prefix_insert_from_slot(req, slot)
                if req.done or len(req.out) >= req.max_new:
                    # cap already met by the prefill-sampled token
                    # (max_new=1: done at admission, never decode-ticked)
                    # — or an on_token callback cancelled the request
                    # mid-emit, before it ever joined a slot
                    self._retire(req)
                    self.backend.free_slot(slot)
                    continue
                self.positions[slot] = len(req.prompt)
                self.slots[slot] = req
                self.active[req.rid] = req

    # --- staged (chunked / warm-prefix) prefill -------------------------
    def _start_staged(self, req: Request, slot: int, hit=None):
        """Reserve ``slot`` for a staged admission.  The prompt is fed to a
        staged 1-row cache — one chunk per tick under ``prefill_chunk``,
        synchronously otherwise — and the request only joins ``active``
        (decode) once the last piece lands.  A prefix ``hit`` seeds the
        staging row (shared blocks gathered + state snapshot) and skips the
        first ``hit.length`` prompt tokens; the final scatter of a warm
        paged admission redirects the shared-block range to the garbage
        block so a shared block is never written in place (copy-on-write)."""
        self.slots[slot] = req
        self.positions[slot] = 0
        consumed, scatter_table = 0, None
        if hit is not None:
            staging = self._seed_staging(hit)
            consumed = hit.length
            scatter_table = self.backend.cow_table(slot, len(hit.blocks))
            self.metrics.prefix_hits += 1
            self.metrics.prefix_tokens_reused += consumed
        else:
            staging = self.backend.fresh(1)
        self.tracer.event("admit", rid=req.rid, slot=slot, staged=True,
                          reused=consumed)
        cap = None
        if self.prefix_cache is not None and self.backend.needs_state:
            c = self._capture_boundary(len(req.prompt))
            if consumed < c < len(req.prompt):
                cap = c
        cp = _ChunkedPrefill(req, slot, staging, consumed, capture_at=cap,
                             scatter_table=scatter_table)
        self._chunked.append(cp)
        if self.prefill_chunk is None:
            # no chunked scheduling: drive the staged admission to
            # completion now, preserving admit-at-submit semantics (cp is
            # the only queue entry — earlier ones all drained the same way)
            while self._chunked and self._chunked[0] is cp:
                self._advance_chunked()

    def _advance_chunked(self):
        """Run AT MOST one prefill piece (FIFO head) — this bounds the
        prefill work any decode tick waits on to one chunk.  Pieces are cut
        at the state-capture grid boundary so the prefix cache can snapshot
        the staged recurrent state mid-prompt."""
        if not self._chunked:
            return
        cp = self._chunked[0]
        req = cp.req
        remaining = len(req.prompt) - cp.consumed
        c = self.prefill_chunk if self.prefill_chunk is not None \
            else remaining
        if cp.capture_at is not None and cp.consumed < cp.capture_at:
            c = min(c, cp.capture_at - cp.consumed)
        if remaining > c:
            with self._phase("prefill", batch=1) as ph:
                toks = np.asarray(req.prompt[cp.consumed:cp.consumed + c],
                                  np.int32)[None]
                cp.staging = self._chunk_step(
                    self.params, jnp.asarray(toks), cp.staging,
                    jnp.int32(cp.consumed))
                jax.block_until_ready(cp.staging)
            cp.consumed += c
            self.metrics.prefill_s += ph.dur
            self.metrics.prefill_tokens += c
            self.metrics.prefill_calls += 1
            if self.prefill_chunk is not None:
                self.metrics.prefill_chunks += 1
                self.tracer.event("prefill_chunk", rid=req.rid, ts=ph.t0,
                                  consumed=cp.consumed)
            if cp.capture_at == cp.consumed:
                cp.captured = self.backend.snapshot(cp.staging, 0)
            return
        # final piece: pad to the bucket grid (static shapes), sample the
        # request's first token, scatter the staged row into the slab/pool
        self._chunked.pop(0)
        with self._phase("prefill", batch=1) as ph:
            pl = min(self._bucket_len(remaining),
                     self.backend.stage_len - cp.consumed)
            toks = np.zeros((1, pl), np.int32)
            toks[0, :remaining] = req.prompt[cp.consumed:]
            slot_ids = jnp.asarray([cp.slot])
            tables = self.backend.finish_tables(cp.slot, cp.scatter_table)
            nxt, self.caches, staged_out = self._chunk_finish(
                self.params, jnp.asarray(toks), cp.staging,
                jnp.int32(cp.consumed), jnp.asarray([remaining - 1]),
                self.caches, slot_ids, tables,
                jnp.asarray([req.rid], jnp.int32), self.key)
            nxt = np.asarray(nxt)
        self.metrics.prefill_s += ph.dur
        self.metrics.prefill_tokens += remaining
        self.metrics.prefill_calls += 1
        if self.prefill_chunk is not None:
            self.metrics.prefill_chunks += 1
            self.tracer.event("prefill_chunk", rid=req.rid, ts=ph.t0,
                              consumed=len(req.prompt))
        self._finish_prefix_insert(cp, staged_out)
        self._emit(req, int(nxt[0]))
        if req.done or len(req.out) >= req.max_new:
            # cap met, or an on_token callback cancelled mid-emit
            self._retire(req)
            self._free_slot(cp.slot)
            return
        self.positions[cp.slot] = len(req.prompt)
        self.active[req.rid] = req

    # --- scheduler-driven admission -------------------------------------
    def _admit_pending(self):
        """Admit queued requests into free slots, highest effective
        priority first (deadline tie-break, one-bucket aging — see
        :class:`Scheduler`).  Cold same-tick admissions batch into one
        bucketed prefill call; a failed reservation stalls admission
        (head-of-line) until capacity grows."""
        free = [s for s, r in enumerate(self.slots) if r is None]
        batch: list[Request] = []
        batch_slots: list[int] = []
        while self.scheduler.pending and free:
            entry = self.scheduler.select()
            req = entry.req
            need = self.backend.reservation_need(len(req.prompt),
                                                 req.max_new)
            if self.scheduler.stalled(req.rid, self.backend.free_capacity,
                                      need):
                break
            try:
                self._validate(req)
                self._check_rid_free(req)
                if any(b.rid == req.rid for b in batch):
                    raise ValueError(
                        f"rid {req.rid} queued twice in one admission "
                        "tick — rids must be unique among live requests")
            except ValueError:
                # direct scheduler pushes bypass serve()'s pre-validation:
                # evict the poison entry so the queue stays serviceable,
                # flush the requests already committed this tick (their
                # blocks are reserved — dropping them would leak the
                # reservation and hang their callers), then surface the
                # error once
                self.scheduler.drop(entry)
                self._retire(req)
                if batch:
                    self._admit(batch, batch_slots)
                raise
            hit = self._match_prefix(req)
            if not self._reserve(req, free[0], hit):
                self.scheduler.note_stall(req.rid,
                                          self.backend.free_capacity, need)
                break          # head-of-line: wait for capacity to free
            self.scheduler.clear_stall(req.rid)
            self.scheduler.commit(entry)
            slot = free.pop(0)
            lone = not batch and not self.scheduler.pending
            if self._route_staged(req, hit, lone):
                self._start_staged(req, slot, hit)
            else:
                batch.append(req)
                batch_slots.append(slot)
        if batch:
            self._admit(batch, batch_slots)

    # --- decode ---------------------------------------------------------
    def step(self):
        """One engine tick, under the engine lock — the public, thread-safe
        spelling of :meth:`_tick` (safe to call even while the background
        loop runs: ticks serialize on the lock)."""
        with self._lock:
            self._tick()

    def _tick(self):
        """One engine tick: admit queued work into free slots, run at most
        one chunk of pending prefill, then every active slot advances one
        token at its own position (free or still-admitting rows compute
        masked garbage that is ignored — a mid-admission slot's garbage
        writes are fully overwritten by its final staged-cache scatter).
        Re-entrant (the lock is an RLock) and caller-agnostic: the
        synchronous ``serve()``/``step()`` path and the background loop
        both drive exactly this body, which is what pins loop-mode output
        token-identical to sync output.  Callers MUST hold the engine
        lock."""
        with self._phase("admit"):
            self._admit_pending()
            self._advance_chunked()
        if not self.active:
            self._update_gauges()
            return
        if self._spec is None or not self._spec_tick():
            self._decode_tick()
        self._update_gauges()

    def _decode_tick(self):
        """The plain one-token decode advance: every active slot steps one
        token at its own position.  Also the speculative mode's fallback
        for ticks where no slot produced a draft — spec mode degrades to
        exactly this path, never stalls."""
        with self._phase("decode_inputs"):
            toks = np.zeros((self.max_batch, 1), np.int32)
            rids = np.full(self.max_batch, -1, np.int32)
            steps = np.zeros(self.max_batch, np.int32)
            n_active = 0
            for s, req in enumerate(self.slots):
                if req is not None and req.rid in self.active:
                    toks[s, 0] = req.out[-1]
                    rids[s] = req.rid
                    steps[s] = len(req.out)
                    n_active += 1
            tables = self.backend.decode_tables([cp.slot for cp in
                                                 self._chunked])
            args = (jnp.asarray(toks), self.caches,
                    jnp.asarray(self.positions), tables, jnp.asarray(rids),
                    jnp.asarray(steps))
        with self._phase("decode", batch=n_active) as ph:
            out = self._decode(self.decode_params, *args, self.key)
            self.caches = out[1]
            del args               # frees the old caches, as before
            with self._phase("decode_wait"):
                # one transfer: the tokens, and an MoE step's expert load
                got = jax.device_get((out[0],) + out[2:])
            nxt = got[0]
        self.metrics.decode_s += ph.dur
        self.metrics.ticks += 1
        self.metrics.occupancy_sum += n_active
        self.metrics.decode_tokens += n_active
        if len(got) > 1:
            self._note_expert_load(got[1], n_active)
        if self._c_kv_read is not None:
            # a row at position p holds its live positions in
            # ceil((p + 1) / bs) blocks
            bs = self.backend.block_size
            self._c_kv_read.add(int(np.sum(
                (self.positions[rids >= 0] + bs) // bs)))
            self._c_kv_table.add(n_active * self.backend.blocks_per_row)
        with self._phase("emit"):
            for s, req in enumerate(self.slots):
                if req is None or req.rid not in self.active:
                    continue
                self._emit(req, int(nxt[s]))
                if req.done:
                    # an on_token callback cancelled from inside the emit:
                    # cancel() already freed the slot and active entry
                    continue
                self.positions[s] += 1
                if len(req.out) >= req.max_new or \
                        self.positions[s] >= self.max_seq - 1:
                    self._retire(req)
                    self.active.pop(req.rid, None)
                    self._free_slot(s)

    def _note_expert_load(self, load: np.ndarray, n_active: int):
        """Count one decode step's (MoE layers, held experts) load."""
        self._c_pairs_held.add(int(load.sum()))
        self._c_pairs_routed.add(n_active * self.cfg.moe.top_k
                                 * load.shape[0])
        self._h_expert_busiest.observe(float(load.max()))

    def _spec_tick(self) -> bool:
        """One speculative advance: draft -> batched verify -> accept
        prefix -> rollback (see :mod:`repro.serve.spec` for the contract).
        False when no slot produced a draft — the caller then runs the
        plain :meth:`_decode_tick`, so speculation can only add tokens per
        tick, never lose them.

        Per-row draft budget: ``k_eff`` caps the window so the emitted
        ``accepted + 1`` tokens can never overrun ``max_new`` or write
        past ``max_seq - 1`` (the same retire boundary the plain tick
        enforces), hence retire checks below stay identical to
        :meth:`_decode_tick`'s.

        Cache discipline: verify runs one ``decode_window`` call against
        the live tree.  Rejected-position writes are dead weight on
        attention substrates (``CacheBackend.rollback`` is bookkeeping
        only; later writes land over them), but a recurrent state has
        already INGESTED the rejected tokens — so on a partial accept the
        window is re-run from the saved pre-verify tree with the SSD scan
        masked at each row's accept boundary (full acceptance skips the
        second pass: the verify-pass state is exactly the committed
        state)."""
        spec_k = self.config.spec_k
        reqs: list[Request | None] = [None] * self.max_batch
        k_eff = np.zeros(self.max_batch, np.int64)
        for s, req in enumerate(self.slots):
            if req is not None and req.rid in self.active:
                reqs[s] = req
                limit = min(len(req.prompt) + req.max_new, self.max_seq)
                k_eff[s] = max(0, min(
                    spec_k,
                    req.max_new - len(req.out) - 1,
                    limit - 2 - int(self.positions[s])))
        with self._phase("draft") as ph:
            drafts = self._spec.propose(reqs, k_eff.tolist())
            total = sum(len(d) for d in drafts)
            ph.args["drafted"] = total
        if total == 0:
            return False
        self.metrics.spec_drafted += total
        pre = self.caches
        with self._phase("decode_inputs"):
            toks = np.zeros((self.max_batch, spec_k + 1), np.int32)
            n_valid = np.zeros(self.max_batch, np.int32)
            n_active = 0
            for s, req in enumerate(reqs):
                if req is None:
                    continue
                d = drafts[s]
                toks[s, 0] = req.out[-1]
                toks[s, 1:1 + len(d)] = d
                n_valid[s] = 1 + len(d)
                n_active += 1
            tables = self.backend.decode_tables([cp.slot for cp in
                                                 self._chunked])
            args = (jnp.asarray(toks), pre, jnp.asarray(self.positions),
                    tables, jnp.asarray(n_valid))
            last = jnp.asarray(n_valid - 1)
        with self._phase("verify", batch=n_active) as ph:
            tgt, post = self._verify(self.decode_params, *args, last)
            with self._phase("verify_wait"):
                tgt = np.asarray(tgt)
        self.metrics.decode_s += ph.dur
        self.metrics.ticks += 1
        self.metrics.spec_ticks += 1
        self.metrics.occupancy_sum += n_active
        accepts = np.zeros(self.max_batch, np.int32)
        partial = False
        for s, req in enumerate(reqs):
            if req is not None:
                accepts[s] = accept_length(drafts[s], tgt[s])
                partial = partial or accepts[s] < len(drafts[s])
        if self.backend.needs_state and partial:
            commit_last = np.where(n_valid > 0, accepts, -1)
            with self._phase("verify", batch=n_active, commit=True) as ph:
                self.caches = self._spec_commit(
                    self.decode_params, *args,
                    jnp.asarray(commit_last.astype(np.int32)))
                with self._phase("verify_wait"):
                    jax.block_until_ready(self.caches)
            self.metrics.decode_s += ph.dur
        else:
            self.caches = post
        del args
        with self._phase("emit"):
            emitted_total = 0
            for s, req in enumerate(reqs):
                if req is None or req.done or self.slots[s] is not req or \
                        req.rid not in self.active:
                    continue   # a callback on an earlier row tore it down
                m = int(accepts[s])
                rejected = len(drafts[s]) - m
                req._spec_accepted += m
                req._spec_rejected += rejected
                self.metrics.spec_accepted += m
                self.metrics.spec_rejected += rejected
                self._h_spec_window.observe(float(m),
                                            proposer=self._spec.name)
                for i in range(m + 1):
                    self._emit(req, int(tgt[s, i]))
                    emitted_total += 1
                    if req.done or self.slots[s] is not req:
                        # an on_token callback cancelled/preempted this
                        # row mid-window: the teardown already released the
                        # slot — stop emitting and leave its bookkeeping
                        # alone
                        break
                else:
                    self.positions[s] += m + 1
                    if rejected:
                        self.backend.rollback(s, rejected)
                    if len(req.out) >= req.max_new or \
                            self.positions[s] >= self.max_seq - 1:
                        self._retire(req)
                        self.active.pop(req.rid, None)
                        self._free_slot(s)
            self.metrics.decode_tokens += emitted_total
        return True

    def serve(self, requests: list[Request], max_ticks: int = 512) -> dict:
        """Queue ``requests`` on the scheduler and run to completion (or
        ``max_ticks``): every tick admits queued requests into free slots
        in priority order (paged mode backpressures the head of the queue
        when the block pool is short), then decodes.  Returned stats cover
        THIS call only (``Engine.metrics`` keeps lifetime totals);
        requests still queued at ``max_ticks`` stay queued for the next
        ``serve()``/``step()`` call.  Requests are validated BEFORE they
        are queued — an invalid one raises here and nothing is enqueued
        (the persistent scheduler must never hold a request admission
        would reject forever)."""
        with self._lock:
            for r in requests:
                self._validate(r)
            now = self.clock()
            for r in requests:
                if r.submit_ts is None:
                    r.submit_ts = now
                self._note_submit(r)
                self.scheduler.push(r)
                self.tracer.event("queue", rid=r.rid)
            self._update_gauges()
            start = self.metrics.snapshot()
        t0 = self.clock()
        ticks = 0
        while (self.scheduler.pending or self.active or self._chunked) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        stats = self.metrics.since(start).summary(self.max_batch)
        stats.update({"wall_s": self.clock() - t0, "ticks": ticks,
                      "done": all(r.done for r in requests)})
        return stats

    # --- background serve loop ------------------------------------------
    @property
    def running(self) -> bool:
        """True while the background serve loop thread is alive."""
        t = self._loop_thread
        return t is not None and t.is_alive()

    def start(self) -> "Engine":
        """Run the engine tick on a background daemon thread until
        :meth:`stop`.  While running, ``submit()`` is the only client
        surface needed: handles stream via :meth:`RequestHandle.tokens`
        without anyone ticking the engine, and backpressured submits queue
        on the scheduler instead of bouncing.  Idempotent (a second
        ``start()`` on a running engine is a no-op); returns ``self`` so
        ``eng = Engine(...).start()`` reads naturally."""
        with self._lock:
            if self.running:
                return self
            self._loop_stop.clear()
            self._loop_wake.clear()
            self._drain_on_stop = True
            self._loop_thread = threading.Thread(
                target=self._serve_loop, name="engine-serve-loop",
                daemon=True)
            self._loop_thread.start()
        return self

    def stop(self, drain: bool = True, timeout: float | None = None):
        """Stop the background loop.  ``drain=True`` (default) keeps
        ticking until every queued, staged, and active request has
        finished before the thread exits — no token already submitted is
        lost.  ``drain=False`` exits at the next tick boundary; unfinished
        requests stay queued/active and a later ``start()``, ``serve()``
        or ``step()`` resumes them exactly where they stopped (state is
        only mutated under the lock, never torn down).  ``timeout`` bounds
        the join; returns True if the thread exited in time."""
        t = self._loop_thread
        if t is None or not t.is_alive():
            self._loop_thread = None
            return True
        self._drain_on_stop = drain
        self._loop_stop.set()
        self._loop_wake.set()
        t.join(timeout)
        alive = t.is_alive()
        if not alive:
            self._loop_thread = None
        return not alive

    def _serve_loop(self):
        """Loop body: tick while there is work, sleep ``idle_backoff_s``
        while there is none (a ``submit``/``cancel``/``preempt``/``stop``
        wakes the sleep immediately).  Every tick runs under the engine
        lock; between ticks the lock is released so client threads can
        submit/cancel without waiting out a whole generation."""
        backoff = max(self.config.idle_backoff_s, 1e-4)
        while True:
            with self._lock:
                worked = not self.idle
                if worked:
                    self._tick()
                drained = self.idle
            if self._loop_stop.is_set() and (drained
                                             or not self._drain_on_stop):
                return
            if not worked:
                with self._phase("loop_idle"):
                    self._loop_wake.wait(backoff)
                self._loop_wake.clear()
