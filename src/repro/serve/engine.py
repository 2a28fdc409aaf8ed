"""Tiered async serving engine: batch-tier decode captures, batched and
chunked prefill admission, a double-buffered host loop, and a hardened
request lifecycle (admission control, deadlines, preemption, fault
isolation).

The runtime dispatcher half of the paper's §3.3.2 story, grown into the
shape the backend thesis demands — a runtime that "manages complex
control/data-flow asynchronously" and "uses custom memory management to
eliminate copy overheads":

  * **Decode batch tiers.**  Decode captures are built at power-of-two
    batch tiers (1, 2, 4, …, ``max_batch``); each step runs the smallest
    tier covering the active rows instead of always paying ``max_batch``
    worth of compute.  Tiers 2..N never re-lower: the decode (graph,
    plan) pair is *structurally* identical across batch sizes, so the
    ``PlanStore`` derives every further tier from one canonical lowering
    via ``specialize()`` (the batch dimension is just another rewritten
    shape bucket; the inner store key carries the tier).  Active rows are
    compacted into the low slots on tier shrink so the tier prefix is
    always dense.

  * **Batched + chunked prefill.**  ``_admit`` packs several waiting
    requests into one bucketed prefill call (a real batch dimension with
    per-row lengths), and prompts longer than the largest bucket run as
    chunked prefill steps through the *decode* graph at chunk-sized
    query length — cached attention where chunk position ``j`` sees
    ``cache_len + j + 1`` keys — instead of crashing.  Chunk dispatch is
    **fair**: each engine iteration admits every waiting whole-prompt
    group first and then issues *one* chunk of the oldest in-progress
    chunked prefill (round-robin), so a long prompt never monopolizes
    dispatch for ``len/chunk`` consecutive iterations and short requests
    submitted behind it keep their TTFT.

  * **Async host loop.**  Sampling is on-device (argmax + eos/length
    masks inside the jitted decode step), prefill KV lands in the cache
    pool via ``dynamic_update_slice`` inside the jitted prefill step
    (donated buffers — no host-side numpy slicing on the copy path), and
    decode steps chain their sampled tokens on-device through a
    ``last_ids`` vector.  The host loop is double-buffered: step k+1 is
    dispatched before step k's small token/done vector is fetched with a
    single ``jax.device_get`` — one host sync per decode iteration
    instead of one per token-row.

  * **Request lifecycle.**  Robustness policy is decoupled from the
    dispatch machinery the same way execution policy is decoupled from
    the model (the paper's transparency claim, applied to survival):

      - *Admission control* — a pluggable ``AdmissionPolicy``
        (``serve/admission.py``) decides per request against a load
        snapshot; load shedding terminates a request as a typed
        ``Shed(reason)`` result instead of stranding it in the queue.
        Expired deadlines/TTFT budgets always shed (built-in gate).
      - *Preempt-and-requeue* — under memory pressure or when a
        higher-priority request is waiting on a full pool, the
        lowest-priority decoding row is evicted (KV row released, its
        generated tokens snapshotted host-side) and later re-admitted as
        a re-prefill over ``prompt + generated`` — through the existing
        batched or chunked prefill path, preserving the
        ≤1-sync-per-decode discipline.  Greedy decode makes the resumed
        token stream bitwise-identical to an uninterrupted run.
      - *Fault isolation* — dispatch and harvest are wrapped in
        per-request error boundaries: a targeted ``PoisonedRequest``
        terminates exactly that request as ``Failed(reason)`` and the
        dispatch retries with the survivors; an untargeted fault fails
        only the requests in that dispatch.  The engine itself never
        dies.
      - *Graceful drain* — ``drain(timeout)`` stops admitting, finishes
        in-flight rows, checkpoints the PlanStore, and reports (and
        releases) stranded work; ``shutdown()`` aborts in-flight work
        and still checkpoints.
      - *Chaos harness* — ``ServeConfig.faults`` threads a deterministic
        ``FaultInjector`` (``serve/faults.py``) through every injection
        site: allocation denial, poisoned/failed dispatches, slow
        iterations, and memory-pressure windows that shrink the KV
        pool's effective capacity.

    Every submitted request terminates in exactly one of ``Finished`` /
    ``Shed`` / ``Failed`` (``Request.result``), mirrored by the
    lifecycle counters in ``stats``.

Set ``ServeConfig(decode_tiers=(max_batch,), prefill_batch=1,
async_host=False)`` to recover the synchronous fixed-batch baseline
(benchmarked in ``benchmarks/serve_bench.py``).

The engine is single-host/mesh-free here (tp=1); the launch layer wraps
the same step functions in shard_map for the production mesh.
"""
from __future__ import annotations

import collections
import copy
import dataclasses
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..core.plan_store import PlanStore, resolve_plan_store
from ..core.scheduler import ScheduleContext
from ..models.base import build_forward
from ..spans import span
from .admission import (
    AdmissionContext,
    ChunkingDisabled,
    DeadlineExceeded,
    DeadlineGate,
    EmptyPrompt,
    EngineDraining,
    Failed,
    Finished,
    Overloaded,
    PromptOverflow,
    Shed,
    UnchunkablePrompt,
    admission_chain,
)
from .faults import PoisonedRequest
from .kv_cache import cache_backend_salt, resolve_cache_backend
from .sampling import resolve_sampling, sample_tokens, sampling_salt
from .speculative import DRAFT_K_CANDIDATES, SpecConfig, resolve_proposer


def pow2_tiers(n: int) -> tuple:
    """Power-of-two capture tiers up to and including ``n``."""
    ts, t = [], 1
    while t < n:
        ts.append(t)
        t *= 2
    ts.append(n)
    return tuple(sorted(set(ts)))


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (prompt_len,) int32
    max_new_tokens: int = 16
    eos_id: int = -1                   # -1: never stop early
    priority: int = 0                  # higher preempts lower under load
    deadline_s: Optional[float] = None     # wall-clock budget from submit
    ttft_budget_s: Optional[float] = None  # budget to the first token
    seed: Optional[int] = None             # sampling seed (None: engine seed)
    # filled by the engine:
    output: list = dataclasses.field(default_factory=list)
    row: int = -1
    submitted_s: float = 0.0
    admitted_s: float = 0.0            # first prefill or chunk dispatched
    first_token_s: float = 0.0
    done_s: float = 0.0
    result: object = None              # Finished | Shed | Failed
    preemptions: int = 0
    _seq: int = dataclasses.field(default=-1, repr=False)
    _resume: Optional[np.ndarray] = dataclasses.field(
        default=None, repr=False)

    @property
    def effective_prompt(self) -> np.ndarray:
        """The token stream a (re-)prefill must cover: the original
        prompt, or prompt + generated tokens after a preemption."""
        return self._resume if self._resume is not None else self.prompt

    @property
    def ok(self) -> bool:
        return isinstance(self.result, Finished)


@dataclasses.dataclass
class ServeConfig:
    max_batch: int = 8
    s_max: int = 256
    prefill_buckets: tuple = (32, 64, 128, 256)
    greedy: bool = True
    lowered: bool = True               # slot-based lowered plan replay
    # On-device sampling policy (serve/sampling.py): a SamplingConfig or
    # None for greedy argmax (the historical behavior, bitwise-identical
    # compiled graph).  The policy — never any seed — salts the
    # executable-cache keys, so two engines with different seeds share
    # every capture.
    sampling: object = None
    # Engine-wide sampling seed; Request(seed=) overrides per request.
    # Seeds are runtime arguments of the captured steps and never enter
    # a PlanStore key.
    seed: int = 0
    # Speculative multi-token decode (serve/speculative.py): a
    # SpecConfig or None (plain one-token decode).  The verify step runs
    # the decode graph at query width k+1 — just another shape bucket of
    # the canonical decode lowering, so it specializes without any new
    # lower() after warm-up.
    spec: object = None
    # Tiered decode: captures at these batch sizes (ascending, last ==
    # max_batch).  None = power-of-two tiers.  A single-element tuple
    # (max_batch,) recovers the fixed-batch baseline.
    decode_tiers: Optional[tuple] = None
    # Batched prefill: pack up to this many waiting requests into one
    # prefill call (batch dim bucketed to power-of-two group tiers).
    prefill_batch: int = 4
    # Chunked prefill: prompts longer than the largest bucket run as
    # chunk-sized steps through the decode graph.  When off, oversized
    # prompts are rejected at submit() with a typed ChunkingDisabled
    # error (the pre-tiered engine raised an opaque numpy broadcast
    # error instead).
    chunked_prefill: bool = True
    # Double-buffered host loop: dispatch step k+1 before fetching step
    # k's token/done vector.  Off = harvest synchronously every step.
    async_host: bool = True
    # Admission policy (serve/admission.py).  None = admit everything
    # well-formed (the pre-hardening behavior); expired deadlines/TTFT
    # budgets shed regardless via a built-in DeadlineGate.
    admission: object = None
    # Preempt-and-requeue: evict the lowest-priority decoding row when a
    # higher-priority request waits on a full pool or a pressure window
    # shrinks effective capacity.  With uniform priorities and no
    # pressure this never triggers.
    preemption: bool = True
    # KV storage backend (serve/kv_cache.py): a CacheBackend instance,
    # the names "dense"/"paged", or None for DenseCache (today's dense
    # per-slot pool).  PagedCache allocates fixed-size pages on demand
    # from a shared pool, so KV memory scales with tokens resident and
    # admission is page-capacity, not row-count.  The backend identity
    # salts every PlanStore key, so dense and paged captures coexist in
    # one store and restore independently.
    cache: object = None
    # Chaos harness: a deterministic serve.faults.FaultInjector threaded
    # through allocation, dispatch, harvest, pacing, and capacity.
    faults: object = None
    # PlanStore budgets: bucketed serving churns through (shape, plan)
    # pairs, so both cache levels are bounded — plans by an LRU byte
    # budget, executables by entry count and an optional byte budget.
    plan_capacity: int = 256
    plan_budget_bytes: Optional[int] = 32 << 20
    exec_capacity: int = 64
    exec_budget_bytes: Optional[int] = None
    # Persistent PlanStore: when set, the engine warm-starts from this
    # file on construction (a restarted server serves every
    # previously-seen bucket without re-lowering) and checkpoints the
    # store back when the request queue drains and on ``shutdown()``.
    plan_store_path: Optional[str] = None


class ServeEngine:
    """``scheduler`` accepts an ``OpSchedulerBase`` *or* a
    ``StrategyPolicy`` (resolved per build context by ``build_forward``).
    ``plan_store`` injects an externally-owned store — the
    ``repro.api.Program`` facade passes its own warm-started store so
    every step the program builds shares one artifact; without it the
    engine opens/creates a store from ``cfg``."""

    def __init__(self, model, params, scheduler, cfg: ServeConfig,
                 plan_store: Optional[PlanStore] = None):
        self.model = model
        self.params = params
        self.scheduler = scheduler
        self.cfg = cfg
        if tuple(sorted(cfg.prefill_buckets)) != tuple(cfg.prefill_buckets):
            raise ValueError("prefill_buckets must be ascending")
        if max(cfg.prefill_buckets) > cfg.s_max:
            raise ValueError("largest prefill bucket exceeds s_max")
        self.tiers = tuple(cfg.decode_tiers or pow2_tiers(cfg.max_batch))
        if self.tiers != tuple(sorted(self.tiers)) \
                or self.tiers[-1] != cfg.max_batch:
            raise ValueError(
                f"decode_tiers must ascend to max_batch: {self.tiers}")
        self.prefill_tiers = pow2_tiers(
            max(1, min(cfg.prefill_batch, cfg.max_batch)))
        self.backend = resolve_cache_backend(cfg.cache)
        self.cache = self.backend.build(model, cfg)
        budgets = dict(plan_capacity=cfg.plan_capacity,
                       plan_budget_bytes=cfg.plan_budget_bytes,
                       exec_capacity=cfg.exec_capacity,
                       exec_budget_bytes=cfg.exec_budget_bytes)
        if plan_store is not None:
            if (cfg.plan_store_path and plan_store.path
                    and cfg.plan_store_path != plan_store.path):
                raise ValueError(
                    f"conflicting persistence targets: the injected "
                    f"PlanStore is bound to {plan_store.path!r} but "
                    f"ServeConfig.plan_store_path={cfg.plan_store_path!r}"
                    "; drop one of them")
            self.store = resolve_plan_store(plan_store,
                                            cfg.plan_store_path)
            # a shared store keeps its own budgets unless this config
            # explicitly overrides them (non-default values win — the
            # facade path must not silently drop a user's byte caps)
            defaults = ServeConfig()
            for field, val in budgets.items():
                if val != getattr(defaults, field):
                    setattr(self.store, field, val)
        elif cfg.plan_store_path:
            self.store = PlanStore.open(cfg.plan_store_path, **budgets)
        else:
            self.store = PlanStore(**budgets)
        # the cache backend changes what the jitted steps close over
        # (pool layout, gather/scatter paths), so its identity salts the
        # plan-level outer key — dense and paged captures coexist in one
        # persisted store and restore independently — and a short digest
        # of it tags the exec-level step-cache keys below
        self._op_config = model.op_closure_config() + (
            ("cache_backend", self.backend.identity()),)
        self._cache_tag = cache_backend_salt(self.backend)
        # store-aware policies (AutoPolicy, possibly wrapped in a
        # PolicyScheduler adapter) persist tuning verdicts in this
        # engine's store and take live step-timing feedback
        target = getattr(scheduler, "policy", scheduler)
        bind = getattr(target, "bind_store", None)
        if callable(bind):
            bind(self.store)
        self._observer = getattr(target, "observe", None)
        self._obs_prev = None      # (tier, perf_counter) of last dispatch
        # on-device sampling: the policy (static, baked into the capture)
        # salts exec keys; seeds/rids/positions are runtime args
        self.sampling = resolve_sampling(cfg.sampling)
        self._samp_salt = sampling_salt(self.sampling)
        # speculative decode state
        if cfg.spec is not None and not isinstance(cfg.spec, SpecConfig):
            raise ValueError(
                "ServeConfig.spec must be a serve.SpecConfig or None")
        self._spec = cfg.spec
        if self._spec is not None:
            self._proposer = resolve_proposer(self._spec.proposer)
            self._spec_sampling = resolve_sampling(
                self._spec.sampling if self._spec.sampling is not None
                else cfg.sampling)
            self._spec_salt = sampling_salt(self._spec_sampling)
            self._k_candidates = self._spec_k_candidates()
            self._k_picker = getattr(target, "spec_draft_k", None)
            kmax = (self._spec.k if isinstance(self._spec.k, int)
                    else max(self._k_candidates))
            # verify width k+1 must not exceed the smallest chunk length
            # (chunk-row garbage beyond the frontier is only overwritten
            # when the next chunk's slab covers it) nor s_max headroom
            if kmax + 1 > cfg.prefill_buckets[0]:
                raise ValueError(
                    f"speculative draft k={kmax} needs verify width "
                    f"{kmax + 1} <= the smallest prefill bucket "
                    f"{cfg.prefill_buckets[0]}")
            # rollback is length bookkeeping, which only works for
            # positional (attention) caches: recurrent SSM states
            # advance irreversibly, so a rejected draft would corrupt
            # them
            bad = [key for key in model.decode_cache_layout()
                   if not (key.endswith("k_cache")
                           or key.endswith("v_cache"))]
            if bad:
                raise ValueError(
                    "speculative decode needs positional decode caches "
                    f"(rollback = length decrement); {model.cfg.name} "
                    f"has non-positional state {bad}")
        else:
            self._proposer = None
            self._spec_sampling = self.sampling
            self._spec_salt = self._samp_salt
            self._k_candidates = DRAFT_K_CANDIDATES
            self._k_picker = None
        self._spec_t0 = 0.0        # perf_counter of the last spec dispatch
        # per-row sampling identity mirrors (compacted alongside _gen)
        self._row_seed = np.zeros((cfg.max_batch,), np.uint32)
        self._row_rid = np.zeros((cfg.max_batch,), np.int32)
        # the built-in deadline gate always runs first: a request whose
        # deadline/TTFT budget expired in the queue sheds even under the
        # default admit-everything policy
        self.admission = admission_chain(DeadlineGate(), cfg.admission)
        self._deadline_gate = admission_chain(DeadlineGate())
        self.faults = cfg.faults
        self.waiting: list[Request] = []
        self.active: dict[int, Request] = {}     # row -> request
        # in-progress chunked prefills: rows are allocated (KV filling
        # chunk by chunk) but not yet decoding; round-robin queue
        self._chunking: list[dict] = []
        self.finished: list[Request] = []
        # admission-order record: ("prefill", rids) / ("chunk", rids)
        # tuples in dispatch order — the fairness contract's test surface;
        # bounded, since a server dispatches for as long as it lives
        self.dispatch_log: collections.deque = collections.deque(
            maxlen=4096)
        # device-resident loop state: the sampled token of every row's
        # last decode step, chained into the next step without touching
        # the host (the async half of the double-buffered loop)
        self._last_ids = jnp.zeros((cfg.max_batch, 1), jnp.int32)
        self._gen = np.zeros((cfg.max_batch,), np.int32)   # tokens sampled
        self._pending = None               # in-flight decode step handle
        self._pending_prefill: list = []   # [(tok_dev, [(slot, req), ...])]
        self._seq = 0                      # submission order tiebreaker
        self._iter = 0                     # engine iteration counter
        self._cur_iter = 0                 # iteration the loop is inside
        self._draining = False
        self._stats = {"prefill_steps": 0, "prefill_reqs": 0,
                       "chunk_steps": 0, "decode_steps": 0,
                       "decode_tokens": 0, "host_syncs": 0, "row_moves": 0,
                       "submitted": 0, "finished": 0,
                       "shed": 0, "failed": 0, "preempted": 0,
                       "resumed": 0, "deadline_missed": 0,
                       "alloc_denied": 0, "page_denied": 0,
                       "peak_active": 0, "stranded": 0,
                       "spec_steps": 0, "spec_drafted": 0,
                       "spec_accepted": 0, "spec_rollbacks": 0,
                       "spec_fallbacks": 0, "spec_builds": {},
                       "tier_steps": {t: 0 for t in self.tiers},
                       "tier_builds": {}, "spans": {}}
        # host time by phase: span counters named engine.* (repro.spans)
        self._spans = self._stats["spans"]
        self._ck = self._cache_keys()

    # -- public -----------------------------------------------------------
    def submit(self, req: Request):
        """Validate and enqueue one request.

        Malformed requests raise a typed :class:`RejectedRequest`
        subclass (all are ``ValueError``s, with the historical
        messages).  A request the admission policy sheds at the door
        terminates immediately as ``Shed(Overloaded)`` — it appears in
        ``finished``/``run()`` like any other terminal request — and
        the ``Shed`` decision is returned; ``None`` means admitted."""
        if self._draining:
            raise EngineDraining()
        self._stats["submitted"] += 1
        n = len(req.prompt)
        if n < 1:
            raise EmptyPrompt("empty prompt")
        if n > self.cfg.s_max - 1:
            raise PromptOverflow(
                f"prompt length {n} cannot fit s_max={self.cfg.s_max} "
                "(need at least one decode slot)")
        if self.cache.paged and (self.cache.pages_needed(n + 1)
                                 > self.cache.num_pages):
            raise PromptOverflow(
                f"prompt length {n} needs "
                f"{self.cache.pages_needed(n + 1)} KV pages but the pool "
                f"holds only {self.cache.num_pages} in total")
        if n > self.cfg.prefill_buckets[-1]:
            if not self.cfg.chunked_prefill:
                raise ChunkingDisabled(
                    f"prompt length {n} exceeds the largest prefill bucket "
                    f"{self.cfg.prefill_buckets[-1]} and chunked prefill "
                    "is disabled")
            self._chunk_plan(n)            # raises if it cannot be chunked
        req.submitted_s = time.perf_counter()
        req._seq = self._seq
        self._seq += 1
        decision = self._decide(req, req.submitted_s)
        if isinstance(decision, Shed):
            self._shed_request(req, decision.reason)
            return decision
        self.waiting.append(req)
        return None

    def step(self) -> bool:
        """One engine iteration: admit, dispatch, harvest.  Returns
        True while work remains (the unit ``run()`` loops over; exposed
        so drains and chaos tests can pace the loop themselves)."""
        it = self._iter
        self._iter += 1
        self._cur_iter = it
        with span(self._spans, "engine.iteration", iter=it):
            if self.faults is not None:
                self.faults.on_iter(it)        # injected straggler
            with span(self._spans, "engine.admit", iter=it):
                self._admit()
            handle = self._dispatch_decode()
            if self._spec is not None:
                # speculative steps harvest synchronously: how far each
                # row advanced (the accepted count) is data-dependent, so
                # the host mirrors cannot move at dispatch time.  Still
                # exactly one device_get per decode iteration.
                self._harvest(handle)
            elif self.cfg.async_host:
                # double-buffered: step k+1 is now in flight; only then
                # pay the (single) host sync for step k's tokens
                prev, self._pending = self._pending, handle
                self._harvest(prev)
            else:
                self._harvest(handle)
        return self._busy()

    def run(self, max_iters: int = 10_000) -> list:
        """Drive the loop until every request terminates (or
        ``max_iters``).  Exhausting the iteration budget no longer
        strands in-flight work silently: survivors terminate as
        ``Failed``, their KV rows are released, and
        ``stats["stranded"]`` counts them."""
        it = 0
        while self._busy() and it < max_iters:
            self.step()
            it += 1
        if self._busy():
            self._strand(f"run() exhausted max_iters={max_iters}")
        # idle: the queue drained — checkpoint lowered plans so a restart
        # (or a sibling process) warm-starts instead of re-lowering
        self.checkpoint()
        return self.finished

    def drain(self, timeout: Optional[float] = None) -> dict:
        """Graceful drain: stop admitting (``submit`` raises
        :class:`EngineDraining`; already-queued requests shed), finish
        every in-flight row, checkpoint the PlanStore, and report.  On
        ``timeout`` (seconds of wall clock) the survivors are stranded:
        terminated as ``Failed``, rows released, rids reported."""
        self._draining = True
        try:
            for req in list(self.waiting):
                self._shed_request(req, EngineDraining(
                    "shed from the queue by drain()"))
            self.waiting = []
            t0 = time.perf_counter()
            stranded: list = []
            it = 0
            while self._inflight():
                if timeout is not None \
                        and time.perf_counter() - t0 > timeout:
                    stranded = self._strand(
                        f"stranded at drain(timeout={timeout})")
                    break
                self.step()
                it += 1
            n = self.checkpoint()
            return {"iters": it, "checkpointed": n,
                    "stranded": stranded,
                    "finished": self._stats["finished"],
                    "shed": self._stats["shed"],
                    "failed": self._stats["failed"],
                    "free_rows": len(self.cache.free_rows)}
        finally:
            self._draining = False

    def warmup(self, tiers: Optional[tuple] = None):
        """Build decode captures ahead of traffic (all tiers by default)
        so tier switches under load never hit a cold build."""
        for t in tiers or self.tiers:
            self._decode_fn(t)
            if self._spec is not None:
                ks = ([self._spec.k] if isinstance(self._spec.k, int)
                      else list(self._k_candidates))
                for k in ks:
                    # after _decode_fn(t): the canonical decode lowering
                    # exists, so verify buckets purely specialize
                    self._spec_verify_fn(t, k)
                    if self._proposer.device:
                        self._spec_draft_fn(t, k)

    def checkpoint(self) -> int:
        """Persist the PlanStore when it is path-bound (via
        ``cfg.plan_store_path`` or an injected store); returns the number
        of outer entries written (0 when persistence is off or nothing
        changed since the last checkpoint — run() calls this on every
        queue drain, so a steady-state server must not rewrite an
        unchanged artifact per request)."""
        if not self.store.path or not self.store.dirty:
            return 0
        return self.store.save()

    def shutdown(self) -> int:
        """Abort in-flight work and checkpoint.  Rows held by active,
        chunking, or pending requests are released (those requests
        terminate as ``Failed``/``Shed``) so the pool leaks nothing,
        and the PlanStore checkpoint still runs — a mid-chunked-prefill
        shutdown must not lose the lowered plans it already paid for.
        The engine stays usable afterwards but a well-behaved server
        calls this exactly once on the way out."""
        if self._busy():
            self._strand("engine shutdown")
        return self.checkpoint()

    @property
    def stats(self):
        """A snapshot of the counters, copied by value: it does not
        change as the engine keeps stepping.  ``spans`` maps each
        ``engine.*`` span to its ``{"count", "seconds"}`` so far."""
        out = copy.deepcopy(self._stats)
        out["plan_store"] = self.store.snapshot()
        out["kv"] = self.cache.kv_stats()
        if self.faults is not None:
            out["faults"] = self.faults.counts
        return out

    # -- lifecycle --------------------------------------------------------
    def _busy(self) -> bool:
        return bool(self.waiting or self._inflight())

    def _inflight(self) -> bool:
        return bool(self.active or self._chunking
                    or self._pending is not None or self._pending_prefill)

    def _decide(self, req: Request, now: float, chain=None):
        """Run the admission chain against a load snapshot."""
        waited = max(0.0, now - req.submitted_s)
        deadline_left = (req.submitted_s + req.deadline_s - now
                         if req.deadline_s is not None else None)
        ttft_left = (req.submitted_s + req.ttft_budget_s - now
                     if req.ttft_budget_s is not None
                     and not req.first_token_s else None)
        ctx = AdmissionContext(
            queue_depth=len(self.waiting),
            active=len(self.active), chunking=len(self._chunking),
            free_rows=len(self._usable_free_rows()),
            max_batch=self.cfg.max_batch,
            prompt_len=len(req.effective_prompt), priority=req.priority,
            waited_s=waited, deadline_left_s=deadline_left,
            ttft_left_s=ttft_left,
            free_tokens=self.cache.free_tokens(),
            capacity_tokens=self.cache.token_capacity())
        return (chain or self.admission)(ctx)

    def _release_row_of(self, req: Request):
        row = req.row
        if row >= 0 and self.cache.row_owner.get(row) == req.rid:
            self.active.pop(row, None)
            self.cache.release(row)
            self._gen[row] = 0
        req.row = -1

    def _shed_request(self, req: Request, reason):
        """Terminate a request as ``Shed(reason)`` — a typed result,
        not a stranded queue entry."""
        if req.done_s:
            return
        req.done_s = time.perf_counter()
        req.result = Shed(reason)
        self._release_row_of(req)
        self._chunking = [st for st in self._chunking
                          if st["req"] is not req]
        self._stats["shed"] += 1
        if isinstance(reason, DeadlineExceeded):
            self._stats["deadline_missed"] += 1
        self.finished.append(req)

    def _fail_request(self, req: Request, reason):
        """Per-request error boundary sink: terminate as
        ``Failed(reason)``, release the KV row, keep the engine alive."""
        if req.done_s:
            return
        req.done_s = time.perf_counter()
        req.result = Failed(str(reason))
        self._release_row_of(req)
        self._chunking = [st for st in self._chunking
                          if st["req"] is not req]
        self._stats["failed"] += 1
        self.finished.append(req)

    def _finish(self, req: Request, now: float):
        self.active.pop(req.row, None)
        if req.row >= 0 and self.cache.row_owner.get(req.row) == req.rid:
            self.cache.release(req.row)
            self._gen[req.row] = 0
        req.row = -1
        req.done_s = now
        req.result = Finished()
        self._stats["finished"] += 1
        self.finished.append(req)

    def _deadline_blown(self, req: Request, now: float) -> bool:
        return (req.deadline_s is not None
                and now > req.submitted_s + req.deadline_s)

    def _fail_deadline(self, req: Request, now: float):
        self._stats["deadline_missed"] += 1
        self._fail_request(
            req, f"deadline {req.deadline_s}s exceeded after "
                 f"{len(req.output)} tokens")

    def _strand(self, reason: str) -> list:
        """Release every in-flight row and terminate its request
        (active/chunking -> ``Failed``, queued -> ``Shed``); returns the
        stranded rids.  Flushes the pending step first so tokens the
        device already produced are kept."""
        self._flush_pending()
        inflight = list(self.active.values()) \
            + [st["req"] for st in self._chunking]
        for req in inflight:
            self._stats["stranded"] += 1
            self._fail_request(req, reason)
        for req in list(self.waiting):
            self._shed_request(req, Overloaded(reason))
        self.waiting = []
        self._chunking = []
        self._pending = None
        return [r.rid for r in inflight]

    def _flush_pending(self):
        """Synchronize: harvest the in-flight decode step and any
        pending prefill first-token vectors so every request's host-side
        token list is current (preemption snapshots depend on this)."""
        if self._pending is not None or self._pending_prefill:
            self._harvest(self._pending)
            self._pending = None

    # -- admission --------------------------------------------------------
    def _bucket(self, n: int) -> int:
        for b in self.cfg.prefill_buckets:
            if n <= b:
                return b
        return self.cfg.prefill_buckets[-1]

    def _tier_for(self, n: int, tiers: tuple) -> int:
        for t in tiers:
            if t >= n:
                return t
        return tiers[-1]

    def _req_seed(self, req: Request) -> np.uint32:
        return np.uint32(req.seed if req.seed is not None
                         else self.cfg.seed)

    def _spec_k_candidates(self) -> tuple:
        """Draft-k candidates for ``SpecConfig(k="auto")``: the
        registered ``spec_decode`` param_space when present, else the
        built-in set."""
        try:
            from ..core.strategies import registry as _registry
            space = dict(_registry.get_entry("spec_decode").param_space)
            ks = tuple(int(v) for v in space.get("draft_k", ()))
            if ks:
                return ks
        except Exception:                           # noqa: BLE001
            pass
        return DRAFT_K_CANDIDATES

    def _pressure_rows(self) -> int:
        return (self.faults.pressure_rows(self._cur_iter)
                if self.faults is not None else 0)

    def _capacity(self) -> int:
        """Effective pool capacity: ``max_batch`` minus any rows
        embargoed by an injected memory-pressure window."""
        return max(0, self.cfg.max_batch - self._pressure_rows())

    def _usable_free_rows(self) -> list:
        """Free rows the engine may actually hand out right now —
        truncated so occupancy never exceeds the effective capacity."""
        occ = len(self.active) + len(self._chunking)
        room = max(0, self._capacity() - occ)
        return self.cache.free_rows[:room]

    def _try_allocate(self, req: Request) -> Optional[int]:
        """Allocate a KV row under admission control: denies under
        pressure-shrunk capacity and injected allocation faults (the
        request stays queued — exhaustion is an admission signal, not
        an exception)."""
        if not self._usable_free_rows():
            return None
        if self.faults is not None and self.faults.deny_alloc():
            self._stats["alloc_denied"] += 1
            return None
        row = self.cache.allocate(req.rid)
        if row is None:
            return None
        # paged backends reserve the whole (effective) prompt's pages up
        # front — chunked prefill then never exhausts mid-prompt, and a
        # shortfall is an admission signal (the request keeps waiting for
        # decodes to finish and free pages), not an exception.  The +1
        # covers the first decode write at position len(prompt).
        if not self.cache.reserve(row, len(req.effective_prompt) + 1):
            self.cache.release(row)
            self._stats["page_denied"] += 1
            return None
        return row

    def _shed_expired(self, now: float):
        """Re-check *deadlines* over the queue: a request that was
        admissible at submit may have blown its deadline/TTFT budget
        while waiting for a row.  Load policies (bounded queue,
        priority floors) do NOT re-run here — admission is a one-time
        gate, and re-applying a depth bound to already-admitted work
        would shed the very queue it admitted."""
        keep = []
        for req in self.waiting:
            decision = self._decide(req, now, chain=self._deadline_gate)
            if isinstance(decision, Shed):
                self._shed_request(req, decision.reason)
            else:
                keep.append(req)
        self.waiting = keep

    def _admit(self):
        """Fair admission under lifecycle control: shed expired work,
        preempt if pressure/priority demands it, then admit waiting
        whole-prompt groups (highest priority first, submission order
        within a priority) and exactly one chunk of the oldest
        in-progress chunked prefill per iteration (round-robin).  An
        oversized prompt at the queue head only *stages* its chunk
        state — its chunks interleave with later iterations' admits
        instead of monopolizing dispatch for ``len/chunk`` consecutive
        steps."""
        now = time.perf_counter()
        self._shed_expired(now)
        self._maybe_preempt()
        big = self.cfg.prefill_buckets[-1]
        self.waiting.sort(key=lambda r: (-r.priority, r._seq))
        while self.waiting:
            if not self._usable_free_rows():
                break
            head = self.waiting[0]
            if len(head.effective_prompt) > big:
                row = self._try_allocate(head)
                if row is None:
                    break
                self._start_chunked(self.waiting.pop(0), row)
                continue
            group, denied = [], False
            while (self.waiting and len(group) < self.cfg.prefill_batch
                   and len(self.waiting[0].effective_prompt) <= big):
                row = self._try_allocate(self.waiting[0])
                if row is None:
                    denied = True
                    break
                req = self.waiting.pop(0)
                req.row = row
                group.append(req)
            if group:
                self._dispatch_prefill(group)
            if denied or not group:
                break
        self._step_chunked()

    # -- preemption -------------------------------------------------------
    def _maybe_preempt(self):
        """Evict decoding rows when the pool must shrink (pressure
        window pushed occupancy over capacity) or a waiting request
        outranks the lowest-priority decoding row on a full pool.  The
        victim's generated tokens are snapshotted host-side, its KV row
        released, and it re-enters the queue as a re-prefill over
        ``prompt + generated`` (chunked when the combined length
        exceeds the largest bucket)."""
        if not self.cfg.preemption:
            return
        # capacity eviction: occupancy must fit the pressured pool
        while (len(self.active) + len(self._chunking) > self._capacity()
               and self._preempt_one()):
            pass
        # priority eviction: one per iteration is enough — admission
        # takes the freed row immediately after
        if self.waiting and not self._usable_free_rows() and self.active:
            best = max(r.priority for r in self.waiting)
            live = [r for r in self.active.values() if not r.done_s]
            if live and best > min(r.priority for r in live):
                self._preempt_one(max_priority=best - 1)

    def _preempt_one(self, max_priority: Optional[int] = None) -> bool:
        self._flush_pending()
        victims = [r for r in self.active.values()
                   if not r.done_s and r.output
                   and r.output[-1] != -100
                   and (max_priority is None
                        or r.priority <= max_priority)]
        if not victims:
            return False
        # lowest priority first; youngest within a priority (the oldest
        # request has waited longest for its tokens)
        victim = min(victims, key=lambda r: (r.priority, -r._seq))
        self.active.pop(victim.row, None)
        self.cache.release(victim.row)
        self._gen[victim.row] = 0
        victim.row = -1
        victim.preemptions += 1
        victim._resume = np.concatenate(
            [np.asarray(victim.prompt, np.int32),
             np.asarray(victim.output, np.int32)])
        self.waiting.append(victim)
        self._stats["preempted"] += 1
        return True

    # -- prefill ----------------------------------------------------------
    def _dispatch_prefill(self, group: list):
        """One bucketed prefill call over a real batch of requests.

        The jitted step writes each row's KV straight into the donated
        cache pool (``dynamic_update_slice`` at the row index) and
        samples the first token on-device; the host fetches the tiny
        token vector together with the next decode harvest.  Group slots
        are padded up to a power-of-two tier; padded slots alias a real
        row and are unrolled *first* so the real row's write wins.

        Error boundary: a ``PoisonedRequest`` excises exactly the named
        request (it terminates as ``Failed``) and the dispatch retries
        with the survivors; any other dispatch exception fails the
        whole group — never the engine.
        """
        while group:
            bp = self._tier_for(len(group), self.prefill_tiers)
            prompts = [r.effective_prompt for r in group]
            bucket = self._bucket(max(len(p) for p in prompts))
            with span(self._spans, "engine.prefill", iter=self._cur_iter,
                      bp=bp, bucket=bucket,
                      rids=tuple(r.rid for r in group)):
                ids = np.zeros((bp, bucket), np.int32)
                rows = np.full((bp,), group[0].row, np.int32)
                full = np.zeros((bp,), bool)
                sent_last = np.zeros((bp,), np.int32)
                seeds = np.zeros((bp,), np.uint32)
                rids = np.zeros((bp,), np.int32)
                pos_emit = np.zeros((bp,), np.int32)
                for j, (req, pr) in enumerate(zip(group, prompts)):
                    n = len(pr)
                    ids[j, :n] = pr[:n]
                    rows[j] = req.row
                    full[j] = n == bucket
                    sent_last[j] = int(pr[n - 1])
                    seeds[j] = self._req_seed(req)
                    rids[j] = req.rid
                    pos_emit[j] = n       # a full bucket emits position n
                    self._row_seed[req.row] = seeds[j]
                    self._row_rid[req.row] = req.rid
                try:
                    if self.faults is not None:
                        self.faults.check_dispatch(
                            "prefill", [r.rid for r in group])
                    fn = self._prefill_fn(bp, bucket)
                    args = [self.params, jnp.asarray(ids), jnp.asarray(rows),
                            jnp.asarray(full), jnp.asarray(sent_last),
                            jnp.asarray(seeds), jnp.asarray(rids),
                            jnp.asarray(pos_emit),
                            self.cache.caches, self._last_ids]
                    if self.cache.paged:
                        args.append(self.cache.page_table_array())
                    tok, self.cache.caches, self._last_ids = fn(*args)
                except PoisonedRequest as e:
                    bad = next(r for r in group if r.rid == e.rid)
                    self._fail_request(bad, e)
                    group = [r for r in group if r is not bad]
                    continue
                except Exception as e:                  # noqa: BLE001
                    for req in group:
                        self._fail_request(
                            req, f"prefill dispatch failed: {e}")
                    return
                now = time.perf_counter()
                slots = []
                for j, (req, pr) in enumerate(zip(group, prompts)):
                    n = len(pr)
                    if not req.admitted_s:     # kept across a preemption
                        req.admitted_s = now
                    # tokens already generated pre-preemption count toward
                    # max_new_tokens; a fresh request starts at 0
                    base = len(req.output)
                    if req._resume is not None:
                        self._stats["resumed"] += 1
                    self._gen[req.row] = base + (1 if full[j] else 0)
                    self.cache.lengths[req.row] = n if full[j] else n - 1
                    self.active[req.row] = req
                    if full[j]:
                        slots.append((j, req))
                    else:
                        # bucket-padded: the cache holds [0, n-1); the first
                        # decode step re-runs the last token at position n-1
                        # and yields the true next token (the -100 sentinel
                        # routes the harvest down the replace path).
                        req.output.append(-100)
                self._stats["prefill_steps"] += 1
                self._stats["prefill_reqs"] += len(group)
                self.dispatch_log.append(("prefill",
                                          tuple(r.rid for r in group)))
                if slots:
                    self._pending_prefill.append((tok, slots))
                return

    def _prefill_fn(self, bp: int, bucket: int) -> Callable:
        def build():
            segs, _ = self.model.build_segments("prefill", bp, bucket,
                                                s_max=self.cfg.s_max)
            info = ScheduleContext(local_batch=bp, seq_len=bucket,
                                   phase="prefill", arch=self.model.cfg.name)
            fwd = build_forward(segs, self.scheduler, info,
                                lowered=self.cfg.lowered,
                                plan_cache=self.store if self.cfg.lowered
                                else None,
                                op_config=self._op_config)
            ck = self._ck
            cache = self.cache
            bds = cache.batch_dims
            samp = self.sampling

            if cache.paged:
                nb = bucket // cache.page_size

                def run(params, ids, rows, full, sent_last, seeds, rids,
                        pos_emit, caches, last_ids, page_tab):
                    pos = jnp.broadcast_to(
                        jnp.arange(bucket, dtype=jnp.int32), (bp, bucket))
                    out = fwd(params, {"ids": ids, "positions": pos})
                    tok = sample_tokens(out["logits"][:, -1, :], samp,
                                        seeds=seeds, rids=rids,
                                        positions=pos_emit)
                    caches = dict(caches)
                    li = last_ids[:, 0]
                    # reversed: padded slots alias rows[0]'s page-table
                    # row, so slot 0's real write lands last and wins;
                    # bucket tail beyond a row's reserved pages scatters
                    # into the trash page
                    for j in reversed(range(bp)):
                        r = rows[j]
                        pt_row = jnp.take(page_tab, r, axis=0)
                        for pk, pv, dk, dv in ck:
                            for src, dst in ((pk, dk), (pv, dv)):
                                axis = 1 if bds[dst] else 0
                                slab = lax.slice_in_dim(out[src], j, j + 1,
                                                        axis=axis)
                                caches.update(cache.scatter_row_pages(
                                    {dst: caches[dst]}, {dst: slab},
                                    pt_row, 0, nb, 0, bucket))
                        li = li.at[r].set(
                            jnp.where(full[j], tok[j], sent_last[j]))
                    return tok, caches, li[:, None]

                return _jit(run, f"prefill_b{bp}_s{bucket}", donate=(8, 9))

            def run(params, ids, rows, full, sent_last, seeds, rids,
                    pos_emit, caches, last_ids):
                pos = jnp.broadcast_to(jnp.arange(bucket, dtype=jnp.int32),
                                       (bp, bucket))
                out = fwd(params, {"ids": ids, "positions": pos})
                tok = sample_tokens(out["logits"][:, -1, :], samp,
                                    seeds=seeds, rids=rids,
                                    positions=pos_emit)
                caches = dict(caches)
                li = last_ids[:, 0]
                # reversed: padded slots (which alias rows[0]) run first,
                # so slot 0's real write lands last and wins
                for j in reversed(range(bp)):
                    r = rows[j]
                    for pk, pv, dk, dv in ck:
                        for src, dst in ((pk, dk), (pv, dv)):
                            val = out[src]
                            c = caches[dst]
                            if bds[dst]:            # stacked (L,B,S,...)
                                slab = lax.slice_in_dim(val, j, j + 1,
                                                        axis=1)
                                start = (0, r) + (0,) * (c.ndim - 2)
                            else:                   # per-layer (B,S,...)
                                slab = lax.slice_in_dim(val, j, j + 1,
                                                        axis=0)
                                start = (r,) + (0,) * (c.ndim - 1)
                            caches[dst] = lax.dynamic_update_slice(
                                c, slab.astype(c.dtype), start)
                    li = li.at[r].set(
                        jnp.where(full[j], tok[j], sent_last[j]))
                return tok, caches, li[:, None]

            return _jit(run, f"prefill_b{bp}_s{bucket}", donate=(8, 9))

        return self.store.get_or_build(
            ("prefill", self._cache_tag, self._samp_salt, bp, bucket),
            build)

    # -- chunked prefill --------------------------------------------------
    def _chunk_plan(self, n: int) -> list:
        """Chunk schedule [(offset, chunk_len)] filling the cache up to
        position ``n - 1`` (the sentinel decode step recomputes the final
        prompt position and yields the first token).  Chunk lengths are
        prefill buckets so their decode-graph captures are shared; the
        final chunk may overhang ``n - 1`` (padding is masked by
        ``cache_len``) but must never overhang ``s_max``, where the
        clamped cache write would corrupt earlier positions."""
        buckets = self.cfg.prefill_buckets
        big = buckets[-1]
        chunks, off, target = [], 0, n - 1
        while off < target:
            rem = target - off
            c = big if rem >= big else next(b for b in buckets if b >= rem)
            if off + c > self.cfg.s_max:
                fits = [b for b in buckets
                        if b >= rem and off + b <= self.cfg.s_max]
                if not fits:
                    raise UnchunkablePrompt(
                        f"prompt length {n} cannot be chunk-prefilled "
                        f"within s_max={self.cfg.s_max} with buckets "
                        f"{buckets}")
                c = fits[0]
            chunks.append((off, c))
            off += c
        return chunks

    def _start_chunked(self, req: Request, row: int):
        """Stage a prompt longer than the largest bucket for chunked
        prefill through the decode graph: bind its (pre-allocated) row
        and queue the chunk schedule; ``_step_chunked`` dispatches one
        chunk per engine iteration."""
        req.row = row
        prompt = np.asarray(req.effective_prompt, np.int32)
        n = len(prompt)
        try:
            chunks = self._chunk_plan(n)
        except UnchunkablePrompt as e:
            # resumed prompts grew past submit-time validation
            self._fail_request(req, e)
            return
        if req._resume is not None:
            self._stats["resumed"] += 1
        self._row_seed[row] = self._req_seed(req)
        self._row_rid[row] = req.rid
        # chunks cover [0, n-1) and may fall exactly one token short of
        # the prompt (position n-1 travels via the sentinel decode), so
        # size the staging buffer for whichever is longer
        padded = np.zeros(max(n, chunks[-1][0] + chunks[-1][1]), np.int32)
        padded[:n] = prompt
        self._chunking.append({"req": req, "prompt": prompt,
                               "padded": padded, "chunks": chunks,
                               "next": 0})

    def _step_chunked(self):
        """Dispatch the pending chunk of the round-robin head — packed
        with every other in-progress chunked prefill whose next chunk
        has the *same* length (one bucketed call over a real batch
        dimension, batch padded to a power-of-two slab tier), writing
        their KV in-place; when a request's final chunk is in flight it
        joins ``active`` and its first token arrives via the sentinel
        decode step like any bucket-padded prefill.  No host sync here.
        A dispatch fault fails exactly the packed requests."""
        if not self._chunking:
            return
        head = self._chunking.pop(0)
        c = head["chunks"][head["next"]][1]
        batch = [head]
        keep = []
        for st in self._chunking:
            if (len(batch) < self.cfg.prefill_batch
                    and st["chunks"][st["next"]][1] == c):
                batch.append(st)
            else:
                keep.append(st)
        self._chunking = keep
        bc = self._tier_for(len(batch), self.prefill_tiers)
        with span(self._spans, "engine.chunk", iter=self._cur_iter,
                  bc=bc, chunk=c,
                  rids=tuple(st["req"].rid for st in batch)):
            ids = np.zeros((bc, c), np.int32)
            offs = np.zeros((bc,), np.int32)
            rows = np.full((bc,), batch[0]["req"].row, np.int32)
            for j, st in enumerate(batch):
                off = st["chunks"][st["next"]][0]
                ids[j] = st["padded"][off:off + c]
                offs[j] = off
                rows[j] = st["req"].row
            # padded slots duplicate slot 0: identical writes are order-safe
            for j in range(len(batch), bc):
                ids[j], offs[j] = ids[0], offs[0]
            try:
                if self.faults is not None:
                    self.faults.check_dispatch(
                        "chunk", [st["req"].rid for st in batch])
                fn = self._chunk_fn(bc, c)
                args = [self.params, jnp.asarray(ids), jnp.asarray(offs),
                        jnp.asarray(rows), self.cache.caches]
                if self.cache.paged:
                    args.append(self.cache.page_table_array())
                self.cache.caches = fn(*args)
            except Exception as e:                      # noqa: BLE001
                for st in batch:
                    self._fail_request(st["req"],
                                       f"chunk dispatch failed: {e}")
                return
            now = time.perf_counter()
            self._stats["chunk_steps"] += 1
            self.dispatch_log.append(
                ("chunk", tuple(st["req"].rid for st in batch)))
            for j, st in enumerate(batch):
                req, row = st["req"], st["req"].row
                if not req.admitted_s:
                    req.admitted_s = now
                off = int(offs[j])
                st["next"] += 1
                if st["next"] < len(st["chunks"]):
                    # keep the host length mirror at the chunk frontier: a
                    # decode step interleaved before the next chunk writes
                    # one garbage k/v at this position for the (inactive)
                    # row, and the next chunk's full-slab write overwrites it
                    self.cache.lengths[row] = off + c
                    self._chunking.append(st)      # round-robin: to the back
                    continue
                prompt = st["prompt"]
                n = len(prompt)
                self._last_ids = self._last_ids.at[row, 0].set(
                    int(prompt[n - 1]))
                self.cache.lengths[row] = n - 1
                self._gen[row] = len(req.output)
                req.output.append(-100)
                self.active[row] = req

    def _chunk_fn(self, bc: int, chunk: int) -> Callable:
        def build():
            segs, _ = self.model.build_segments("decode", bc, chunk,
                                                s_max=self.cfg.s_max)
            info = ScheduleContext(local_batch=bc, seq_len=self.cfg.s_max,
                                   phase="decode", arch=self.model.cfg.name)
            fwd = build_forward(segs, self.scheduler, info,
                                lowered=self.cfg.lowered,
                                plan_cache=self.store if self.cfg.lowered
                                else None,
                                op_config=self._op_config)
            cache = self.cache
            bds = cache.batch_dims

            if cache.paged:
                nbc = chunk // cache.page_size

                def run(params, ids, offs, rows, caches, page_tab):
                    pos = offs[:, None] \
                        + jnp.arange(chunk, dtype=jnp.int32)[None]
                    pt_rows = jnp.take(page_tab, rows, axis=0)
                    rcaches = cache.gather_row_batch(caches, pt_rows)
                    out = fwd(params, {"ids": ids, "positions": pos,
                                       "cache_len": offs, **rcaches})
                    # chunk offsets are bucket sums and buckets are page
                    # multiples (validated at backend build), so each
                    # slot's slab is exactly nbc whole blocks.  Reversed
                    # unroll: padded slots duplicate slot 0, so slot 0's
                    # (identical) write lands last
                    new = dict(caches)
                    for j in reversed(range(bc)):
                        out_j = {k: lax.slice_in_dim(
                                     out[k], j, j + 1,
                                     axis=1 if bds[k] else 0)
                                 for k in caches}
                        new.update(cache.scatter_row_pages(
                            new, out_j, pt_rows[j],
                            offs[j] // cache.page_size, nbc, offs[j],
                            chunk))
                    return new

                return _jit(run, f"chunk_b{bc}_s{chunk}", donate=(4,))

            def run(params, ids, offs, rows, caches):
                pos = offs[:, None] \
                    + jnp.arange(chunk, dtype=jnp.int32)[None]
                rcaches = {k: jnp.take(v, rows, axis=bds[k])
                           for k, v in caches.items()}
                out = fwd(params, {"ids": ids, "positions": pos,
                                   "cache_len": offs, **rcaches})
                new = dict(caches)
                for j in reversed(range(bc)):
                    for k in caches:
                        slab = lax.slice_in_dim(out[k], j, j + 1,
                                                axis=bds[k])
                        new[k] = lax.dynamic_update_slice_in_dim(
                            new[k], slab.astype(new[k].dtype), rows[j],
                            axis=bds[k])
                return new

            return _jit(run, f"chunk_b{bc}_s{chunk}", donate=(4,))

        return self.store.get_or_build(
            ("chunk", self._cache_tag, bc, chunk), build)

    # -- decode -----------------------------------------------------------
    def _decode_fn(self, tier: int) -> Callable:
        def build():
            before = dict(self.store.stats)
            segs, _ = self.model.build_segments(
                "decode", tier, 1, s_max=self.cfg.s_max)
            info = ScheduleContext(local_batch=tier, seq_len=self.cfg.s_max,
                                   phase="decode", arch=self.model.cfg.name)
            fwd = build_forward(segs, self.scheduler, info,
                                lowered=self.cfg.lowered,
                                plan_cache=self.store if self.cfg.lowered
                                else None,
                                op_config=self._op_config)
            st = self.store.stats
            self._stats["tier_builds"][tier] = {
                k: st[k] - before[k]
                for k in ("misses", "shares", "restore_hits")}
            cache = self.cache
            bds = cache.batch_dims
            samp = self.sampling

            if cache.paged:

                def run(params, last_ids, cache_len, active, eos,
                        will_end, seeds, rids, caches, page_tab):
                    ids = lax.slice_in_dim(last_ids, 0, tier, axis=0)
                    clen = lax.slice_in_dim(cache_len, 0, tier, axis=0)
                    # gather the tier's pages into the contiguous
                    # (tier, s_max, ...) view — the model forward (and
                    # its captured plan) is identical to the dense path
                    tcaches = cache.gather_rows(caches, page_tab, tier)
                    out = fwd(params, {"ids": ids,
                                       "positions": clen[:, None],
                                       "cache_len": clen, **tcaches})
                    # only the frontier block per row was written;
                    # unmapped frontiers (mid-chunk rows, freed rows in
                    # the tier prefix) scatter into the trash page
                    new_caches = cache.scatter_frontier(
                        caches, out, page_tab, cache_len, tier)
                    tok_t = sample_tokens(
                        out["logits"][:, -1, :], samp,
                        seeds=lax.slice_in_dim(seeds, 0, tier, axis=0),
                        rids=lax.slice_in_dim(rids, 0, tier, axis=0),
                        positions=clen + 1)
                    tok = lax.dynamic_update_slice(last_ids[:, 0], tok_t,
                                                   (0,))
                    tok = jnp.where(active, tok, last_ids[:, 0])
                    done = active & (will_end | (tok == eos))
                    return tok, done, tok[:, None], new_caches

                return _jit(run, f"decode_t{tier}", donate=(1, 8))

            def run(params, last_ids, cache_len, active, eos, will_end,
                    seeds, rids, caches):
                ids = lax.slice_in_dim(last_ids, 0, tier, axis=0)
                clen = lax.slice_in_dim(cache_len, 0, tier, axis=0)
                tcaches = {k: lax.slice_in_dim(v, 0, tier, axis=bds[k])
                           for k, v in caches.items()}
                out = fwd(params, {"ids": ids, "positions": clen[:, None],
                                   "cache_len": clen, **tcaches})
                new_caches = {
                    k: lax.dynamic_update_slice_in_dim(
                        caches[k], out[k].astype(caches[k].dtype), 0,
                        axis=bds[k])
                    for k in caches}
                tok_t = sample_tokens(
                    out["logits"][:, -1, :], samp,
                    seeds=lax.slice_in_dim(seeds, 0, tier, axis=0),
                    rids=lax.slice_in_dim(rids, 0, tier, axis=0),
                    positions=clen + 1)
                tok = lax.dynamic_update_slice(last_ids[:, 0], tok_t, (0,))
                tok = jnp.where(active, tok, last_ids[:, 0])
                done = active & (will_end | (tok == eos))
                return tok, done, tok[:, None], new_caches

            return _jit(run, f"decode_t{tier}", donate=(1, 8))

        return self.store.get_or_build(
            ("decode", self._cache_tag, self._samp_salt, tier), build)

    def _compact(self, tier: int):
        """Restore the prefix invariant: every allocated row < tier —
        active requests *and* in-progress chunked prefills, whose
        partially-filled cache rows relocate the same way (cache rows
        move on-device; the in-flight step, if any, ordered ahead by
        data dependencies)."""
        with span(self._spans, "engine.compact", iter=self._cur_iter,
                  tier=tier):
            chunk_rows = {st["req"].row: st for st in self._chunking}
            occupied = sorted((r for r in (*self.active, *chunk_rows)
                               if r >= tier), reverse=True)
            for src in occupied:
                dst = next(r for r in self.cache.free_rows if r < tier)
                self.cache.move_row(src, dst)
                self._last_ids = self._last_ids.at[dst].set(
                    self._last_ids[src])
                self._gen[dst] = self._gen[src]
                self._row_seed[dst] = self._row_seed[src]
                self._row_rid[dst] = self._row_rid[src]
                if src in self.active:
                    req = self.active.pop(src)
                    req.row = dst
                    self.active[dst] = req
                else:
                    chunk_rows[src]["req"].row = dst
                self._stats["row_moves"] += 1

    def _ensure_decode_pages(self):
        """Paged backends only: every active row writes position
        ``lengths[row]`` this step, which needs a fresh page whenever the
        length crosses a page boundary (including the boundary cases a
        prefill or final chunk leaves the length exactly page-aligned).
        On pool exhaustion, preempt the lowest-priority decoding row
        (its release frees pages — the victim may itself be one of the
        short rows) and retry; rows that still cannot get a page
        terminate as ``Failed`` so the survivors keep decoding."""
        if not self.cache.paged:
            return
        while True:
            short = [row for row in sorted(self.active)
                     if not self.cache.reserve(
                         row, int(self.cache.lengths[row]) + 1)]
            if not short:
                return
            self._stats["page_denied"] += len(short)
            if self.cfg.preemption and self._preempt_one():
                continue
            for row in short:
                req = self.active.get(row)
                if req is not None:
                    self._fail_request(req, (
                        "KV page pool exhausted: no page free for the "
                        f"decode write at position {self.cache.lengths[row]}"
                        " and no preemptible victim"))
            return

    def _dispatch_decode(self):
        """Dispatch one decode step at the smallest covering tier.
        Returns an opaque handle ``(tok_dev, done_dev, snapshot)`` the
        harvest consumes — in async mode one loop iteration later.

        Error boundary: a ``PoisonedRequest`` fails exactly that row
        and the dispatch retries with the survivors; any other dispatch
        exception fails the rows in this dispatch (blast radius is the
        batch, never the engine)."""
        while self.active:
            self._ensure_decode_pages()
            if not self.active:
                return None
            B = self.cfg.max_batch
            occ = len(self.active) + len(self._chunking)
            self._stats["peak_active"] = max(self._stats["peak_active"],
                                             occ)
            # the tier must cover every allocated row: chunking rows ride
            # in the prefix (their frontier-position garbage writes are
            # overwritten by the next chunk — see _step_chunked)
            tier = self._tier_for(occ, self.tiers)
            with span(self._spans, "engine.decode", iter=self._cur_iter,
                      tier=tier,
                      rids=tuple(r.rid for r in self.active.values())):
                self._compact(tier)
                if self._spec is not None:
                    k = self._spec_k_for_dispatch()
                    if k:
                        result = self._dispatch_spec(tier, k)
                        if result == "retry":
                            continue
                        return result
                    self._stats["spec_fallbacks"] += 1
                active = np.zeros((B,), bool)
                will_end = np.zeros((B,), bool)
                eos = np.full((B,), -1, np.int32)
                snapshot = []
                for row, req in self.active.items():
                    active[row] = True
                    eos[row] = req.eos_id
                    will_end[row] = (self._gen[row] + 1 >= req.max_new_tokens
                                     or self.cache.lengths[row] + 1
                                     >= self.cfg.s_max - 1)
                    snapshot.append((row, req))
                try:
                    if self.faults is not None:
                        self.faults.check_dispatch(
                            "decode", [r.rid for _, r in snapshot])
                    fn = self._decode_fn(tier)
                    # .copy(): on CPU jnp.asarray may alias the host buffer,
                    # and these mirrors mutate between dispatch and execute
                    args = [self.params, self._last_ids,
                            self.cache.cache_len_array(),
                            jnp.asarray(active), jnp.asarray(eos),
                            jnp.asarray(will_end),
                            jnp.asarray(self._row_seed.copy()),
                            jnp.asarray(self._row_rid.copy()),
                            self.cache.caches]
                    if self.cache.paged:
                        args.append(self.cache.page_table_array())
                    tok, done, self._last_ids, self.cache.caches = fn(*args)
                except PoisonedRequest as e:
                    bad = next(r for _, r in snapshot if r.rid == e.rid)
                    self._fail_request(bad, e)
                    continue
                except Exception as e:                  # noqa: BLE001
                    for _, req in snapshot:
                        self._fail_request(req, f"decode dispatch failed: {e}")
                    return None
                # host mirrors advance at dispatch, not harvest: the device's
                # view of every row is derivable without a sync
                for row, _ in snapshot:
                    self.cache.lengths[row] += 1
                    self._gen[row] += 1
                self._stats["decode_steps"] += 1
                self._stats["tier_steps"][tier] += 1
                if self._observer is not None:
                    self._feed_observer(tier)
                return (tok, done, snapshot)
        return None

    def _feed_observer(self, tier: int):
        """Feed the policy live step timings: the wall clock between two
        successive same-tier decode dispatches bounds one device step
        (the loop is double-buffered — dispatch N+1 waits on step N), so
        it is the cheapest honest signal that needs no extra sync."""
        t_now = time.perf_counter()
        prev = self._obs_prev
        self._obs_prev = (tier, t_now)
        if prev is None or prev[0] != tier:
            return
        try:
            self._observer(
                phase="decode", arch=self.model.cfg.name,
                local_batch=tier, seq_len=self.cfg.s_max,
                seconds=t_now - prev[1],
                stats={"decode_steps": self._stats["decode_steps"],
                       "active": len(self.active),
                       "shed": self._stats["shed"]})
        except Exception:                           # noqa: BLE001
            self._observer = None   # a broken observer never kills serving

    # -- speculative decode -----------------------------------------------
    def _pick_k(self) -> int:
        """Draft length for this iteration: the static ``SpecConfig.k``,
        or — under ``k="auto"`` — the policy's pick from measured
        acceptance (``AutoPolicy.spec_draft_k``), defaulting to 4."""
        if isinstance(self._spec.k, int):
            return self._spec.k
        if self._k_picker is not None:
            try:
                k = int(self._k_picker(arch=self.model.cfg.name,
                                       candidates=self._k_candidates))
                if k >= 1:
                    return k
            except Exception:                       # noqa: BLE001
                self._k_picker = None   # broken picker: fall back, once
        return 4 if 4 in self._k_candidates else self._k_candidates[0]

    def _spec_k_for_dispatch(self) -> int:
        """Decide whether this iteration can run speculatively and at
        what k; 0 means fall back to plain one-token decode.  A verify
        step writes ``W = k + 1`` cache positions per allocated row
        (active rows at their frontier; chunk rows write garbage the
        next chunk slab overwrites), so every row needs W positions of
        headroom and — paged — W positions of reserved pages.  Any page
        shortfall or injected allocation denial falls back rather than
        failing rows: plain decode only needs the +1 the caller already
        reserved."""
        k = self._pick_k()
        W = k + 1
        for row in self.active:
            if int(self.cache.lengths[row]) + W > self.cfg.s_max:
                return 0
        for st in self._chunking:
            off, c = st["chunks"][st["next"]]
            if c < W or int(self.cache.lengths[st["req"].row]) + W \
                    > self.cfg.s_max:
                return 0
        if self.cache.paged:
            for row in sorted(self.active):
                need = self.cache.pages_needed(
                    int(self.cache.lengths[row]) + W)
                if need > int(self.cache.blocks_used[row]):
                    if self.faults is not None \
                            and self.faults.deny_alloc():
                        self._stats["alloc_denied"] += 1
                        return 0
                if not self.cache.reserve(
                        row, int(self.cache.lengths[row]) + W):
                    self._stats["page_denied"] += 1
                    return 0
        return k

    def _dispatch_spec(self, tier: int, k: int):
        """Dispatch one speculative verify step: draft k tokens per
        active row, run the decode graph once at query width k + 1, and
        return the handle the (synchronous) harvest consumes.  Host
        mirrors do NOT advance here — how far each row moved is the
        data-dependent accepted count, applied at harvest.  Returns
        ``"retry"`` after excising a poisoned request."""
        B = self.cfg.max_batch
        active = np.zeros((B,), bool)
        eos = np.full((B,), -1, np.int32)
        gen_left = np.ones((B,), np.int32)
        snapshot = []
        for row, req in self.active.items():
            active[row] = True
            eos[row] = req.eos_id
            gen_left[row] = max(1, req.max_new_tokens - self._gen[row])
            snapshot.append((row, req))
        try:
            if self.faults is not None:
                self.faults.check_dispatch(
                    "decode", [r.rid for _, r in snapshot])
            drafts = self._make_drafts(tier, k, snapshot)
            fn = self._spec_verify_fn(tier, k)
            args = [self.params, self._last_ids,
                    self.cache.cache_len_array(),
                    jnp.asarray(active), jnp.asarray(eos),
                    jnp.asarray(gen_left),
                    jnp.asarray(self._row_seed.copy()),
                    jnp.asarray(self._row_rid.copy()),
                    drafts, self.cache.caches]
            if self.cache.paged:
                args.append(self.cache.page_table_array())
            u, n_emit, done, self._last_ids, self.cache.caches = fn(*args)
        except PoisonedRequest as e:
            bad = next(r for _, r in snapshot if r.rid == e.rid)
            self._fail_request(bad, e)
            return "retry"
        except Exception as e:                      # noqa: BLE001
            for _, req in snapshot:
                self._fail_request(req, f"decode dispatch failed: {e}")
            return None
        self._stats["decode_steps"] += 1
        self._stats["spec_steps"] += 1
        self._stats["spec_drafted"] += k * len(snapshot)
        self._stats["tier_steps"][tier] += 1
        self._spec_t0 = time.perf_counter()
        return ("spec", u, n_emit, done, snapshot, k, tier)

    def _make_drafts(self, tier: int, k: int, snapshot: list):
        """(tier, k) int32 draft tokens: device proposers run their
        captured draft step; host proposers see each row's current token
        stream (the trailing ``-100`` sentinel is a placeholder, not a
        token — popped before drafting)."""
        if self._proposer.device:
            fn = self._spec_draft_fn(tier, k)
            args = [self.params, self._last_ids,
                    self.cache.cache_len_array(),
                    jnp.asarray(self._row_seed.copy()),
                    jnp.asarray(self._row_rid.copy()),
                    self.cache.caches]
            if self.cache.paged:
                args.append(self.cache.page_table_array())
            return fn(*args)
        drafts = np.zeros((tier, k), np.int32)
        streams, rows = [], []
        for row, req in snapshot:
            s = list(req.prompt) + list(req.output)
            if s and s[-1] == -100:
                s.pop()
            streams.append(s)
            rows.append(row)
        if streams:
            got = np.asarray(self._proposer.draft(streams, k), np.int32)
            for i, row in enumerate(rows):
                drafts[row] = got[i]
        return jnp.asarray(drafts)

    def _spec_verify_fn(self, tier: int, k: int) -> Callable:
        """The verify step: the canonical decode graph at query width
        ``W = k + 1`` — just another shape bucket, so after ``warmup``
        (or any plain decode build) it *specializes* off the canonical
        decode lowering with zero new ``lower()`` calls (asserted via
        ``stats["spec_builds"]``).  Accepts the longest draft prefix
        matching what the target itself emits, plus one corrected
        token; eos / token-budget / s_max cuts mirror the plain decode
        ``will_end``/``done`` semantics position by position, which is
        what makes greedy speculative decode bitwise-identical to plain
        greedy decode."""
        W = k + 1

        def build():
            before = dict(self.store.stats)
            segs, _ = self.model.build_segments(
                "decode", tier, W, s_max=self.cfg.s_max)
            info = ScheduleContext(local_batch=tier, seq_len=self.cfg.s_max,
                                   phase="decode", arch=self.model.cfg.name)
            fwd = build_forward(segs, self.scheduler, info,
                                lowered=self.cfg.lowered,
                                plan_cache=self.store if self.cfg.lowered
                                else None,
                                op_config=self._op_config)
            st = self.store.stats
            self._stats["spec_builds"][(tier, k)] = {
                key: st[key] - before[key]
                for key in ("misses", "shares", "restore_hits")}
            cache = self.cache
            bds = cache.batch_dims
            samp = self._spec_sampling
            s_max = self.cfg.s_max

            def body(params, last_ids, clen, act, eo, gl, sd, rd,
                     drafts, tcaches):
                ids = jnp.concatenate(
                    [lax.slice_in_dim(last_ids, 0, tier, axis=0), drafts],
                    axis=1)                                   # (tier, W)
                pos = clen[:, None] \
                    + jnp.arange(W, dtype=jnp.int32)[None]    # (tier, W)
                out = fwd(params, {"ids": ids, "positions": pos,
                                   "cache_len": clen, **tcaches})
                # u[:, j]: the token the target emits at stream position
                # clen + 1 + j given the draft prefix — drawn with the
                # exact (seed, rid, position) key plain decode would use
                u = sample_tokens(out["logits"], samp,
                                  seeds=sd[:, None], rids=rd[:, None],
                                  positions=pos + 1)          # (tier, W)
                match = (drafts == u[:, :k]).astype(jnp.int32)
                m = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                n_base = m + 1            # accepted prefix + correction
                steps = jnp.arange(W, dtype=jnp.int32)[None]
                hit = (u == eo[:, None]) & (eo[:, None] >= 0) \
                    & (steps < n_base[:, None])
                any_eos = hit.any(axis=1)
                first_eos = jnp.argmax(hit, axis=1).astype(jnp.int32)
                n_emit = jnp.where(any_eos, first_eos + 1, n_base)
                n_emit = jnp.minimum(n_emit, gl)
                n_emit = jnp.minimum(n_emit, s_max - 1 - clen)
                n_emit = jnp.where(act, jnp.maximum(n_emit, 1), 0)
                new_last = jnp.take_along_axis(
                    u, jnp.maximum(n_emit - 1, 0)[:, None], axis=1)[:, 0]
                done = act & ((any_eos & (first_eos < n_emit))
                              | (n_emit >= gl)
                              | (clen + n_emit >= s_max - 1))
                li = last_ids[:, 0]
                li = lax.dynamic_update_slice(
                    li, jnp.where(act, new_last,
                                  lax.slice_in_dim(li, 0, tier, axis=0)),
                    (0,))
                return out, u, n_emit, done, li

            if cache.paged:

                def run(params, last_ids, cache_len, active, eos,
                        gen_left, seeds, rids, drafts, caches, page_tab):
                    clen = lax.slice_in_dim(cache_len, 0, tier, axis=0)
                    sl = lambda a: lax.slice_in_dim(a, 0, tier, axis=0)  # noqa: E731
                    tcaches = cache.gather_rows(caches, page_tab, tier)
                    out, u, n_emit, done, li = body(
                        params, last_ids, clen, sl(active), sl(eos),
                        sl(gen_left), sl(seeds), sl(rids), drafts,
                        tcaches)
                    new_caches = cache.scatter_span(
                        caches, out, page_tab, cache_len, tier, W)
                    return u, n_emit, done, li[:, None], new_caches

                return _jit(run, f"spec_verify_t{tier}_k{k}", donate=(1, 9))

            def run(params, last_ids, cache_len, active, eos, gen_left,
                    seeds, rids, drafts, caches):
                clen = lax.slice_in_dim(cache_len, 0, tier, axis=0)
                sl = lambda a: lax.slice_in_dim(a, 0, tier, axis=0)  # noqa: E731
                tcaches = {ck: lax.slice_in_dim(v, 0, tier, axis=bds[ck])
                           for ck, v in caches.items()}
                out, u, n_emit, done, li = body(
                    params, last_ids, clen, sl(active), sl(eos),
                    sl(gen_left), sl(seeds), sl(rids), drafts, tcaches)
                new_caches = {
                    ck: lax.dynamic_update_slice_in_dim(
                        caches[ck], out[ck].astype(caches[ck].dtype), 0,
                        axis=bds[ck])
                    for ck in caches}
                return u, n_emit, done, li[:, None], new_caches

            return _jit(run, f"spec_verify_t{tier}_k{k}", donate=(1, 9))

        return self.store.get_or_build(
            ("spec_verify", self._cache_tag, self._spec_salt, tier, k),
            build)

    def _spec_draft_fn(self, tier: int, k: int) -> Callable:
        """Self-speculative draft step: k width-1 decode passes through
        the first ``n`` layers of the *same* model.  The layer-stack
        ``lax.scan`` infers its length from the xs leading dim, so
        slicing the stacked params and caches to ``n`` layers replays
        the already-lowered per-layer decode plans — zero new lowers.
        Draft-step cache updates are discarded (read-only drafting);
        the verify step rewrites every touched position."""
        def build():
            stacks = self.model.layer_stacks("decode")
            scanned = [s for s in stacks if s[2] > 1]
            if len(stacks) != 1 or not scanned:
                raise ValueError(
                    "SelfSpecProposer needs a model whose decode phase "
                    "is a single scanned layer stack; "
                    f"{self.model.cfg.name} has "
                    f"{[s[0] for s in stacks]} — use the 'ngram' "
                    "proposer instead")
            stack_name, total = stacks[0][0], stacks[0][2]
            n = self._proposer.n_layers or max(1, total // 2)
            n = min(n, total)
            segs, _ = self.model.build_segments(
                "decode", tier, 1, s_max=self.cfg.s_max)
            info = ScheduleContext(local_batch=tier, seq_len=self.cfg.s_max,
                                   phase="decode", arch=self.model.cfg.name)
            fwd = build_forward(segs, self.scheduler, info,
                                lowered=self.cfg.lowered,
                                plan_cache=self.store if self.cfg.lowered
                                else None,
                                op_config=self._op_config)
            cache = self.cache
            bds = cache.batch_dims
            if any(not bds[ck] for ck in bds):
                raise ValueError(
                    "SelfSpecProposer needs stacked decode caches")
            samp = self._spec_sampling

            def body(params, last_ids, clen, sd, rd, tcaches):
                sub = dict(params)
                sub[stack_name] = jax.tree_util.tree_map(
                    lambda x: x[:n], params[stack_name])
                dc = {ck: lax.slice_in_dim(v, 0, n, axis=0)
                      for ck, v in tcaches.items()}
                cur = lax.slice_in_dim(last_ids, 0, tier, axis=0)
                cl = clen
                toks = []
                for _ in range(k):
                    out = fwd(sub, {"ids": cur, "positions": cl[:, None],
                                    "cache_len": cl, **dc})
                    tok = sample_tokens(out["logits"][:, -1, :], samp,
                                        seeds=sd, rids=rd,
                                        positions=cl + 1)
                    dc = {ck: out[ck].astype(dc[ck].dtype) for ck in dc}
                    cur = tok[:, None]
                    cl = cl + 1
                    toks.append(tok)
                return jnp.stack(toks, axis=1)                # (tier, k)

            if cache.paged:

                def run(params, last_ids, cache_len, seeds, rids, caches,
                        page_tab):
                    clen = lax.slice_in_dim(cache_len, 0, tier, axis=0)
                    tcaches = cache.gather_rows(caches, page_tab, tier)
                    return body(params, last_ids, clen,
                                lax.slice_in_dim(seeds, 0, tier, axis=0),
                                lax.slice_in_dim(rids, 0, tier, axis=0),
                                tcaches)

                return _jit(run, f"spec_draft_t{tier}_k{k}")

            def run(params, last_ids, cache_len, seeds, rids, caches):
                clen = lax.slice_in_dim(cache_len, 0, tier, axis=0)
                tcaches = {ck: lax.slice_in_dim(v, 0, tier, axis=bds[ck])
                           for ck, v in caches.items()}
                return body(params, last_ids, clen,
                            lax.slice_in_dim(seeds, 0, tier, axis=0),
                            lax.slice_in_dim(rids, 0, tier, axis=0),
                            tcaches)

            return _jit(run, f"spec_draft_t{tier}_k{k}")

        return self.store.get_or_build(
            ("spec_draft", self._cache_tag, self._spec_salt,
             self._proposer.identity(), tier, k), build)

    # -- harvest ----------------------------------------------------------
    def _harvest(self, pending):
        """The loop's single host sync: fetch the pending decode step's
        token/done vectors (plus any prefill first-token vectors) in one
        ``device_get`` and run the host bookkeeping.  Each request's
        bookkeeping runs inside its own error boundary — a poisoned
        request terminates as ``Failed`` without touching its
        batchmates."""
        prefills, self._pending_prefill = self._pending_prefill, []
        if pending is None and not prefills:
            return
        spec = pending is not None and isinstance(pending[0], str)
        if spec:
            fetch = list(pending[1:4])     # u, n_emit, done
        elif pending is not None:
            fetch = list(pending[:2])
        else:
            fetch = []
        i = len(fetch)
        fetch.extend(t for t, _ in prefills)
        with span(self._spans, "engine.harvest_wait", iter=self._cur_iter):
            vals = jax.device_get(fetch)
        self._stats["host_syncs"] += 1
        now = time.perf_counter()
        with span(self._spans, "engine.harvest", iter=self._cur_iter):
            # prefill first: in sync mode the same harvest also carries the
            # first decode step of the just-admitted rows
            for (_, slots), toks in zip(prefills, vals[i:]):
                for j, req in slots:
                    if req.done_s:
                        continue
                    try:
                        if self.faults is not None:
                            self.faults.check_harvest(req.rid)
                        req.output.append(int(toks[j]))
                        if not req.first_token_s:
                            req.first_token_s = now
                        if (len(req.output) >= req.max_new_tokens
                                or req.output[-1] == req.eos_id):
                            self._finish(req, now)
                        elif self._deadline_blown(req, now):
                            self._fail_deadline(req, now)
                    except Exception as e:              # noqa: BLE001
                        self._fail_request(req, f"harvest failed: {e}")
            if pending is None:
                return
            if spec:
                self._harvest_spec(vals, pending, now)
                return
            tok, done, snapshot = np.asarray(vals[0]), np.asarray(vals[1]), \
                pending[2]
            for row, req in snapshot:
                if req.done_s:       # finished by an earlier harvest: the
                    continue         # in-flight step decoded a stale row
                try:
                    if self.faults is not None:
                        self.faults.check_harvest(req.rid)
                    t = int(tok[row])
                    if req.output and req.output[-1] == -100:
                        req.output[-1] = t     # sentinel: first real token
                        if not req.first_token_s:
                            req.first_token_s = now
                    else:
                        req.output.append(t)
                    self._stats["decode_tokens"] += 1
                    if done[row]:
                        self._finish(req, now)
                    elif self._deadline_blown(req, now):
                        self._fail_deadline(req, now)
                except Exception as e:                  # noqa: BLE001
                    self._fail_request(req, f"harvest failed: {e}")

    def _harvest_spec(self, vals, pending, now: float):
        """Apply one verify step's results: append each row's accepted
        tokens (+ the correction), advance the host mirrors by the
        data-dependent amount, and roll the cache length — and, paged,
        the page reservation — back over the rejected tail.  Rollback
        is pure length bookkeeping: rejected-position KV is garbage the
        attention mask already hides and later writes overwrite."""
        u, n_emit, done = (np.asarray(vals[0]), np.asarray(vals[1]),
                           np.asarray(vals[2]))
        snapshot, k, tier = pending[4], pending[5], pending[6]
        accepted = 0
        for row, req in snapshot:
            if req.done_s:
                continue
            try:
                if self.faults is not None:
                    self.faults.check_harvest(req.rid)
                n = int(n_emit[row])
                toks = [int(t) for t in u[row, :n]]
                if toks and req.output and req.output[-1] == -100:
                    req.output[-1] = toks[0]       # sentinel: first token
                    req.output.extend(toks[1:])
                else:
                    req.output.extend(toks)
                if toks and not req.first_token_s:
                    req.first_token_s = now
                self._gen[row] += n
                self.cache.lengths[row] += n
                if n < k + 1:
                    self._stats["spec_rollbacks"] += 1
                    self.cache.rollback(row, int(self.cache.lengths[row]))
                self._stats["decode_tokens"] += n
                accepted += max(0, n - 1)
                if done[row]:
                    self._finish(req, now)
                elif self._deadline_blown(req, now):
                    self._fail_deadline(req, now)
            except Exception as e:                  # noqa: BLE001
                self._fail_request(req, f"harvest failed: {e}")
        self._stats["spec_accepted"] += accepted
        if self._observer is not None and snapshot:
            try:
                self._observer(
                    phase="spec_decode", arch=self.model.cfg.name,
                    local_batch=tier, seq_len=k,
                    seconds=now - self._spec_t0,
                    stats={"draft_k": k, "accepted": accepted,
                           "acceptance_rate":
                               accepted / max(1, k * len(snapshot))})
            except Exception:                       # noqa: BLE001
                self._observer = None

    # -- cache key mapping --------------------------------------------------
    def _cache_keys(self):
        """[(prefill_k, prefill_v, decode_k_cache, decode_v_cache)] pairs."""
        out = []
        pstacks = self.model.layer_stacks("prefill")
        dstacks = self.model.layer_stacks("decode")
        for ps, ds in zip(pstacks, dstacks):
            pname, _, pcount, _, psc_out = ps[:5]
            if "k" not in psc_out:
                continue
            popts = ps[5] if len(ps) > 5 else {}
            omap = popts.get("output_map", {})
            dopts = ds[5] if len(ds) > 5 else {}
            imap = dopts.get("input_map", {})
            pk = omap.get("k", f"{pname}.k" if pcount > 1 else "k")
            pv = omap.get("v", f"{pname}.v" if pcount > 1 else "v")
            out.append((pk, pv, imap.get("k_cache", "k_cache"),
                        imap.get("v_cache", "v_cache")))
        return out


def _jit(fn, name: str, donate: tuple = ()):
    """jit with buffer donation where the backend supports it (donation
    is a no-op warning on CPU, so skip it there to keep test logs clean).
    ``name`` names the device program: it lowers as ``jit_<name>``, so a
    profile tells step kinds and shapes apart."""
    fn.__name__ = fn.__qualname__ = name
    if donate and jax.default_backend() != "cpu":
        return jax.jit(fn, donate_argnums=donate)
    return jax.jit(fn)
