"""Request scheduling: serial FIFO baseline + continuous batching.

Two schedulers share one Request surface:

``FIFOScheduler`` is the honest serial baseline — ``step()`` pops requests
off the queue and runs ``engine.generate`` one at a time.  It exists as the
reference the batched path is benchmarked (and tested token-for-token)
against.

``ContinuousBatchingScheduler`` drives a pool engine — the dense
``BatchedEngine`` slot pool or the paged ``PagedEngine`` block-table pool,
both behind the same ``free_slots``/``admit_slot``/``decode_batch``
surface: it owns the admission policy (FIFO order, admit-before-decode),
the slot allocator (free list over pool rows), and the in-flight set.  Every
``step()`` first fills free slots from the queue head, then advances ALL
in-flight requests with one ``decode_batch`` call.  What an admission costs
inside that call is the ENGINE's choice: the dense pool (and the paged pool
in ``prefill_mode="staged"``) runs the whole single-row prefill inside
``admit_slot``; the paged pool's chunked default instead queues the
admission and ``decode_batch`` advances it ONE fixed-size chunk per step,
interleaved with the batched decode dispatch — a long prompt admits over
several steps while the resident batch keeps emitting tokens, and its slot
counts as in-flight the whole time (``admit_slot`` returned None).  Rows
that hit EOS or their token budget are freed at the step boundary and the
next ``step()`` refills them mid-flight: the batch never drains to refill,
which is what "continuous" means and where the throughput over the serial
loop comes from (one dispatch per token-step instead of one per request).

Static shapes still rule everything: the pool is a fixed ``[max_batch,
capacity, ...]`` allocation, so the decode executable compiles exactly once
per pool shape regardless of arrival order, mix of hit/miss requests, or
occupancy.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Deque, Dict, List, Optional

from repro.core.blockpool import AdmissionRejected, PoolSaturated
from repro.serving.engine import BatchedEngine, Engine, GenResult
from repro.serving.trace import install_gc_spans, span


class RequestOutcome:
    """Typed terminal states.  Shedding and preemption-era failures are
    DATA, not exceptions: callers inspect ``req.outcome`` instead of
    catching anything, and a load test can assert on the exact mix."""
    OK = "ok"
    SHED_QUEUE_FULL = "shed_queue_full"   # bounded queue rejected at submit
    SHED_DEADLINE = "shed_deadline"       # TTL expired before admission
    ERRORED = "errored"                   # permanent reject / engine error


@dataclass
class Request:
    request_id: int
    prompt: str
    max_new_tokens: Optional[int] = None
    use_recycling: bool = True
    admit: bool = False
    # sampling controls; 0 temperature = greedy (the paper's
    # do_sample=False default).  Rows at different temperatures mix
    # freely in one pool dispatch (engine `sample_batched`).
    temperature: float = 0.0
    top_k: int = 0
    # owner for per-tenant L2 byte quotas (threaded engine -> recycler ->
    # HostKVStore; the scheduler's quota check reads the same accounting)
    tenant: Optional[str] = None
    submitted_at: float = field(default_factory=time.perf_counter)
    # request clock: enqueue_t stamps at submit (== submitted_at, kept
    # under both names for back-compat), admit_t when a slot is taken,
    # first_token_t when the first token lands.
    enqueue_t: float = field(default_factory=time.perf_counter)
    admit_t: Optional[float] = None
    first_token_t: Optional[float] = None
    result: Optional[GenResult] = None
    error: Optional[str] = None          # set when admission rejects it
    # SLO deadline: seconds from submit the request stays worth serving.
    # ``deadline_t`` is the absolute perf_counter stamp; expired requests
    # are shed from the queue BEFORE claiming any pool blocks, and the
    # engine's victim policy prefers preempting the latest deadline.
    deadline_s: Optional[float] = None
    deadline_t: Optional[float] = field(default=None, repr=False)
    outcome: Optional[str] = None        # RequestOutcome.* once terminal
    _ids: Optional[object] = field(default=None, repr=False)  # encode memo
    # preemption resume payload (engine "preempted" event) + requeue count
    _resume: Optional[dict] = field(default=None, repr=False)
    _requeues: int = field(default=0, repr=False)

    def __post_init__(self):
        if self.deadline_s is not None and self.deadline_t is None:
            self.deadline_t = self.enqueue_t + self.deadline_s

    @property
    def done(self) -> bool:
        return self.result is not None or self.error is not None

    @property
    def queue_delay_s(self) -> Optional[float]:
        """Seconds spent queued before admission (None until admitted)."""
        if self.admit_t is None:
            return None
        return self.admit_t - self.enqueue_t


class FIFOScheduler:
    """Serial reference scheduler: one ``engine.generate`` per request."""

    def __init__(self, engine: Engine, *, max_batch: int = 8):
        self.engine = engine
        self.max_batch = max_batch
        self._queue: Deque[Request] = deque()
        self._next_id = 0
        self.completed: List[Request] = []

    def submit(self, prompt: str, **kw) -> Request:
        req = Request(self._next_id, prompt, **kw)
        self._next_id += 1
        self._queue.append(req)
        return req

    def pending(self) -> int:
        return len(self._queue)

    def step(self) -> List[Request]:
        """Serve up to max_batch requests from the queue head, sequentially
        (the engine's jit cache makes same-shape requests share one
        executable, but each still pays its own dispatch per token)."""
        served = []
        while self._queue and len(served) < self.max_batch:
            req = self._queue.popleft()
            req.admit_t = time.perf_counter()
            req.result = self.engine.generate(
                req.prompt, max_new_tokens=req.max_new_tokens,
                use_recycling=req.use_recycling, admit=req.admit,
                temperature=req.temperature, top_k=req.top_k,
                tenant=req.tenant)
            req.first_token_t = req.admit_t + req.result.ttft_s
            req.outcome = RequestOutcome.OK
            served.append(req)
            self.completed.append(req)
        return served

    def run(self) -> List[Request]:
        while self._queue:
            self.step()
        return self.completed


class ContinuousBatchingScheduler:
    """Admission policy + slot allocator over a ``BatchedEngine`` pool."""

    def __init__(self, engine: BatchedEngine, *,
                 max_admissions_per_step: Optional[int] = None,
                 admission_policy: str = "fifo",
                 tenant_quotas: Optional[Dict[str, int]] = None,
                 queue_limit: Optional[int] = None,
                 tenant_queue_limits: Optional[Dict[str, int]] = None,
                 max_requeues: int = 32):
        self.engine = engine
        install_gc_spans()
        # at most this many single-row prefills per step before decoding;
        # None = fill every free slot (prefill-heavy but maximal occupancy)
        if max_admissions_per_step is not None and max_admissions_per_step < 1:
            raise ValueError("max_admissions_per_step must be >= 1 (0 would "
                             "make run() spin forever admitting nothing)")
        if admission_policy not in ("fifo", "cache_aware"):
            raise ValueError(f"unknown admission_policy {admission_policy!r}")
        self.max_admissions = max_admissions_per_step
        # "fifo" refills strictly in arrival order; "cache_aware" prefers
        # the queued request with the DEEPEST resident prefix in the
        # engine's block trie (``trie.peek`` — recency untouched), so warm
        # requests admit with near-zero prefill work while the batch is
        # hot.  FIFO breaks ties (strict > comparison), so a queue of
        # all-cold requests degenerates to exact FIFO — no starvation of
        # equally-cold requests, though a steady warm stream can delay a
        # cold one (that's the policy's documented trade).
        self.admission_policy = admission_policy
        # tenant -> max L2 (HostKVStore) bytes.  Enforced at ADMIT time:
        # an over-quota tenant's request still decodes, but its
        # ``admit=True`` is downgraded so it cannot grow the store
        # further.  Serving is never rejected on quota.
        self.tenant_quotas = tenant_quotas
        # bounded backpressure: a full queue sheds AT SUBMIT with a typed
        # outcome instead of growing without bound (None = unbounded, the
        # pre-existing behavior).  ``tenant_queue_limits`` bounds each
        # tenant's share so one flooding tenant cannot occupy the whole
        # global budget.
        if queue_limit is not None and queue_limit < 1:
            raise ValueError("queue_limit must be >= 1")
        self.queue_limit = queue_limit
        self.tenant_queue_limits = tenant_queue_limits or {}
        if max_requeues < 1:
            raise ValueError("max_requeues must be >= 1")
        self.max_requeues = max_requeues
        self._queue: Deque[Request] = deque()
        self._next_id = 0
        self._free: List[int] = engine.free_slots()
        # feature-detect the preemption surface (PagedEngine); the dense
        # BatchedEngine has no resume/deadline kwargs and emits no events
        self._engine_preempts = hasattr(engine, "drain_events")
        self.in_flight: Dict[int, Request] = {}       # slot -> request
        self.completed: List[Request] = []
        self.stats = {"decode_steps": 0, "admissions": 0,
                      "instant_finishes": 0, "slot_reuses": 0,
                      "rejected": 0, "occupancy_sum": 0,
                      "quota_denied_admits": 0, "cache_aware_picks": 0,
                      "shed_queue_full": 0, "shed_deadline": 0,
                      "preemptions": 0, "resumes": 0,
                      "admissions_deferred": 0}

    # ------------------------------------------------------------------
    def _tenant_queued(self, tenant: Optional[str]) -> int:
        return sum(1 for r in self._queue if r.tenant == tenant)

    def submit(self, prompt: str, **kw) -> Request:
        req = Request(self._next_id, prompt, **kw)
        self._next_id += 1
        limit = self.tenant_queue_limits.get(req.tenant)
        if ((self.queue_limit is not None
                and len(self._queue) >= self.queue_limit)
                or (limit is not None
                    and self._tenant_queued(req.tenant) >= limit)):
            req.outcome = RequestOutcome.SHED_QUEUE_FULL
            req.error = "shed: queue full"
            self.completed.append(req)
            self.stats["shed_queue_full"] += 1
            return req
        self._queue.append(req)
        return req

    def _shed_expired(self) -> List[Request]:
        """Drop deadline-expired queued requests BEFORE they claim any
        pool blocks — serving a request that already missed its SLO only
        steals capacity from ones that can still make theirs."""
        now = time.perf_counter()
        shed: List[Request] = []
        if any(r.deadline_t is not None for r in self._queue):
            keep: Deque[Request] = deque()
            for r in self._queue:
                if r.deadline_t is not None and now >= r.deadline_t:
                    r.outcome = RequestOutcome.SHED_DEADLINE
                    r.error = "shed: deadline expired in queue"
                    self.completed.append(r)
                    self.stats["shed_deadline"] += 1
                    shed.append(r)
                else:
                    keep.append(r)
            self._queue = keep
        return shed

    def pending(self) -> int:
        return len(self._queue)

    # ------------------------------------------------------------------
    def _pop_next(self) -> Request:
        """Select and remove the next request to admit.  FIFO pops the
        queue head; cache_aware scans the queue for the deepest resident
        prefix in the engine's block trie (peek — no recency stamp) and
        falls back to FIFO when the engine has no trie/tokenizer or
        nothing queued is warm (strict > keeps arrival order on ties)."""
        if self._queue and self._queue[0]._resume is not None:
            # a preempted request resumes ahead of new arrivals — its
            # blocks were taken from it, it does not also lose its turn
            return self._queue.popleft()
        if self.admission_policy == "cache_aware" and len(self._queue) > 1:
            trie = getattr(self.engine, "trie", None)
            tok = getattr(self.engine, "tok", None)
            if trie is not None and tok is not None:
                best_i, best_d = 0, -1
                for i, req in enumerate(self._queue):
                    if req._ids is None:
                        req._ids = tok.encode(req.prompt)
                    depth, _ = trie.peek(req._ids)
                    if depth > best_d:
                        best_i, best_d = i, depth
                if best_i > 0:
                    self.stats["cache_aware_picks"] += 1
                    req = self._queue[best_i]
                    del self._queue[best_i]
                    return req
        return self._queue.popleft()

    def _admit_allowed(self, req: Request) -> bool:
        """Admit-time quota gate: False when the request's tenant is at or
        over its L2 byte quota (serving still proceeds, admission to the
        host store is what gets denied)."""
        if not req.admit or not self.tenant_quotas or req.tenant is None:
            return req.admit
        quota = self.tenant_quotas.get(req.tenant)
        if quota is None:
            return True
        store = getattr(getattr(self.engine, "recycler", None), "store", None)
        if store is None or store.tenant_usage(req.tenant) < quota:
            return True
        self.stats["quota_denied_admits"] += 1
        return False

    def _admit(self) -> List[Request]:
        """Fill free slots from the queue; returns requests that
        completed during admission (rejections and instant finishes)."""
        done: List[Request] = []
        budget = (len(self._free) if self.max_admissions is None
                  else min(self.max_admissions, len(self._free)))
        while self._queue and budget > 0:
            slot = self._free.pop()
            req = self._pop_next()
            req.admit_t = req.admit_t or time.perf_counter()
            kw = {}
            if self._engine_preempts:
                kw["deadline_t"] = req.deadline_t
                if req._resume is not None:
                    kw["resume"] = req._resume
            try:
                res = self.engine.admit_slot(
                    slot, req.prompt, max_new_tokens=req.max_new_tokens,
                    use_recycling=req.use_recycling,
                    admit=self._admit_allowed(req),
                    temperature=req.temperature, top_k=req.top_k,
                    tenant=req.tenant, **kw)
            except PoolSaturated:
                # transient: in-flight work will free blocks — put the
                # request BACK at the head and stop admitting this step
                # (later queue entries would hit the same wall)
                self._free.append(slot)
                self._queue.appendleft(req)
                self.stats["admissions_deferred"] += 1
                break
            except AdmissionRejected as e:
                # reject THIS request (it can never fit the pool) without
                # dropping the rest of the queue or the slot
                self._free.append(slot)
                req.error = str(e)
                req.outcome = RequestOutcome.ERRORED
                self.completed.append(req)
                self.stats["rejected"] += 1
                done.append(req)
                continue
            except Exception:
                self._free.append(slot)      # don't leak the slot
                raise
            if req._resume is not None:
                req._resume = None
                self.stats["resumes"] += 1
            self.stats["admissions"] += 1
            budget -= 1    # admission work happened either way (a staged
            #                prefill ran, or chunk steps were queued)
            if res is not None:                       # finished at token 0
                req.result = res
                req.outcome = RequestOutcome.OK
                if res.ttft_s and res.ttft_s > 0.0:
                    req.first_token_t = req.admit_t + res.ttft_s
                self.completed.append(req)
                self.stats["instant_finishes"] += 1
                self._free.append(slot)
                done.append(req)
                continue
            self.in_flight[slot] = req
        return done

    def _drain_engine_events(self, finished: List[Request]) -> None:
        """Apply the engine's typed lifecycle events: a "preempted" slot's
        request requeues AT THE HEAD with its resume payload (bounded by
        ``max_requeues`` — a request the pool can never hold errors out
        instead of cycling forever); an "errored" slot's request
        terminates with a typed outcome.  Slot->request mapping is stable
        within the step: the engine freed the row, but the scheduler only
        reuses a slot after processing its event here."""
        if not self._engine_preempts:
            return
        for kind, payload in self.engine.drain_events():
            slot = payload["slot"]
            req = self.in_flight.pop(slot, None)
            if req is None:
                continue     # already finalized (defensive)
            self._free.append(slot)
            if kind == "preempted" and req._requeues < self.max_requeues:
                req._resume = payload
                req._requeues += 1
                self.stats["preemptions"] += 1
                self._queue.appendleft(req)
                continue
            req.outcome = RequestOutcome.ERRORED
            req.error = (payload.get("error", "preempted: requeue limit")
                         if kind != "preempted"
                         else "preempted: requeue limit reached")
            self.completed.append(req)
            finished.append(req)

    def step(self) -> List[Request]:
        """Shed expired requests, admit into free slots, then advance
        every in-flight request one token.  Returns the requests that
        completed this step (including admission-time completions:
        rejections, sheds and instant finishes)."""
        with span("sched.step"):
            with span("sched.admit"):
                finished: List[Request] = list(self._shed_expired())
                finished.extend(self._admit())
            decoded = bool(self.in_flight)
            self.stats["occupancy_sum"] += len(self.in_flight)
            results = self.engine.decode_batch()
            with span("sched.finish"):
                self._finish(results, finished)
            self.stats["decode_steps"] += int(decoded)
        return finished

    def _finish(self, results, finished: List[Request]) -> None:
        """Hand the rows the engine finished back to their requests and
        free their slots, then apply the engine's lifecycle events."""
        for slot, result in results:
            req = self.in_flight.pop(slot)
            req.result = result
            req.outcome = RequestOutcome.OK
            # first-token wall time, reconstructed from the engine's TTFT
            # measurement relative to this request's admit stamp (the
            # engine measures TTFT from its own admission start, which is
            # within one step() of admit_t — documented approximation)
            if (req.admit_t is not None and result.ttft_s
                    and result.ttft_s > 0.0):
                req.first_token_t = req.admit_t + result.ttft_s
            self.completed.append(req)
            finished.append(req)
            if self._queue:
                self.stats["slot_reuses"] += 1
            self._free.append(slot)
        self._drain_engine_events(finished)

    def run(self) -> List[Request]:
        while self._queue or self.in_flight:
            self.step()
        return self.completed

    # ------------------------------------------------------------------
    def mean_occupancy(self) -> float:
        steps = max(self.stats["decode_steps"], 1)
        return self.stats["occupancy_sum"] / steps
