"""Continuous batching scheduler.

Drives a :class:`GenerationEngine`'s slot API: admits queued requests into
free decode slots as soon as they open (prefill-on-admit), runs one fused
decode *chunk* (up to ``decode_chunk`` tokens per slot, compiled as one
``lax.scan`` with on-device sampling and termination masks) per tick for
all active slots, retires finished requests on chunk boundaries and
immediately backfills. This is the serving loop a TPU pod actually needs —
the paper's per-request ``model.predict()`` generalised to batched,
compiled execution, with ONE host<->device sync per chunk instead of one
per token (the dispatch-bound regime continuous-batching systems target).

Admission is *non-blocking*: placing a request dispatches its prefill and
an on-device argmax for the first token, but the host read of that token
is deferred to the tick's single sync point — admitting a request overlaps
the in-flight decode work instead of stalling every active slot.

Admission order is pluggable: by default a FIFO deque (arrival order), or a
:class:`~repro.serving.qos.AdmissionController` — priority classes,
per-client fairness, and deadline shedding — when one is passed. Shed
requests retire with ``error_code='DEADLINE_EXCEEDED'`` without ever
touching an engine slot. With ``rate_unit="token"`` in the QoS config,
admission cost is charged as ``max_new_tokens`` instead of a flat 1 —
long generations are priced honestly by the token buckets and the DRR
fairness quantum alike.

Streaming and cancellation ride the same chunk boundaries: each
:class:`Request` may carry a ``token_sink`` fed at the tick's single sync
point with exactly the tokens that sync revealed (no extra host syncs),
``first_token_s`` is stamped at the request's first sync, and
``cancel(request_id)`` drops queued work from admission (never touching a
slot) or frees a running slot at the next chunk boundary — freed slots
backfill in the same tick, and cancelled requests retire with
``error_code='CANCELLED'``.

Admission is additionally *block-gated* on paged engines: a request is
placed only when the shared KV page pool can hold its prefill
(``engine.can_admit``). The FIFO path holds its head in line; the QoS
path parks already-granted tickets in a deferred queue with first claim
on freed pages. Before each chunk the scheduler secures a page per
upcoming KV write (``ensure_capacity``) — a slot that cannot take a
single further write retires cleanly with ``KV_POOL_EXHAUSTED`` instead
of stalling the co-batch, and prompts that could never be satisfied
(no generation headroom -> ``PROMPT_TOO_LONG``; more pages than the pool
holds) retire without touching a slot.

Invariants (property-tested):
- a slot is never double-occupied;
- admission never starves: FIFO is arrival order; under QoS every
  non-empty priority class is served within one weighted round, and order
  *within* a (class, client) pair stays FIFO;
- every admitted request retires with <= max_new_tokens generated;
- fused K-step decode is token-identical to K single steps;
- a slot whose cache fills retires cleanly with ``MAX_SEQ_EXCEEDED``
  instead of writing past ``max_seq``;
- throughput accounting: sum of emitted tokens == sum over requests, and
  ``wall_s`` accrues per tick so ``tokens_per_s`` is real whichever loop
  drives ``tick()``; ``wall_s == host_s + sync_wait_s``.

A tick is four profiler spans in a row (``tracing.span``): ``max.sched.admit``
(cancel sweep, admission and the prefills it dispatches),
``max.sched.dispatch`` (budgets, pages, the chunk's enqueue),
``max.sched.sync`` (the one sanctioned sync, including the wait to take
the GIL back) and ``max.sched.deliver`` (commit, sinks, retire). The
stats count the same boundaries: ``sync_wait_s`` is the sync span's wall
time, ``host_s`` the rest of the tick, ``host_cpu_s`` the worker's CPU
time over that rest.

Thread-safety: ``submit``/``poll``/``tick`` take an internal lock so HTTP
threads can enqueue while a single worker thread drives ``tick`` (the model
used by ``core.service.BatchedService``). Engine state is only ever touched
from inside ``tick``, i.e. from whichever single thread drives the loop.

Fault boundary: the two places a tick touches the engine — prefill
admission and the fused chunk dispatch/commit — are supervised. An
exception there quarantines only the implicated slots (an injected fault
names its victim; a real exception implicates the whole co-batch, whose
device state is no longer trustworthy), retiring them as structured
``ENGINE_FAULT`` instead of unwinding the worker. Uncommitted chunk work
is dropped safely: sinks and ``req.output`` are only fed from committed
sync points, so a faulted chunk never half-delivers tokens. An optional
:class:`~repro.serving.faults.FaultPlane` injects deterministic faults at
exactly these boundaries; with ``faults=None`` each hook is a single
``is not None`` check and behavior is byte-identical to a build without
injection.
"""

from __future__ import annotations

import itertools
import threading
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.serving.engine import GenerationEngine
from repro.serving.faults import FaultPlane, InjectedFault
from repro.serving.tracing import cpu_now as _cpu_now, now as _now
from repro.serving.tracing import span as _span


# eq=False: requests compare by IDENTITY. Beyond being semantically right
# (two requests are never "the same work" by field value), it keeps
# deque.remove() a pure C-level scan with no Python-level __eq__ thread-
# switch points — submit() appends lock-free, and a generated __eq__ would
# let an append land mid-remove and blow up the cancel sweep.
@dataclass(eq=False)
class Request:
    id: int
    prompt: List[int]
    max_new_tokens: int = 32
    temperature: float = 0.0
    extra: Optional[Dict[str, Any]] = None
    # QoS identity (set when submitted through an AdmissionController)
    priority: str = "batch"
    client: str = "anon"
    # per-chunk token sink: called at the tick's sync point with the tokens
    # the chunk produced for this request (the streaming surface rides this
    # — no extra host syncs). Runs under the scheduler lock on the worker
    # thread, so it must be O(1) and non-blocking; exceptions are swallowed.
    token_sink: Optional[Any] = None
    # absolute monotonic start-by deadline (the controller enforces it
    # while queued; this copy covers the block-deferred wait, where the
    # ticket is already granted)
    deadline_at: Optional[float] = None
    # filled by the scheduler
    output: List[int] = field(default_factory=list)
    slot: int = -1
    admitted_at_tick: int = -1
    finished_at_tick: int = -1
    # lifecycle timestamps on the serving clock (tracing.now): stamped at
    # existing sync points whether or not a tracer is attached, so the
    # service layer can always report queue/prefill/decode phase durations
    submitted_at_s: float = 0.0
    admitted_at_s: Optional[float] = None
    finished_at_s: Optional[float] = None
    first_token_s: Optional[float] = None  # serving clock, first sync point
    trace: Optional[Any] = field(default=None, repr=False)  # RequestTrace
    cancelled: bool = False                # set via Scheduler.cancel()
    error: Optional[str] = None
    error_code: Optional[str] = None      # e.g. DEADLINE_EXCEEDED when shed

    @property
    def done(self) -> bool:
        return self.finished_at_tick >= 0


@dataclass
class SchedulerStats:
    ticks: int = 0
    decode_steps: int = 0             # engine decode steps (chunk = K steps)
    chunks: int = 0                   # fused chunk dispatches (sync points)
    prefills: int = 0
    emitted_tokens: int = 0
    completed: int = 0
    shed: int = 0                     # deadline-expired, never ran
    cancelled: int = 0                # cancelled while queued or running
    cache_overflows: int = 0          # retired with MAX_SEQ_EXCEEDED
    pool_exhausted: int = 0           # retired with KV_POOL_EXHAUSTED
    rejected: int = 0                 # retired with PROMPT_TOO_LONG
    engine_faults: int = 0            # retired with ENGINE_FAULT
    wall_s: float = 0.0               # accrued per tick (run() adds nothing)
    host_s: float = 0.0               # tick wall time outside the sync
    sync_wait_s: float = 0.0          # tick wall time inside the sync
    host_cpu_s: float = 0.0           # worker CPU time over host_s
    occupancy_sum: int = 0            # sum of active-batch sizes per decode
    max_occupancy: int = 0
    # sum over decode steps of the batch's live context tokens (host
    # length mirror): over decode_steps * max_batch * max_seq, the share
    # of the reserved KV in use
    kv_tokens_sum: int = 0

    @property
    def tokens_per_s(self) -> float:
        return self.emitted_tokens / self.wall_s if self.wall_s else 0.0

    @property
    def mean_batch_size(self) -> float:
        return self.occupancy_sum / self.decode_steps \
            if self.decode_steps else 0.0


class ContinuousBatchingScheduler:
    def __init__(self, engine: GenerationEngine, *, seed: int = 0,
                 retain_completed: int = 1024, admission=None,
                 decode_chunk: Optional[int] = None, tracer=None,
                 faults=None):
        self.engine = engine
        # Optional fault-injection plane (FaultPlane | FaultSpec | dict).
        # None keeps every hook a bare attribute check — byte-identical
        # behavior with injection compiled out.
        if faults is not None and not isinstance(faults, FaultPlane):
            faults = FaultPlane(faults)
        self.faults = faults
        # consecutive engine faults with no committed chunk in between —
        # the supervising service's rebuild trigger
        self.fault_streak = 0
        # Optional[Tracer]: span recording at the existing sync points.
        # Every hook below is guarded so tracer=None costs one attribute
        # check per boundary, nothing on the per-token path.
        self.tracer = tracer
        # scheduler-local override: two schedulers sharing an engine (e.g.
        # a warm-up one) must not reconfigure each other through it.
        # Floored to a power of two like the engine default — the reported
        # decode_chunk must be the one that actually runs
        self._decode_chunk = 1 << (max(1, int(decode_chunk)).bit_length() - 1) \
            if decode_chunk is not None else None
        self.admission = admission        # Optional[AdmissionController]
        self.queue: deque[Request] = deque()      # FIFO path (admission=None)
        # QoS-admitted work waiting for KV pool blocks (paged engines): the
        # controller already dequeued it, so it holds first claim — in its
        # dequeue order — on blocks freed by retiring slots
        self._deferred: deque[Request] = deque()
        self.active: Dict[int, Request] = {}      # slot -> request
        # per-slot temperature: mixed-temperature batches must not
        # interfere (fixed [max_batch] shape keeps the decode compile-stable)
        self._temps = np.zeros((engine.max_batch,), np.float32)
        # requests placed this tick whose on-device first token has not
        # been read yet (resolved at the tick's sync point)
        self._pending_first: List[Tuple[Request, jax.Array]] = []
        self._rng = jax.random.PRNGKey(seed)
        self._ids = itertools.count()
        self._lock = threading.RLock()
        # bounded: callers that hold their own Request reference (the
        # batched service) never poll, so retention must not grow with
        # server lifetime
        self.retain_completed = retain_completed
        self._completed: Dict[int, Request] = {}
        # id -> every not-yet-retired request (queued OR active), so
        # cancel() can find work wherever it currently lives. Inserted by
        # submit (lock-free: dict setitem is atomic under the GIL, same
        # contract as the FIFO deque), removed at retire under the lock.
        self._pending: Dict[int, Request] = {}
        self.stats = SchedulerStats()

    @property
    def decode_chunk(self) -> int:
        return self._decode_chunk if self._decode_chunk is not None \
            else self.engine.decode_chunk

    def submit(self, prompt: List[int], *, max_new_tokens: int = 32,
               temperature: float = 0.0,
               extra: Optional[Dict[str, Any]] = None,
               priority: Optional[str] = None,
               client: Optional[str] = None,
               deadline_s: Optional[float] = None,
               token_sink: Optional[Any] = None) -> Request:
        """Enqueue a request. With an admission controller attached this
        may raise a :class:`~repro.serving.qos.AdmissionError`
        (rate-limited / queue-full) on the *submitting* thread — rejection
        must never reach the decode loop.

        ``token_sink`` is installed before the request becomes visible to
        the decode loop, so a streaming caller never misses tokens.

        Deliberately does NOT take the scheduler lock: ``tick`` holds it
        across a whole engine decode chunk, and request threads must not
        queue behind JAX compute just to enqueue. The id counter is an
        atomic ``itertools.count``; the controller and the FIFO deque have
        their own synchronization."""
        t_sub = _now()
        req = Request(next(self._ids), list(prompt), max_new_tokens,
                      temperature, extra, token_sink=token_sink,
                      submitted_at_s=t_sub,
                      deadline_at=(t_sub + deadline_s
                                   if deadline_s is not None else None))
        if self.tracer is not None:
            req.trace = self.tracer.start(
                req.id, prompt_tokens=len(req.prompt),
                max_new_tokens=max_new_tokens, submitted_at=t_sub)
        self._pending[req.id] = req
        if self.admission is not None:
            try:
                ticket = self.admission.submit(
                    req, priority=priority, client=client,
                    cost=self.admission.cfg.request_cost(max_new_tokens),
                    deadline_s=deadline_s)
            except Exception as e:
                self._pending.pop(req.id, None)   # rejected: nothing to cancel
                if req.trace is not None:         # rejection is a complete
                    code = getattr(e, "code", "REJECTED")   # trace too
                    self.tracer.finish(req.trace, outcome=code,
                                       error_code=code)
                raise
            req.priority, req.client = ticket.priority, ticket.client
            if req.trace is not None:
                req.trace.priority, req.trace.client = \
                    ticket.priority, ticket.client
                req.trace.event("qos_enqueue", **{
                    "class": ticket.priority, "client": ticket.client,
                    "cost": ticket.cost})
        else:
            if req.trace is not None:
                req.trace.priority, req.trace.client = \
                    req.priority, req.client
            self.queue.append(req)      # deque.append is atomic
        return req

    def cancel(self, request_id: int) -> bool:
        """Cancel a queued or running request.

        Marks the request; the decode loop honors the mark at its next
        boundary — a queued request is dropped from admission without ever
        touching a slot, a running one frees its slot at the next chunk
        boundary (and its partial output stays on the request). Both retire
        with ``error_code='CANCELLED'``. Returns False when the request is
        unknown or already finished (cancellation raced completion)."""
        with self._lock:
            req = self._pending.get(request_id)
            if req is None or req.done:
                return False
            req.cancelled = True
        return True

    def poll(self, request_id: int) -> Optional[Request]:
        """Completed request by id, else None (still queued/active)."""
        with self._lock:
            return self._completed.get(request_id)

    def queued_count(self) -> int:
        # lock-free: depth()/len() are point-in-time reads used for window
        # heuristics and stats — they must not stall behind a decode step
        if self.admission is not None:
            return self.admission.depth() + len(self._deferred)
        return len(self.queue)

    def has_work(self) -> bool:
        if self.admission is not None:
            return bool(self.admission.depth() or self._deferred
                        or self.active)
        return bool(self.queue or self.active)

    def active_count(self) -> int:
        """Occupied decode slots right now (lock-free point-in-time read
        — the fleet dispatcher's load signal alongside queued_count)."""
        return len(self.active)

    # -- scheduling ----------------------------------------------------------

    def _retire(self, req: Request):
        req.finished_at_tick = self.stats.ticks
        req.finished_at_s = _now()
        req.extra = None              # may pin large arrays (image embeds…)
        self._pending.pop(req.id, None)
        self._completed[req.id] = req
        while len(self._completed) > self.retain_completed:
            self._completed.pop(next(iter(self._completed)))
        if req.trace is not None:
            # every retire path funnels here, so cancelled/shed/exhausted
            # requests get complete traces too — exactly the ones pulled
            self.tracer.finish(req.trace,
                               outcome=req.error_code or "ok",
                               error_code=req.error_code,
                               tick=self.stats.ticks,
                               completion_tokens=len(req.output),
                               ts=req.finished_at_s)

    def _shed(self, req: Request):
        if req.cancelled:             # cancelled while queued: its own code
            self._cancel_retire(req)
            return
        req.error = ("deadline exceeded while queued "
                     f"(waited for a decode slot, class {req.priority!r})")
        req.error_code = "DEADLINE_EXCEEDED"
        if req.trace is not None:
            req.trace.event("qos_shed", **{"class": req.priority,
                                           "client": req.client})
        self._retire(req)
        self.stats.shed += 1

    def _cancel_retire(self, req: Request):
        """Retire a cancelled request (queued: never ran; active: caller
        releases the slot first). Partial output stays on the request."""
        req.error = (f"cancelled after {len(req.output)} generated tokens"
                     if req.output else "cancelled before starting")
        req.error_code = "CANCELLED"
        if req.trace is not None:
            req.trace.event("cancel", ran=req.slot >= 0,
                            generated=len(req.output))
        self._retire(req)
        self.stats.cancelled += 1

    def _too_long(self, req: Request):
        """Defense-in-depth for direct submitters: the service layer
        rejects these at validation time (PROMPT_TOO_LONG, HTTP 400), but
        a raw ``submit`` must still retire instead of queueing forever."""
        req.error = (f"prompt of {len(req.prompt)} tokens leaves no "
                     f"generation headroom (max_seq {self.engine.max_seq}, "
                     f"max admissible {self.engine.max_prompt_len()})")
        req.error_code = "PROMPT_TOO_LONG"
        self._retire(req)
        self.stats.rejected += 1

    def _pool_exhausted(self, req: Request):
        """The shared KV pool cannot give the slot its next page: retire
        cleanly (partial output stays on the request) rather than stall
        the whole co-batch behind an unpageable slot. Preemption could
        instead swap the slot out here — same boundary, future work."""
        req.error = (f"KV pool exhausted after {len(req.output)} generated "
                     f"tokens (requested {req.max_new_tokens}; pool = "
                     f"{self.engine.kv_pool_blocks} pages of "
                     f"{self.engine.page_size} tokens)")
        req.error_code = "KV_POOL_EXHAUSTED"
        if req.trace is not None:
            req.trace.event("stall", kind="KV_POOL_EXHAUSTED",
                            generated=len(req.output))
        self._release(req)
        # ran and retired -> counted completed (same reconciliation rule
        # as MAX_SEQ_EXCEEDED) plus the specific exhaustion counter
        self.stats.completed += 1
        self.stats.pool_exhausted += 1

    def _engine_fault_retire(self, req: Request, msg: str, site: str):
        """Retire ``req`` as structured ENGINE_FAULT (HTTP 500). The fault
        is scoped to the request, never the worker: the supervising
        service sees the code and decides retry/terminal per its
        delivered-token state."""
        req.error = f"engine fault during {site}: {msg}"
        req.error_code = "ENGINE_FAULT"
        if req.trace is not None:
            req.trace.event("fault", site=site, generated=len(req.output))
        self._retire(req)
        self.stats.engine_faults += 1
        self.fault_streak += 1

    def _quarantine_slot(self, slot: int, msg: str, site: str):
        """Evict one active slot after a fault. The release passes no
        tokens — a faulted slot's KV is suspect and must not be registered
        with the prefix cache — and is defensive: a partially-inserted
        slot still returns whatever pages it took."""
        req = self.active.pop(slot, None)
        if req is None:
            return
        try:
            self.engine.release_slot(slot)
        # maxlint: allow[exception-safety] reason=defensive release while quarantining an already-faulted slot; the quarantine itself records the ENGINE_FAULT outcome
        except Exception:
            pass
        self._pending_first = [(r, f) for (r, f) in self._pending_first
                               if r is not req]
        self._engine_fault_retire(req, msg, site)

    def quarantine_active(self, reason: str, *, site: str = "engine"):
        """Retire EVERY active slot as ENGINE_FAULT and drop unread first
        tokens. Used when engine state as a whole is no longer
        trustworthy: a real (non-injected) exception from a fused dispatch,
        a dead worker found by the watchdog, or an engine rebuild."""
        with self._lock:
            for slot in sorted(self.active):
                self._quarantine_slot(slot, reason, site)
            for req, _ in self._pending_first:
                # placed this tick but never resolved: the request is in
                # active and was handled above unless insert raced — drop
                # any stragglers without reading poisoned device values
                if not req.done:
                    self._engine_fault_retire(req, reason, site)
            self._pending_first.clear()

    @staticmethod
    def _sweep_queue(q: "deque[Request]") -> List[Request]:
        """Remove cancelled entries from ``q`` in place and return them.

        Single filtered pass over a snapshot + per-item ``remove`` — never
        the popleft/append rotation the previous version used: ``submit``
        appends lock-free, and an arrival landing mid-rotation was spliced
        between rotated items, losing its FIFO position. ``remove`` leaves
        every other element (including concurrent tail appends) exactly
        where it was.
        """
        swept = []
        for req in [r for r in list(q) if r.cancelled]:
            try:
                q.remove(req)
            except (ValueError, IndexError, RuntimeError):
                continue              # raced another sweep / a concurrent
            swept.append(req)         # append (retry next tick)
        return swept

    def _sweep_cancelled(self):
        """Honor cancellation marks — runs at the top of the tick, BEFORE
        admission, so a slot freed by a running cancel backfills this very
        tick. Queued FIFO work and block-deferred work are swept in place
        (the admission-controller path sweeps inside ``take``)."""
        for req in [r for r in self.active.values() if r.cancelled]:
            self.engine.release_slot(req.slot,
                                     tokens=req.prompt + req.output)
            del self.active[req.slot]
            self._cancel_retire(req)
        if self.admission is None:
            for req in self._sweep_queue(self.queue):
                self._cancel_retire(req)
        for req in self._sweep_queue(self._deferred):
            self._cancel_retire(req)
        # deadlines keep ticking while a granted ticket waits for pool
        # blocks — the controller only enforces them up to the grant
        now = _now()
        for req in [r for r in list(self._deferred)
                    if r.deadline_at is not None and r.deadline_at < now]:
            try:
                self._deferred.remove(req)
            except (ValueError, IndexError, RuntimeError):
                continue
            self._shed(req)

    def _place(self, req: Request, slot: int) -> bool:
        """Dispatch prefill + on-device first token; no host sync here —
        the first token is read with the chunk at the tick's sync point.

        Returns False when admission faulted: the request retires as
        ENGINE_FAULT (it never emitted a token, so the service layer can
        requeue it safely) and the slot stays free for the next request."""
        req.admitted_at_s = _now()
        try:
            if self.faults is not None:
                self.faults.check_admission(self.stats.ticks)
            first = self.engine.insert_request(req.prompt, slot,
                                               extra=req.extra)
        except Exception as e:
            # a partial insert may have taken pool pages before raising;
            # a defensive release returns them (no-op on an untouched slot)
            try:
                self.engine.release_slot(slot)
            # maxlint: allow[exception-safety] reason=defensive page release after a failed insert; the ENGINE_FAULT retire right below carries the structured outcome
            except Exception:
                pass
            self._engine_fault_retire(req, str(e), "admission")
            return False
        req.slot = slot
        req.admitted_at_tick = self.stats.ticks
        self._temps[slot] = req.temperature
        self.active[slot] = req
        self._pending_first.append((req, first))
        self.stats.prefills += 1
        if req.trace is not None:
            # the engine's host-side admission summary (prefix-cache hit
            # tokens vs cold prefill, pages allocated, COW) — the
            # warm-vs-cold distinction operators diff traces on
            req.trace.admitted(
                req.admitted_at_s, slot=slot, tick=self.stats.ticks,
                admission=getattr(self.engine, "last_admission", None))
        return True

    def _admit_charge(self, req: Request):
        """What the admission gate charges for ``req``: the token list —
        a prefix-cached engine then charges only the pages the cache
        cannot seat — unless the request carries extra inputs, which
        bypass the cache (KV not a pure function of the token ids) and
        pay the full page count."""
        if req.extra or self.engine.extra_inputs:
            return len(req.prompt)
        return req.prompt

    def _never_admissible(self, req: Request) -> bool:
        """True for requests no amount of waiting can place: prompts with
        no generation headroom and prompts whose prefill needs more pages
        than the whole pool holds."""
        if not self.engine.fits_prompt(len(req.prompt)):
            return True
        return (self.engine.paged
                and self.engine.blocks_for_prompt(len(req.prompt))
                > self.engine.kv_pool_blocks)

    def _retire_inadmissible(self, req: Request):
        if not self.engine.fits_prompt(len(req.prompt)):
            self._too_long(req)
            return
        req.error = (f"prompt of {len(req.prompt)} tokens needs more "
                     f"KV pool pages than the pool holds "
                     f"({self.engine.kv_pool_blocks} pages of "
                     f"{self.engine.page_size} tokens)")
        req.error_code = "KV_POOL_EXHAUSTED"
        self._retire(req)
        self.stats.pool_exhausted += 1

    def _admit(self):
        """Admission is gated on free *slots* AND (paged engines) free
        pool *blocks*: a prompt whose prefill pages cannot be allocated
        holds its place in line instead of being placed just to starve."""
        free = self.engine.free_slots()
        blocked = False
        # block-deferred work first: the controller already granted it
        while free and self._deferred:
            req = self._deferred[0]
            if req.cancelled:
                self._deferred.popleft()
                self._cancel_retire(req)
                continue
            if not self.engine.can_admit(self._admit_charge(req)):
                blocked = True                    # pool still tight: hold
                break                             # order, retry next tick
            self._deferred.popleft()
            if req.trace is not None:
                req.trace.event("deferred_unpark")
            slot = free.pop(0)
            if not self._place(req, slot):
                free.insert(0, slot)      # admission faulted: slot unused
        if self.admission is not None:
            # controller decides order; it also sweeps deadline-expired
            # and cancelled work even when no slot is free (k == 0) so
            # doomed requests fail promptly instead of rotting behind a
            # full batch
            tickets, shed = self.admission.take(
                0 if blocked else len(free))
            for t in shed:
                self._shed(t.item)
            for t in tickets:
                if t.item.trace is not None:
                    t.item.trace.event("qos_grant", **{
                        "class": t.priority, "client": t.client})
                if t.item.cancelled:              # raced the sweep
                    self._cancel_retire(t.item)
                    continue
                if self._never_admissible(t.item):
                    self._retire_inadmissible(t.item)
                    continue
                if not free or not self.engine.can_admit(
                        self._admit_charge(t.item)):
                    # no slot left (an earlier ticket took the last) or no
                    # pool blocks: hold in grant order until capacity frees
                    if t.item.trace is not None:
                        t.item.trace.event(
                            "deferred_park",
                            reason="no_slot" if not free else "no_blocks")
                    self._deferred.append(t.item)
                    continue
                slot = free.pop(0)
                if not self._place(t.item, slot):
                    free.insert(0, slot)
            return
        while free and self.queue and not blocked:
            req = self.queue[0]                   # peek: FIFO holds even
            if req.cancelled:                     # when blocks are tight
                self.queue.popleft()
                self._cancel_retire(req)
                continue
            if self._never_admissible(req):
                self.queue.popleft()
                self._retire_inadmissible(req)
                continue
            if not self.engine.can_admit(self._admit_charge(req)):
                break                             # blocks exhausted: wait
            self.queue.popleft()                  # FIFO: no starvation
            slot = free.pop(0)
            if not self._place(req, slot):
                free.insert(0, slot)

    def _maybe_finish(self, req: Request):
        eos = self.engine.eos_id
        if (len(req.output) >= req.max_new_tokens
                or (eos is not None and req.output and req.output[-1] == eos)):
            self._release(req)
            self.stats.completed += 1

    def _release(self, req: Request):
        # tokens as fed (prompt + generated) let a prefix-cached engine
        # register the slot's fully-decoded pages before they free — a
        # multi-turn continuation then hits the whole previous exchange
        self.engine.release_slot(req.slot, tokens=req.prompt + req.output)
        del self.active[req.slot]
        self._retire(req)

    def _overflow(self, req: Request):
        """Cache full before the request finished: retire cleanly instead
        of writing past ``max_seq`` (the engine's termination mask already
        froze the slot on device)."""
        req.error = (f"sequence reached max_seq {self.engine.max_seq} after "
                     f"{len(req.output)} generated tokens "
                     f"(requested {req.max_new_tokens})")
        req.error_code = "MAX_SEQ_EXCEEDED"
        self._release(req)
        # counted as completed (it ran and retired — the service layer
        # counts it too, keeping the two 'completed' totals reconciled;
        # only shed work is excluded on both sides) plus the specific
        # overflow counter
        self.stats.completed += 1
        self.stats.cache_overflows += 1

    def _feed_sink(self, req: Request, tokens: List[int]):
        """Per-chunk token delivery + first-token timestamp, at the sync
        point. A sink fault must never poison the co-batch's tick."""
        if req.first_token_s is None:
            req.first_token_s = _now()
            if req.trace is not None:
                req.trace.first_token(req.first_token_s)
        if req.token_sink is not None:
            try:
                req.token_sink(tokens)
            # maxlint: allow[exception-safety] reason=a faulty subscriber sink must not kill the batch; tokens stay in req.output and the request still retires with its outcome
            except Exception:
                pass

    def _resolve_pending_first(self):
        """The deferred host reads for this tick's admissions (the decode
        chunk for previously-active slots is already in flight)."""
        for req, first in self._pending_first:
            # maxlint: allow[host-sync] reason=part of the single sanctioned sync point: deferred first-token reads resolve at the chunk boundary
            req.output.append(int(first))
            self.stats.emitted_tokens += 1
            # maxlint: allow[host-sync] reason=part of the single sanctioned sync point: deferred first-token reads resolve at the chunk boundary
            self._feed_sink(req, [int(first)])
        self._pending_first.clear()

    def tick(self):
        """One scheduler iteration: admit -> decode chunk -> retire.

        Exactly one host sync per tick (reading the chunk's token block),
        however many tokens the chunk produced."""
        t0 = _now()
        c0 = _cpu_now()
        emitted_before = self.stats.emitted_tokens
        faults_before = self.stats.engine_faults
        prefills_before = self.stats.prefills
        chunk_k = 0
        with _span("max.sched.tick") as tick_span, self._lock:
            with _span("max.sched.admit") as sp:
                self._sweep_cancelled()
                self._admit()
                sp.set_metadata(
                    admitted=self.stats.prefills - prefills_before)
                t_admit = _now()
            with _span("max.sched.dispatch") as sp:
                toks = emitted = None
                if self.active:
                    budgets = np.zeros((self.engine.max_batch,), np.int32)
                    pending = {id(r) for r, _ in self._pending_first}
                    for slot, req in self.active.items():
                        have = len(req.output) + (1 if id(req) in pending
                                                  else 0)
                        budgets[slot] = max(0, req.max_new_tokens - have)
                    if self.engine.paged:
                        # every KV write this chunk needs a pool page
                        # secured BEFORE dispatch. A slot that cannot take
                        # one more write retires NOW (its pages may unblock
                        # the slots ensured after it); a partially-secured
                        # slot decodes up to its headroom and retries next
                        # tick.
                        for slot, req in list(self.active.items()):
                            if budgets[slot] <= 0:
                                continue
                            got = self.engine.ensure_capacity(
                                slot,
                                min(self.decode_chunk, int(budgets[slot])))
                            if got == 0:
                                if (self.engine.context_len(slot)
                                        >= self.engine.max_seq):
                                    self._overflow(req)
                                else:
                                    self._pool_exhausted(req)
                                budgets[slot] = 0
                                continue
                            budgets[slot] = min(int(budgets[slot]), got)
                if self.active:
                    # budget-aligned chunk: never decode past the earliest
                    # completion, so a finishing request's result is
                    # visible at the very next sync instead of idling
                    # masked behind longer co-tenants (interactive latency
                    # == stepwise while long batches still amortize the
                    # full chunk). Rounded down to a power of two so the
                    # engine compiles a bounded set of scan programs
                    # ({1,2,4,8,...}) — a solo request's budget decomposes
                    # binarily, warming every size it will ever use.
                    k = min(self.decode_chunk,
                            max(1, min(int(budgets[s]) for s in self.active)))
                    k = 1 << (k.bit_length() - 1)
                    chunk_k = k
                    try:
                        if self.faults is not None:
                            # may raise InjectedFault / WorkerKill, or
                            # stall. WorkerKill is a BaseException: it
                            # unwinds past tick (the `with` releases the
                            # lock) and kills the driving thread — the
                            # watchdog's problem.
                            self.faults.check_chunk(self.stats.ticks,
                                                    sorted(self.active))
                        # maxlint: allow[lock-discipline] reason=single-owner design: the scheduler RLock is the engine ownership token and submit() is lock-free, so no request thread ever queues behind dispatch
                        self._rng, sub = jax.random.split(self._rng)
                        # maxlint: allow[lock-discipline] reason=single-owner design: the scheduler RLock is the engine ownership token and submit() is lock-free, so no request thread ever queues behind dispatch
                        toks, emitted = self.engine.step_chunk(
                            sub, self._temps, budgets, k)
                    except InjectedFault as e:
                        # scoped fault: quarantine only the named victim;
                        # the co-batch skips this chunk (nothing was
                        # committed) and resumes next tick
                        if e.slot is not None and e.slot in self.active:
                            self._quarantine_slot(e.slot, str(e), e.site)
                        else:
                            self.quarantine_active(str(e), site=e.site)
                        toks = emitted = None
                        chunk_k = 0
                    except Exception as e:
                        # real dispatch fault: the whole co-batch's device
                        # state is suspect — quarantine everything, keep
                        # the worker alive
                        self.quarantine_active(
                            f"chunk dispatch failed: {e}", site="chunk")
                        toks = emitted = None
                        chunk_k = 0
                sp.set_metadata(k=chunk_k)
            with _span("max.sched.sync"):
                c_sync = _cpu_now()
                t_sync = _now()
                # single sync point: first tokens of fresh admissions,
                # then the chunk block (np.asarray forces both)
                self._resolve_pending_first()
                if toks is not None:
                    try:
                        # maxlint: allow[host-sync] reason=THE one sanctioned chunk-boundary sync: a single blocking transfer drains the whole chunk
                        toks = np.asarray(toks)       # the tick's host sync
                        # maxlint: allow[host-sync] reason=THE one sanctioned chunk-boundary sync: a single blocking transfer drains the whole chunk
                        emitted = np.asarray(emitted)
                    except Exception as e:
                        # the sync surfaces deferred device failures:
                        # nothing was committed, no token reached any sink
                        # — the whole batch retires ENGINE_FAULT and
                        # remains retry-safe
                        self.quarantine_active(
                            f"chunk sync failed: {e}", site="chunk")
                        toks = None
                t_deliver = _now()
                c_deliver = _cpu_now()
            with _span("max.sched.deliver"):
                steps_before = self.stats.decode_steps
                kv_before = self.stats.kv_tokens_sum
                if toks is not None:
                    counts = emitted.sum(axis=1).astype(np.int32)
                    self.engine.commit_chunk(counts)
                    per_step = emitted.sum(axis=0)
                    self.stats.chunks += 1
                    self.stats.decode_steps += int((per_step > 0).sum())
                    self.stats.occupancy_sum += int(per_step.sum())
                    self.stats.max_occupancy = max(
                        self.stats.max_occupancy,
                        int(per_step.max(initial=0)))
                    for slot, req in list(self.active.items()):
                        n = int(counts[slot])
                        if n:
                            # the slot's n steps saw L-n+1 ... L tokens
                            # in its cache (L: the length mirror after
                            # the commit)
                            length = self.engine.context_len(slot)
                            self.stats.kv_tokens_sum += \
                                n * length - n * (n - 1) // 2
                            chunk_toks = [int(t) for t in toks[slot, :n]]
                            req.output.extend(chunk_toks)
                            self.stats.emitted_tokens += n
                            self._feed_sink(req, chunk_toks)
                            if req.trace is not None:
                                req.trace.event("chunk", n=n, k=chunk_k,
                                                occupancy=len(self.active))
                        self._maybe_finish(req)
                        # physical capacity only: a pool-starved (but not
                        # max_seq-full) slot is retired by the pre-chunk
                        # ensure pass with KV_POOL_EXHAUSTED, not
                        # mislabelled here
                        if not req.done and (self.engine.context_len(slot)
                                             >= self.engine.max_seq):
                            self._overflow(req)
                    if self.stats.engine_faults == faults_before:
                        self.fault_streak = 0     # a clean committed chunk
                if self.tracer is not None:
                    # tick lane + occupancy counter tracks, host mirrors
                    # only (blocks_in_use / prefix stats never touch the
                    # device)
                    kv = self.engine.blocks_in_use() if self.engine.paged \
                        else None
                    pages = None
                    if getattr(self.engine, "prefix_cache", None) is not None:
                        pages = self.engine.prefix_stats().get("cached_pages")
                    self.tracer.tick(
                        self.stats.ticks, t0, _now(), k=chunk_k,
                        active=len(self.active),
                        emitted=self.stats.emitted_tokens - emitted_before,
                        kv_blocks_in_use=kv, prefix_cached_pages=pages,
                        parts=(t_admit, t_sync, t_deliver))
                self.stats.ticks += 1
                c1 = _cpu_now()
                t1 = _now()
                wall, sync = t1 - t0, t_deliver - t_sync
                host_cpu = (c1 - c0) - (c_deliver - c_sync)
                self.stats.wall_s += wall
                self.stats.sync_wait_s += sync
                self.stats.host_s += wall - sync
                self.stats.host_cpu_s += host_cpu
                # the tick's share of the counters, for a profiler trace
                tick_span.set_metadata(
                    host_s=wall - sync, sync_s=sync, cpu_s=host_cpu,
                    steps=self.stats.decode_steps - steps_before,
                    kv_tokens=self.stats.kv_tokens_sum - kv_before)

    def run(self, *, max_ticks: int = 10_000) -> SchedulerStats:
        """Run until queue + active drain (or tick budget). ``wall_s`` is
        accrued inside ``tick`` so ``tokens_per_s`` stays meaningful for
        external drivers (``BatchedService``) too."""
        for _ in range(max_ticks):
            if not self.has_work():
                break
            self.tick()
        return self.stats
