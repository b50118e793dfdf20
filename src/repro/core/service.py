"""Inference services — the execution strategy behind a deployment.

The v1 stack hard-wired ``Deployment.predict -> wrapper.predict()``: one
HTTP thread, one model call, no batching. This module makes the execution
strategy pluggable:

- :class:`SyncService`     current semantics — the request thread runs the
                           wrapper directly (right for classifiers and
                           cheap per-call models).
- :class:`BatchedService`  owns a :class:`ContinuousBatchingScheduler` on a
                           background worker thread; concurrent HTTP
                           requests land in a QoS admission queue, a short
                           *batching window* lets simultaneous arrivals
                           coalesce, and the engine decodes them as ONE
                           batch. Throughput scales with batch size instead
                           of thread count.

Admission is governed by a :class:`~repro.serving.qos.AdmissionController`
(priority classes, per-client deficit-weighted fairness, token-bucket rate
limits, deadline shedding) — both services consume one, record every
outcome in a shared :class:`~repro.serving.metrics.MetricsRegistry`, and
expose per-class/per-client queue depth in ``stats()``.

Both speak the same envelope contract as ``wrapper.predict_envelope`` so
the API layer (v1 or v2) cannot tell them apart, and both support async
*jobs* (submit -> poll) for long generations. Finished job records expire
after ``job_ttl_s`` (plus a bounded-count fallback) and can be deleted
explicitly, so long-running servers don't accrete job state.

Streaming: both services implement ``predict_stream`` — an iterator of
:class:`~repro.core.router.StreamEvent` the API layer renders as
``text/event-stream``. ``SyncService`` falls back to the whole result as
one ``token`` event; ``BatchedService`` bridges the scheduler worker to
the HTTP thread through a *bounded* per-request queue fed at chunk
boundaries (backpressure: a consumer that stops draining is treated as
abandoned and its request is cancelled, so a dead stream never pins a
decode slot — closing the iterator mid-stream cancels the same way).
Every job additionally owns a :class:`JobStream`, a bounded replay buffer
of its events that late subscribers can attach to (and resume via a
sequence cursor); cancellation is a first-class outcome: ``cancel_job``
works on queued AND running jobs and the envelope/job state becomes
``cancelled``.
"""

from __future__ import annotations

import abc
import queue as _queue
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional

from repro.core.router import StreamEvent
from repro.core.wrapper import MAXError, MAXModelWrapper, PromptTooLong
from repro.serving.faults import (
    BROWNOUT_STATES, BrownoutController, FaultPlane, FaultSpec, WorkerKill,
)
from repro.serving.metrics import TOKEN_LATENCY_BUCKETS, MetricsRegistry
from repro.serving.qos import (
    AdmissionController, AdmissionError, QoSConfig, QueueFull,
)
from repro.serving.tracing import Tracer, now as _mono, phases_ms, span
from repro.serving.tracing import unwatch_gc, watch_gc


class ServiceOverloaded(MAXError):
    """Bounded request queue is full — client should back off (HTTP 429)."""


#: request-scoped QoS fields accepted by predict/predict_batch/submit_job
QOS_KEYS = ("priority", "client", "deadline_s")


def _qos_field(qos: Optional[Dict[str, Any]], key: str):
    return qos.get(key) if qos else None


# ---------------------------------------------------------------------------
# Async jobs (submit -> poll -> attach), shared by both service kinds.
# ---------------------------------------------------------------------------

class JobStream:
    """Bounded per-job event log with live fan-out.

    The producing side (scheduler token sink / job worker) ``push``es
    events; any number of subscribers replay the buffered events from a
    sequence cursor and then follow live pushes — the mechanism behind
    ``GET /v2/jobs/{id}/events`` and its ``Last-Event-ID``/``?from_seq=``
    resume. The buffer keeps the most recent ``maxlen`` events (a resume
    pointing before the retained window just gets what is still held); a
    terminal ``done``/``error`` event closes the stream and releases every
    subscriber.
    """

    def __init__(self, maxlen: int = 1024):
        self._buf: deque = deque(maxlen=maxlen)
        self._cv = threading.Condition()
        self._next_seq = 0
        self._closed = False

    def push(self, event: str, data: Dict[str, Any]) -> Optional[StreamEvent]:
        with self._cv:
            if self._closed:          # late results after a cancel race
                return None
            ev = StreamEvent(event, data, self._next_seq)
            self._next_seq += 1
            self._buf.append(ev)
            if event in ("done", "error"):
                self._closed = True
            self._cv.notify_all()
            return ev

    @property
    def closed(self) -> bool:
        with self._cv:
            return self._closed

    def subscribe(self, from_seq: int = 0, *,
                  timeout_s: float = 300.0) -> Iterator[StreamEvent]:
        """Yield events with ``seq >= from_seq``: buffered ones first, then
        live until the terminal event (or ``timeout_s`` of silence, which
        yields a structured ``error`` event and stops)."""
        next_seq = from_seq
        while True:
            with self._cv:
                batch = [e for e in self._buf if e.seq >= next_seq]
                while not batch and not self._closed:
                    if not self._cv.wait(timeout_s):
                        break                     # silence: stop below
                    batch = [e for e in self._buf if e.seq >= next_seq]
                closed = self._closed
            if not batch:
                if not closed:
                    # synthetic frame: seq next_seq-1, NOT next_seq — a
                    # client resuming with this id as Last-Event-ID must
                    # land back on the real event that will get next_seq
                    yield StreamEvent("error", {
                        "code": "TIMEOUT",
                        "message": f"no job events for {timeout_s}s"},
                        next_seq - 1)
                return
            for ev in batch:
                yield ev
                next_seq = ev.seq + 1
            if closed:                # the batch ended in the terminal event
                return


@dataclass
class Job:
    id: str
    model_id: str
    state: str = "queued"     # queued | running | done | error | cancelled
    # reported wall-clock stamps (API surface); never used for arithmetic
    # maxlint: allow[clock-discipline] reason=submitted_at is a reported wall-clock timestamp, not a duration source
    submitted_at: float = field(default_factory=time.time)
    finished_at: Optional[float] = None
    finished_mono: Optional[float] = None   # tracing.now stamp; drives TTL GC
    result: Optional[Any] = None      # envelope when done
    error: Optional[str] = None
    stream: JobStream = field(default_factory=JobStream, repr=False)
    cancel_requested: bool = False    # sync running jobs honor it post-hoc
    trace_id: Optional[int] = None    # RequestTrace id when tracing is on

    def to_json(self) -> Dict[str, Any]:
        out = {"id": self.id, "model_id": self.model_id, "state": self.state,
               "submitted_at": self.submitted_at}
        if self.finished_at is not None:
            out["finished_at"] = self.finished_at
        if self.result is not None:
            out["result"] = self.result
        if self.error is not None:
            out["error"] = self.error
        return out


class InferenceService(abc.ABC):
    """Uniform predict/predict_batch/jobs surface over one wrapped model."""

    kind: str = "abstract"
    retain_jobs: int = 512            # finished jobs kept for polling

    def __init__(self, wrapper: MAXModelWrapper, *,
                 qos: Optional[Any] = None,
                 metrics: Optional[MetricsRegistry] = None,
                 job_ttl_s: Optional[float] = None,
                 trace: bool = True, trace_buffer: int = 256,
                 slow_trace_ms: Optional[float] = None):
        self.wrapper = wrapper
        self.qos_cfg = qos if isinstance(qos, QoSConfig) \
            else QoSConfig.from_json(qos)
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self.job_ttl_s = job_ttl_s
        # request-lifecycle tracing: bounded ring of finished traces;
        # slow_trace_ms turns on slow-request capture (fast traces compact
        # under ring pressure, slow ones keep full span detail)
        self.tracer: Optional[Tracer] = Tracer(
            capacity=trace_buffer, slow_trace_ms=slow_trace_ms,
            model=wrapper.metadata.id) if trace else None
        self.admission = AdmissionController(
            self.qos_cfg, metrics=self.metrics,
            model_id=wrapper.metadata.id)
        for name, help_text in (
            ("max_ttft_seconds",
             "Time to first token from submit, per model"),
            ("max_inter_token_seconds",
             "Mean per-token interval of each decode chunk"),
            ("max_active_streams",
             "Currently open SSE token streams"),
            ("max_phase_queue_seconds",
             "Per-request queue/admission wait, by priority class"),
            ("max_phase_prefill_seconds",
             "Per-request prefill span (admission to first token), by "
             "priority class"),
            ("max_decode_per_token_seconds",
             "Per-request decode span divided by tokens generated, by "
             "priority class"),
            ("max_e2e_latency_seconds",
             "Per-request end-to-end latency (submit to retire), by "
             "priority class"),
        ):
            self.metrics.describe(name, help_text)
        self._jobs: Dict[str, Job] = {}
        self._jobs_lock = threading.Lock()
        # streaming accounting (both kinds): instantaneous gauge + totals
        self._streams_lock = threading.Lock()
        self._active_streams = 0
        self.streams_started = 0
        self.streams_cancelled = 0
        self.jobs_cancelled = 0
        self.metrics.register_gauge(
            "max_active_streams", lambda: self._active_streams,
            model=wrapper.metadata.id)
        # garbage collections show as max.gc spans while a service is live
        watch_gc()
        self._gc_watched = True

    @property
    def model_id(self) -> str:
        return self.wrapper.metadata.id

    def _request_cost(self, inp: Any) -> float:
        """Admission cost of one input — parses the generation-style dict
        field and delegates the pricing rule to
        :meth:`QoSConfig.request_cost` (shared with the scheduler, so both
        service kinds price identical traffic identically)."""
        if not self.wrapper.supports_generation():
            return self.qos_cfg.request_cost(1)   # classifiers: one unit
        budget = None
        if isinstance(inp, dict):
            try:
                budget = int(inp["max_new_tokens"])
            except (KeyError, TypeError, ValueError):
                budget = None
        return self.qos_cfg.request_cost(budget)

    def _count_request(self, priority: Optional[str],
                       env: Dict[str, Any]):
        """One requests_total increment per finished request; rejections
        are counted by the admission controller at submit time, so the sum
        over outcomes equals total submit attempts."""
        outcome = "ok" if env.get("status") == "ok" \
            else str(env.get("code") or "error").lower()
        self.metrics.inc(
            "max_requests_total", 1,
            **{"model": self.model_id, "outcome": outcome,
               "class": priority or self.qos_cfg.default_priority})

    def _observe_phases(self, priority: Optional[str],
                        usage: Optional[Dict[str, Any]]):
        """Phase histograms (queue wait / prefill / per-token decode /
        e2e) labelled by priority class, fed from the usage record both
        service kinds already compute — no extra stamps."""
        if not usage:
            return
        labels = {"model": self.model_id,
                  "class": priority or self.qos_cfg.default_priority}
        if usage.get("queue_ms") is not None:
            self.metrics.observe("max_phase_queue_seconds",
                                 usage["queue_ms"] / 1e3, **labels)
        if usage.get("prefill_ms"):
            self.metrics.observe("max_phase_prefill_seconds",
                                 usage["prefill_ms"] / 1e3, **labels)
        toks = usage.get("completion_tokens")
        if usage.get("decode_ms") and toks:
            self.metrics.histogram(
                "max_decode_per_token_seconds",
                buckets=TOKEN_LATENCY_BUCKETS, **labels,
            ).observe(usage["decode_ms"] / 1e3 / toks)
        if usage.get("latency_ms") is not None:
            self.metrics.observe("max_e2e_latency_seconds",
                                 usage["latency_ms"] / 1e3, **labels)

    # -- predictions -------------------------------------------------------

    @abc.abstractmethod
    def predict(self, inp: Any,
                qos: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        """Return the standardized envelope for one input. ``qos`` carries
        request-scoped fields (:data:`QOS_KEYS`)."""

    def predict_batch(self, inputs: List[Any],
                      qos: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
        """Per-input envelopes for an explicit multi-input request."""
        return [self.predict(i, qos) for i in inputs]

    # -- streaming ---------------------------------------------------------

    @abc.abstractmethod
    def predict_stream(self, inp: Any,
                       qos: Optional[Dict[str, Any]] = None
                       ) -> Iterator[StreamEvent]:
        """Iterator of :class:`StreamEvent` for one input: ``token`` deltas
        (monotone per-stream ``seq``), then a terminal ``done`` carrying
        the same envelope ``predict`` would return plus usage — or an
        ``error`` event with a structured code. Closing the iterator
        mid-stream cancels the underlying work."""

    def _stream_opened(self):
        with self._streams_lock:
            self._active_streams += 1
            self.streams_started += 1

    def _stream_closed(self, cancelled: bool = False):
        with self._streams_lock:
            self._active_streams -= 1
            if cancelled:
                self.streams_cancelled += 1

    @staticmethod
    def _terminal_event_data(envelope: Dict[str, Any],
                             usage: Optional[Dict[str, Any]] = None
                             ) -> tuple:
        """(event_name, data) for a finished request's terminal event."""
        status = envelope.get("status")
        if status == "ok":
            return "done", {"envelope": envelope, "usage": usage}
        code = envelope.get("code") or (
            "CANCELLED" if status == "cancelled" else "INTERNAL")
        err = envelope.get("error")
        if isinstance(err, dict):
            err = err.get("message", str(err))
        return "error", {"code": code, "message": str(err or "failed"),
                         "envelope": envelope, "usage": usage}

    # -- jobs --------------------------------------------------------------

    def _new_job(self) -> Job:
        job = Job(id=uuid.uuid4().hex[:12], model_id=self.model_id)
        with self._jobs_lock:
            self._jobs[job.id] = job
        return job

    def _gc_jobs_locked(self):
        """Expire finished jobs past the TTL and enforce the count bound
        (``_jobs_lock`` held)."""
        finished = [jid for jid, j in self._jobs.items()
                    if j.state in ("done", "error")]
        if self.job_ttl_s is not None:
            # monotonic clock: a host wall-clock step must not mass-expire
            # (step forward) or immortalize (step back) finished jobs
            cutoff = _mono() - self.job_ttl_s
            for jid in finished:
                if (self._jobs[jid].finished_mono or 0) < cutoff:
                    del self._jobs[jid]
            finished = [jid for jid in finished if jid in self._jobs]
        # bounded retention, like the scheduler's completed map: evict
        # the oldest finished jobs so records don't grow with uptime
        for jid in finished[:max(0, len(finished) - self.retain_jobs)]:
            del self._jobs[jid]

    def _finish_job(self, job: Job, envelope: Dict[str, Any],
                    usage: Optional[Dict[str, Any]] = None,
                    token_event: Optional[Dict[str, Any]] = None):
        """``token_event`` (the sync whole-result fallback) is pushed only
        after the locked cancel resolution decides the result stands — a
        cancelled job must not leak its discarded output to subscribers."""
        with self._jobs_lock:
            if job.cancel_requested and envelope.get("status") != "cancelled":
                # cancel raced completion: cancel_job set the flag under
                # this lock while the job was still live and already
                # answered 200 "cancelled" — that answer must win over
                # the late result (checked here, under the same lock, so
                # there is no window for a 'done' record to slip through)
                envelope = {"status": "cancelled", "code": "CANCELLED",
                            "error": "cancelled while running",
                            "model_id": self.model_id}
                usage = None
            status = envelope.get("status")
            # state flips LAST: pollers read without the lock, and a job
            # observed as done/error must already carry result+finished_at
            job.result = envelope
            job.error = envelope.get("error") if status != "ok" else None
            if isinstance(job.error, dict):     # structured error message
                job.error = job.error.get("message", str(job.error))
            # maxlint: allow[clock-discipline] reason=finished_at is the reported wall-clock timestamp; TTL GC uses finished_mono
            job.finished_at = time.time()
            job.finished_mono = _mono()
            job.state = "done" if status == "ok" \
                else "cancelled" if status == "cancelled" else "error"
            self._gc_jobs_locked()
        if job.state == "cancelled":
            with self._streams_lock:    # += races worker/request threads
                self.jobs_cancelled += 1
        # stream events outside the lock (JobStream has its own cv); the
        # state flip above makes any later cancel_job return False, so
        # this ordering cannot race a cancel
        if token_event is not None and job.state == "done":
            job.stream.push("token", token_event)
        event, data = self._terminal_event_data(envelope, usage)
        job.stream.push(event, data)

    @abc.abstractmethod
    def submit_job(self, inp: Any,
                   qos: Optional[Dict[str, Any]] = None) -> Job:
        """Enqueue ``inp`` for asynchronous prediction; returns immediately."""

    def get_job(self, job_id: str) -> Job:
        with self._jobs_lock:
            self._gc_jobs_locked()
            try:
                return self._jobs[job_id]
            except KeyError:
                raise KeyError(f"unknown job {job_id!r}") from None

    def delete_job(self, job_id: str) -> bool:
        """Drop a *finished* job's record (``DELETE /v2/jobs/{id}`` falls
        through to this after :meth:`cancel_job` declines — queued/running
        jobs are cancelled, not silently unrecorded)."""
        with self._jobs_lock:
            return self._jobs.pop(job_id, None) is not None

    @abc.abstractmethod
    def cancel_job(self, job_id: str) -> bool:
        """Cancel a queued or running job: the job finishes with state
        ``cancelled`` and envelope ``{"status": "cancelled", ...}``, and
        any decode slot it held is freed at the next chunk boundary.
        Returns False when the job is unknown or already finished."""

    def job_events(self, job_id: str, from_seq: int = 0,
                   *, timeout_s: float = 300.0) -> Iterator[StreamEvent]:
        """Attach to a job's event stream (replay + live); raises KeyError
        for unknown jobs like :meth:`get_job`."""
        return self.get_job(job_id).stream.subscribe(
            from_seq, timeout_s=timeout_s)

    def get_trace(self, job_id: str) -> Dict[str, Any]:
        """Span timeline JSON for a job's request. Raises KeyError for
        unknown jobs (like :meth:`get_job`), for jobs submitted before
        tracing was enabled, and for traces the bounded ring evicted."""
        job = self.get_job(job_id)
        if self.tracer is None:
            raise KeyError(
                f"tracing is disabled for {self.model_id!r} "
                "(redeploy with {\"trace\": true})")
        if job.trace_id is None:
            raise KeyError(f"job {job_id!r} has no trace record")
        trace = self.tracer.get(job.trace_id)
        if trace is None:
            raise KeyError(
                f"trace for job {job_id!r} was evicted from the "
                f"{self.tracer.capacity}-entry ring")
        return trace

    # -- lifecycle / introspection ----------------------------------------

    def health(self) -> Dict[str, Any]:
        """Liveness/readiness/degradation summary for ``GET /v2/health``.
        The sync service is live and ready as long as it is open (the
        request thread does the work — there is no worker to die); the
        batched service overrides this with worker/brownout state."""
        open_ = not getattr(self, "_closed", False)
        return {"live": open_, "ready": open_, "degradation": "normal"}

    def stats(self) -> Dict[str, Any]:
        with self._jobs_lock:
            self._gc_jobs_locked()
            jobs = len(self._jobs)
        with self._streams_lock:
            streams = {"active": self._active_streams,
                       "started": self.streams_started,
                       "cancelled": self.streams_cancelled}
        return {"kind": self.kind, "jobs": jobs,
                "job_ttl_s": self.job_ttl_s,
                "cancelled": self.jobs_cancelled,
                "streams": streams,
                "ttft": self.metrics.histogram(
                    "max_ttft_seconds", model=self.model_id).snapshot(),
                "inter_token": self.metrics.histogram(
                    "max_inter_token_seconds",
                    buckets=TOKEN_LATENCY_BUCKETS,
                    model=self.model_id).snapshot(),
                "tracing": (self.tracer.snapshot_stats()
                            if self.tracer is not None
                            else {"enabled": False}),
                "qos": self.admission.stats()}

    def close(self):
        self.metrics.unregister_gauges(model=self.model_id)
        if self._gc_watched:
            self._gc_watched = False
            unwatch_gc()


# ---------------------------------------------------------------------------
# SyncService — v1 semantics behind the uniform interface.
# ---------------------------------------------------------------------------

class SyncService(InferenceService):
    kind = "sync"

    def __init__(self, wrapper: MAXModelWrapper, **kw):
        super().__init__(wrapper, **kw)
        # generation wrappers keep decode-slot state on their engine; two
        # HTTP threads calling predict concurrently would race on it (the
        # pre-service server had exactly this bug), so those run one call
        # at a time. Stateless wrappers (classifiers) stay concurrent.
        self._serialize = wrapper.supports_generation()
        self._predict_lock = threading.Lock()
        self._job_queue: deque = deque()
        self._job_cv = threading.Condition()
        self._job_thread: Optional[threading.Thread] = None
        self._closed = False

    def _admit_or_envelope(self, qos: Optional[Dict[str, Any]],
                           cost: float = 1.0) -> Optional[Dict[str, Any]]:
        """Sync admission = token-bucket + class validation only (there is
        no queue to prioritise — the request thread runs the call now)."""
        try:
            self.admission.try_acquire(
                _qos_field(qos, "client") or "anon", cost,
                _qos_field(qos, "priority"))
            return None
        except AdmissionError as e:
            # no _count_request here: rate-limits are already counted by
            # the controller (counting again would double the series), and
            # an invalid priority must not mint a metrics label from a
            # client-controlled string
            env = {"status": "error", "error": str(e), "code": e.code,
                   "model_id": self.model_id}
            if getattr(e, "retry_after_s", None) is not None:
                env["retry_after_s"] = e.retry_after_s
            return env

    @staticmethod
    def _first_prediction(env: Dict[str, Any]) -> Dict[str, Any]:
        preds = env.get("predictions")
        return preds[0] if isinstance(preds, list) and preds \
            and isinstance(preds[0], dict) else {}

    def _sync_usage(self, env: Dict[str, Any], latency_ms: float,
                    queue_ms: float = 0.0) -> Dict[str, Any]:
        """Usage for the whole-result fallback: token counts when the
        wrapper reports them, TTFT = engine-measured first token (sync
        generation) or the whole-call latency (classifiers). Phase fields
        mirror the batched service: sync has no scheduler queue (only job
        submissions wait, measured by ``queue_ms``), prefill is the
        engine-measured TTFT, decode the remainder."""
        first = self._first_prediction(env)
        ttft = first.get("ttft_ms", latency_ms)
        prefill = float(ttft) if ttft is not None else 0.0
        return {"prompt_tokens": first.get("prompt_tokens"),
                "completion_tokens": first.get("generated_tokens"),
                "ttft_ms": ttft,
                "latency_ms": latency_ms,
                "queue_ms": round(queue_ms, 3),
                "prefill_ms": round(min(prefill, latency_ms), 3),
                "decode_ms": round(max(0.0, latency_ms - prefill), 3),
                "sched_ticks": 0}

    def _sync_token_event(self, env: Dict[str, Any]) -> Dict[str, Any]:
        """The whole-result-as-one-event token payload (one grammar for
        /stream and /jobs/{id}/events alike)."""
        return {"text": self._first_prediction(env).get("generated_text"),
                "predictions": env.get("predictions"),
                "model_id": self.model_id}

    def _observe_ttft(self, env: Dict[str, Any]):
        """Sync TTFT: the engine's measured first-token time when the
        wrapper reports one (generation assets), else the whole-call
        latency (classifiers emit their one result all at once)."""
        if env.get("status") != "ok":
            return
        ttft_ms = self._first_prediction(env).get("ttft_ms",
                                                  env.get("latency_ms"))
        if ttft_ms is not None:
            self.metrics.observe("max_ttft_seconds", float(ttft_ms) / 1e3,
                                 model=self.model_id)

    def _start_sync_trace(self, qos: Optional[Dict[str, Any]],
                          ts: Optional[float] = None):
        if self.tracer is None:
            return None
        return self.tracer.start(
            self.tracer.next_id(),
            priority=(_qos_field(qos, "priority")
                      or self.qos_cfg.default_priority),
            client=_qos_field(qos, "client") or "anon",
            submitted_at=ts)

    def _finish_sync_trace(self, tr, env: Dict[str, Any], t_exec: float,
                           *, outcome: Optional[str] = None):
        """Close a sync trace from its envelope: first-token derived from
        the engine-measured TTFT (sync execution has no chunk boundary to
        stamp at), outcome from the envelope unless overridden (a cancel
        race resolved by ``_finish_job`` wins over the late result)."""
        if tr is None:
            return
        t_end = _mono()
        ttft_ms = self._first_prediction(env).get("ttft_ms")
        if env.get("status") == "ok" and ttft_ms is not None:
            tr.first_token(min(t_end, t_exec + float(ttft_ms) / 1e3))
        if outcome is None:
            outcome = "ok" if env.get("status") == "ok" \
                else str(env.get("code") or "INTERNAL")
        toks = self._first_prediction(env).get("generated_tokens") or 0
        self.tracer.finish(tr, outcome=outcome,
                           error_code=None if outcome == "ok" else outcome,
                           completion_tokens=int(toks), ts=t_end)

    def predict(self, inp: Any,
                qos: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        t0 = _mono()
        tr = self._start_sync_trace(qos, ts=t0)
        rejected = self._admit_or_envelope(qos, cost=self._request_cost(inp))
        if rejected is not None:
            if tr is not None:
                code = rejected.get("code") or "REJECTED"
                self.tracer.finish(tr, outcome=code, error_code=code)
            return rejected
        t_exec = _mono()
        if tr is not None:
            tr.admitted(t_exec, slot=-1, tick=-1)
        if self._serialize:
            with self._predict_lock:
                env = self.wrapper.predict_envelope(inp)
        else:
            env = self.wrapper.predict_envelope(inp)
        self._observe_ttft(env)
        self._count_request(_qos_field(qos, "priority"), env)
        if env.get("status") == "ok":
            self._observe_phases(
                _qos_field(qos, "priority"),
                self._sync_usage(env, round((_mono() - t0) * 1e3, 3)))
        self._finish_sync_trace(tr, env, t_exec)
        return env

    def predict_stream(self, inp: Any,
                       qos: Optional[Dict[str, Any]] = None
                       ) -> Iterator[StreamEvent]:
        """Whole-result-as-one-event fallback: sync execution has no chunk
        boundaries to stream from, so the stream is ``token`` (full
        payload) then ``done`` — the same event grammar as the batched
        service, so clients need not care which service kind answered."""
        def gen():
            self._stream_opened()
            try:
                t0 = _mono()
                env = self.predict(inp, qos)
                latency_ms = round((_mono() - t0) * 1e3, 3)
                if env.get("status") != "ok":
                    code = env.get("code") or "INVALID_INPUT"
                    yield StreamEvent("error", {
                        "code": code, "message": str(env.get("error")),
                        "model_id": self.model_id}, 0)
                    return
                yield StreamEvent("token", self._sync_token_event(env), 0)
                yield StreamEvent("done", {
                    "envelope": env,
                    "usage": self._sync_usage(env, latency_ms)}, 1)
            finally:
                self._stream_closed()
        return gen()

    def predict_batch(self, inputs: List[Any],
                      qos: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
        rejected = self._admit_or_envelope(
            qos, cost=sum(self._request_cost(i) for i in inputs))
        if rejected is not None:
            return [dict(rejected) for _ in inputs]
        if self._serialize:
            with self._predict_lock:
                envs = self.wrapper.predict_batch_envelope(inputs)
        else:
            envs = self.wrapper.predict_batch_envelope(inputs)
        for env in envs:
            self._count_request(_qos_field(qos, "priority"), env)
        return envs

    def submit_job(self, inp: Any,
                   qos: Optional[Dict[str, Any]] = None) -> Job:
        # admission failures surface at submit (429), not as dead jobs
        self.admission.try_acquire(_qos_field(qos, "client") or "anon",
                                   self._request_cost(inp),
                                   _qos_field(qos, "priority"))
        job = self._new_job()
        tr = self._start_sync_trace(qos)    # queue span = submit -> pickup
        if tr is not None:
            job.trace_id = tr.trace_id
        with self._job_cv:
            if self._closed:
                with self._jobs_lock:
                    self._jobs.pop(job.id, None)
                if tr is not None:
                    self.tracer.finish(tr, outcome="INTERNAL",
                                       error_code="INTERNAL")
                raise MAXError(f"service for {self.model_id!r} is closed")
            if self._job_thread is None:        # lazy single worker
                self._job_thread = threading.Thread(
                    target=self._job_worker, daemon=True,
                    name=f"sync-jobs-{self.model_id}")
                self._job_thread.start()
            self._job_queue.append((job, inp, qos, tr))
            self._job_cv.notify()
        return job

    def _cancelled_envelope(self, detail: str) -> Dict[str, Any]:
        return {"status": "cancelled", "code": "CANCELLED",
                "error": f"cancelled {detail}", "model_id": self.model_id}

    def cancel_job(self, job_id: str) -> bool:
        """Queued jobs cancel immediately (dropped from the worker queue);
        a *running* sync job cannot be preempted mid-wrapper-call — the
        mark makes it finish as ``cancelled`` with its result discarded
        (there is no decode slot to reclaim in the sync service)."""
        with self._job_cv:
            for i, (job, _inp, _qos, tr) in enumerate(self._job_queue):
                if job.id == job_id:
                    del self._job_queue[i]
                    if tr is not None:
                        self.tracer.finish(tr, outcome="CANCELLED",
                                           error_code="CANCELLED")
                    self._finish_job(job,
                                     self._cancelled_envelope("while queued"))
                    return True
        with self._jobs_lock:
            job = self._jobs.get(job_id)
            if job is None or job.state not in ("queued", "running"):
                return False
            job.cancel_requested = True
        return True

    def _job_worker(self):
        while True:
            with self._job_cv:
                while not self._job_queue and not self._closed:
                    self._job_cv.wait()
                if self._closed:
                    return
                job, inp, qos, tr = self._job_queue.popleft()
            if job.cancel_requested:             # cancelled between queue
                if tr is not None:               # scan and pickup
                    self.tracer.finish(tr, outcome="CANCELLED",
                                       error_code="CANCELLED")
                self._finish_job(job,
                                 self._cancelled_envelope("while queued"))
                continue
            job.state = "running"
            try:
                # rate limit was paid at submit; run the wrapper directly
                t0 = _mono()
                if tr is not None:               # queue wait ends here
                    tr.admitted(t0, slot=-1, tick=-1)
                if self._serialize:
                    with self._predict_lock:
                        env = self.wrapper.predict_envelope(inp)
                else:
                    env = self.wrapper.predict_envelope(inp)
                self._observe_ttft(env)
                self._count_request(_qos_field(qos, "priority"), env)
            except Exception as e:              # fault isolation per job
                env = {"status": "error", "error": str(e),
                       "model_id": self.model_id}
            usage = token_event = None
            if env.get("status") == "ok":
                latency_ms = round((_mono() - t0) * 1e3, 3)
                usage = self._sync_usage(
                    env, latency_ms,
                    queue_ms=(t0 - tr.submitted_at) * 1e3
                    if tr is not None else 0.0)
                self._observe_phases(_qos_field(qos, "priority"), usage)
                token_event = self._sync_token_event(env)
            # a cancel that races this completion is resolved inside
            # _finish_job under the jobs lock: the record can never flip
            # to 'done' after cancel_job answered "cancelled", and the
            # whole-result token event is only pushed if the result stands
            self._finish_job(job, env, usage=usage, token_event=token_event)
            # trace outcome follows the resolved job state (a cancel race
            # answered "cancelled" — the trace must agree)
            self._finish_sync_trace(
                tr, env, t0,
                outcome="CANCELLED" if job.state == "cancelled" else None)

    def close(self):
        with self._job_cv:
            self._closed = True
            queued = list(self._job_queue)
            self._job_queue.clear()
            self._job_cv.notify_all()
        # fail undrained jobs now — pollers must not spin on 'queued' forever
        for job, _inp, _qos, tr in queued:
            if tr is not None:
                self.tracer.finish(tr, outcome="INTERNAL",
                                   error_code="INTERNAL")
            self._finish_job(job, {
                "status": "error",
                "error": f"service for {self.model_id!r} is closed",
                "model_id": self.model_id})
        super().close()


# ---------------------------------------------------------------------------
# BatchedService — the continuous-batching bridge.
# ---------------------------------------------------------------------------

@dataclass
class _Work:
    """One logical generation riding the scheduler."""
    inp: Any
    prompt: List[int]
    gen_kw: Dict[str, Any]
    extra: Optional[Dict[str, Any]]
    t0: float
    event: threading.Event = field(default_factory=threading.Event)
    job: Optional[Job] = None
    request: Optional[Any] = None     # scheduler Request once admitted
    envelope: Optional[Dict[str, Any]] = None
    # streaming plumbing: ``push(token_ids, text)`` forwards a chunk's
    # tokens, ``notify(envelope, usage)`` delivers the terminal result —
    # both run on the scheduler worker thread and must not block it
    push: Optional[Callable] = None
    notify: Optional[Callable] = None
    last_tok_t: Optional[float] = None   # previous sync-point timestamp
    # retry bookkeeping: a faulted request is retry-safe only while ZERO
    # tokens were DELIVERED outside the service (streamed to a bridge or a
    # job replay buffer) — internal scheduler output is discarded freely,
    # but a token a client may have seen must never be re-emitted
    sink: Optional[Callable] = None      # token_sink, reused on resubmit
    qos: Optional[Dict[str, Any]] = None # original QoS fields, for resubmit
    deadline_at: Optional[float] = None  # absolute: retries never extend it
    attempts: int = 0                    # completed (faulted) attempts
    delivered: int = 0                   # tokens pushed to an external sink


@dataclass
class BatchStats:
    """Service-level counters; batch-size/occupancy numbers live on the
    scheduler's own stats (the single source of truth for decode batches)."""
    submitted: int = 0
    completed: int = 0
    rejected: int = 0                 # queue-full + rate-limited at submit
    cancelled: int = 0                # user cancel / disconnect / abandon


class BatchedService(InferenceService):
    """Aggregates concurrent requests into engine decode batches.

    A single worker thread owns the :class:`ContinuousBatchingScheduler`
    (and therefore the engine cache) — HTTP threads submit through the
    scheduler's admission controller (which may reject with structured
    ``QUEUE_FULL`` / ``RATE_LIMITED`` on the *request* thread) and wait on
    a per-request event, so no engine state is ever touched concurrently.
    ``batch_window_s`` is the coalescing window: when the engine is idle
    and the first request arrives, the worker waits that long (or until
    the batch is full) for simultaneous arrivals before the first prefill,
    then keeps admitting newcomers every tick (continuous batching
    proper). Dequeue order is the controller's: priority classes, then
    deficit-weighted fairness across clients — not raw FIFO.

    ``decode_chunk`` is the fused-decode granularity: the scheduler syncs
    to host (and admits newcomers / retires finished work) once per chunk
    of up to that many tokens, not once per token. Larger chunks cut
    dispatch overhead; smaller chunks admit fresh arrivals sooner — the
    batching window and the chunk size together bound how long a request
    can wait before joining the batch (window + one chunk).
    """

    kind = "batched"

    def __init__(self, wrapper: MAXModelWrapper, *,
                 batch_window_s: float = 0.01, max_queue: int = 64,
                 request_timeout_s: float = 300.0,
                 decode_chunk: Optional[int] = None,
                 stream_queue_depth: int = 256,
                 faults: Optional[Any] = None,
                 brownout: Optional[Any] = None,
                 max_retries: int = 3,
                 retry_backoff_s: float = 0.05,
                 stall_budget_s: float = 5.0,
                 rebuild_after_faults: int = 3,
                 watchdog_interval_s: float = 0.1, **kw):
        if not wrapper.supports_generation():
            raise ValueError(
                f"{wrapper.metadata.id!r} does not implement the generation "
                "protocol (prepare_generation/format_generation); "
                "use SyncService")
        if kw.get("qos") is None:
            kw["qos"] = QoSConfig(max_queue=max_queue)
        super().__init__(wrapper, **kw)
        from repro.serving.scheduler import ContinuousBatchingScheduler
        self.engine = wrapper.engine
        # fault injection (chaos testing): an unarmed spec attaches no
        # plane at all, so disabled injection is byte-identical to a build
        # without it — the scheduler hook is a bare `is not None` check
        spec = faults if isinstance(faults, FaultSpec) \
            else FaultSpec.from_json(faults)
        self.fault_plane: Optional[FaultPlane] = \
            FaultPlane(spec) if spec.armed else None
        self.scheduler = ContinuousBatchingScheduler(
            self.engine, admission=self.admission,
            decode_chunk=decode_chunk, tracer=self.tracer,
            faults=self.fault_plane)
        self.batch_window_s = batch_window_s
        self.max_queue = self.qos_cfg.max_queue
        self.request_timeout_s = request_timeout_s
        # bounded bridge between the scheduler worker and a stream's HTTP
        # thread: at ~1 event per decode chunk this holds minutes of
        # backlog, so hitting the bound means the consumer is gone
        self.stream_queue_depth = stream_queue_depth
        self.batch_stats = BatchStats()
        self._inflight: Dict[int, _Work] = {}
        self._cv = threading.Condition()
        self._closed = False
        self._draining = False            # fleet drain: stop admitting
        self._worker_error: Optional[str] = None
        # -- supervision / retry / brownout --------------------------------
        self.max_retries = max(0, int(max_retries))
        self.retry_backoff_s = retry_backoff_s
        self.stall_budget_s = stall_budget_s
        self.rebuild_after_faults = max(0, int(rebuild_after_faults))
        self.watchdog_interval_s = watchdog_interval_s
        self._retry_q: List[tuple] = []   # (due_monotonic, _Work), sorted
        self.retries = 0
        self.worker_restarts = 0
        self.engine_rebuilds = 0
        self.tick_stalls = 0
        self._faults_seen = 0             # metric-delta mirror of scheduler
        self._pool_exhausted_seen = 0
        self._tick_started: Optional[float] = None
        self._stall_flagged = False
        self._brownout: Optional[BrownoutController] = None
        if brownout is not None:
            self._brownout = BrownoutController(
                brownout, metrics=self.metrics, model_id=self.model_id)
            self.metrics.register_gauge(
                "max_brownout_state",
                lambda: BROWNOUT_STATES.index(self._brownout.state),
                model=self.model_id)
        for name, help_text in (
            ("max_engine_faults_total",
             "Requests retired as ENGINE_FAULT (injected or real)"),
            ("max_retries_total",
             "Automatic requeues of zero-delivery faulted requests"),
            ("max_worker_restarts_total",
             "Dead scheduler workers respawned by the watchdog"),
            ("max_engine_rebuilds_total",
             "Engine state rebuilds after repeated faults"),
            ("max_tick_stalls_total",
             "Scheduler ticks that exceeded the stall budget"),
            ("max_brownout_transitions_total",
             "Brownout state-machine transitions, by target state"),
            ("max_brownout_shed_total",
             "Requests shed at admission by brownout degradation"),
            ("max_brownout_state",
             "Current degradation state (0=normal, 1=soft, 2=hard)"),
        ):
            self.metrics.describe(name, help_text)
        self.metrics.register_gauge(
            "max_queue_depth", self.admission.depth, model=self.model_id)
        if getattr(self.engine, "paged", False):
            # pool occupancy: the number every capacity dashboard needs —
            # a paged deployment's device memory scales with pages in use,
            # not with max_batch * max_seq
            self.metrics.register_gauge(
                "max_kv_pool_blocks_in_use", self.engine.blocks_in_use,
                model=self.model_id)
            self.metrics.register_gauge(
                "max_kv_pool_blocks_total",
                lambda: self.engine.kv_pool_blocks, model=self.model_id)
        if getattr(self.engine, "prefix_cache", None) is not None:
            # prefix-cache effectiveness: hit/miss/eviction rates (counters
            # rendered as gauges — monotonic reads off engine state, no
            # write per event on the hot path) plus instantaneous sharing
            def _pstat(key):
                return lambda: self.engine.prefix_stats()[key]
            for key in ("hits", "misses", "hit_tokens", "evictions",
                        "cow_copies"):
                self.metrics.register_gauge(
                    f"max_prefix_cache_{key}_total", _pstat(key),
                    model=self.model_id)
            for key in ("shared_pages", "cached_pages",
                        "unreferenced_pages"):
                self.metrics.register_gauge(
                    f"max_prefix_cache_{key}", _pstat(key),
                    model=self.model_id)
        self._thread = threading.Thread(
            target=self._worker, daemon=True,
            name=f"batched-{self.model_id}")
        self._thread.start()
        # the watchdog outlives any one worker incarnation: it respawns
        # dead workers (quarantining whatever they held) and flags ticks
        # that blow the stall budget
        self._watchdog_thread = threading.Thread(
            target=self._watchdog, daemon=True,
            name=f"watchdog-{self.model_id}")
        self._watchdog_thread.start()

    # -- request path ------------------------------------------------------

    def _enqueue(self, inp: Any, job: Optional[Job] = None,
                 qos: Optional[Dict[str, Any]] = None,
                 push: Optional[Callable] = None,
                 notify: Optional[Callable] = None) -> _Work:
        prompt, gen_kw, extra = self.wrapper.prepare_generation(inp)
        # reject here, on the request thread, BEFORE admission: a raise
        # inside the worker's tick would fail every request sharing the
        # decode batch, and a zero-headroom prompt would burn a prefill +
        # slot only to retire with nothing generated
        if not self.engine.fits_prompt(len(prompt)):
            raise PromptTooLong(
                f"prompt of {len(prompt)} tokens does not fit max_seq "
                f"{self.engine.max_seq} with generation headroom (longest "
                f"admissible prompt: {self.engine.max_prompt_len()} tokens)")
        if self._brownout is not None:
            # re-evaluate with the live queue (so an idle service cools
            # down even while the worker sleeps), then shed or clamp:
            # HARD raises CircuitOpen for everyone, SOFT raises Degraded
            # for best_effort and caps the generation budget for the rest
            self._brownout.observe(self._queue_frac())
            self._brownout.admit(_qos_field(qos, "priority")
                                 or self.qos_cfg.default_priority)
            mnt = gen_kw.get("max_new_tokens")
            clamped = self._brownout.clamp(mnt if mnt is not None else 32)
            if clamped is not None and clamped != mnt:
                gen_kw = dict(gen_kw, max_new_tokens=clamped)
        work = _Work(inp=inp, prompt=prompt, gen_kw=gen_kw, extra=extra,
                     t0=_mono(), job=job,
                     push=push, notify=notify, qos=dict(qos) if qos else None)
        dl = _qos_field(qos, "deadline_s")
        if dl is not None:
            work.deadline_at = work.t0 + float(dl)

        def sink(toks: List[int]):
            # runs at the scheduler's per-chunk sync point (worker thread,
            # scheduler lock held): record per-token pacing, then forward.
            # TTFT rides Request.first_token_s (stamped by the scheduler)
            # so queue wait is included; the gap/len(toks) sample is the
            # chunk's mean inter-token interval.
            now = _mono()
            if work.last_tok_t is None:
                self.metrics.observe("max_ttft_seconds", now - work.t0,
                                     model=self.model_id)
            else:
                self.metrics.histogram(
                    "max_inter_token_seconds",
                    buckets=TOKEN_LATENCY_BUCKETS,
                    model=self.model_id,
                ).observe((now - work.last_tok_t) / len(toks))
            work.last_tok_t = now
            if work.push is not None:
                # tokens handed to an external consumer (stream bridge /
                # job replay buffer): from here on a fault is terminal for
                # this request — retrying could duplicate what the client
                # already saw
                work.delivered += len(toks)
                work.push(list(toks),
                          self.wrapper.format_stream_delta(toks))

        work.sink = sink
        with self._cv:
            if self._closed:
                raise MAXError(f"service for {self.model_id!r} is closed")
            if self._draining:
                # a draining replica finishes what it holds but admits
                # nothing new — the fleet dispatcher fails over to a
                # surviving replica on this rejection
                self.batch_stats.rejected += 1
                raise ServiceOverloaded(
                    f"replica for {self.model_id!r} is draining")
            try:
                work.request = self.scheduler.submit(
                    prompt, extra=extra,
                    priority=_qos_field(qos, "priority"),
                    client=_qos_field(qos, "client"),
                    deadline_s=_qos_field(qos, "deadline_s"),
                    token_sink=sink,
                    **gen_kw)
            except QueueFull as e:
                self.batch_stats.rejected += 1
                raise ServiceOverloaded(str(e)) from None
            except AdmissionError:
                self.batch_stats.rejected += 1      # rate-limited etc.
                raise
            if job is not None and self.tracer is not None:
                # the scheduler request IS the trace (same id), so
                # GET /v2/jobs/{id}/trace resolves through the job record
                job.trace_id = work.request.id
            self._inflight[work.request.id] = work
            self.batch_stats.submitted += 1
            self._cv.notify_all()
        return work

    def _error_envelope(self, msg: str, code: str = "INVALID_INPUT",
                        retry_after_s: Optional[float] = None
                        ) -> Dict[str, Any]:
        # "code" is consumed (and stripped) by the API layer: v2 maps it to
        # a structured error + HTTP status, v1 drops it; retry_after_s
        # surfaces as the Retry-After header on 429/503 responses
        env = {"status": "error", "error": msg, "code": code,
               "model_id": self.model_id}
        if retry_after_s is not None:
            env["retry_after_s"] = retry_after_s
        return env

    def _enqueue_or_error(self, inp: Any, job: Optional[Job] = None,
                          qos: Optional[Dict[str, Any]] = None):
        try:
            return self._enqueue(inp, job, qos)
        except ServiceOverloaded as e:
            env = self._error_envelope(str(e), "QUEUE_FULL")
        except PromptTooLong as e:
            env = self._error_envelope(str(e), "PROMPT_TOO_LONG")
        except AdmissionError as e:
            env = self._error_envelope(
                str(e), e.code,
                retry_after_s=getattr(e, "retry_after_s", None))
        except MAXError as e:
            env = self._error_envelope(str(e))
        return env

    def _await(self, work) -> Dict[str, Any]:
        if isinstance(work, dict):              # rejected at enqueue
            return work
        if not work.event.wait(self.request_timeout_s):
            return self._error_envelope(
                f"timed out after {self.request_timeout_s}s", "TIMEOUT")
        return work.envelope

    def predict(self, inp: Any,
                qos: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        return self._await(self._enqueue_or_error(inp, qos=qos))

    def predict_batch(self, inputs: List[Any],
                      qos: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
        # enqueue all first so they share decode batches, then wait all
        return [self._await(w)
                for w in [self._enqueue_or_error(i, qos=qos)
                          for i in inputs]]

    def submit_job(self, inp: Any,
                   qos: Optional[Dict[str, Any]] = None) -> Job:
        job = self._new_job()

        def push(toks: List[int], text: Optional[str]):
            # feeds the job's replay buffer at each chunk boundary, so any
            # number of /v2/jobs/{id}/events subscribers can attach/resume
            job.stream.push("token", {"token_ids": toks, "text": text,
                                      "model_id": self.model_id})

        try:
            self._enqueue(inp, job=job, qos=qos, push=push)
        except (MAXError, AdmissionError):
            # bad input / full queue / rate limit is a submit-time failure:
            # surface it as the HTTP error (429/400), not a 202 with a
            # dead job (AdmissionError is not a MAXError — both must
            # release the record)
            with self._jobs_lock:
                self._jobs.pop(job.id, None)
            raise
        return job

    def cancel_job(self, job_id: str) -> bool:
        """Cancel via the scheduler: queued work is dropped from admission,
        a running slot is freed at the next chunk boundary (and backfilled
        from the queue in the same tick). The worker reaps the retired
        request and flips the job to ``cancelled``."""
        with self._cv:
            work = next((w for w in self._inflight.values()
                         if w.job is not None and w.job.id == job_id), None)
        if work is None or work.request is None:
            return False
        return self.scheduler.cancel(work.request.id)

    def predict_stream(self, inp: Any,
                       qos: Optional[Dict[str, Any]] = None
                       ) -> Iterator[StreamEvent]:
        """Live token stream for one input.

        The scheduler worker feeds a *bounded* queue at each chunk
        boundary; this generator (the HTTP thread) drains it. End-to-end
        cancellation:

        - closing the generator mid-stream (client disconnect) cancels the
          scheduler request — the decode slot frees at the next chunk
          boundary and backfills;
        - a consumer that stops draining (``stream_queue_depth`` events of
          backlog) is treated as abandoned and cancelled the same way;
        - admission rejection (rate limit / queue full / bad input)
          arrives as a pre-stream ``error`` event with its structured code.
        """
        def gen():
            bridge: _queue.Queue = _queue.Queue(
                maxsize=self.stream_queue_depth)
            box: Dict[str, Any] = {}

            def push(toks: List[int], text: Optional[str]):
                try:
                    bridge.put_nowait(
                        ("token", {"token_ids": toks, "text": text,
                                   "model_id": self.model_id}))
                except _queue.Full:
                    # abandoned consumer: free the slot instead of
                    # decoding into a queue nobody drains
                    req = box.get("request")
                    if req is not None:
                        self.scheduler.cancel(req.id)

            def notify(env, usage):
                event, data = self._terminal_event_data(env, usage)
                try:
                    bridge.put_nowait((event, data))
                except _queue.Full:     # guarantee the terminal lands
                    try:
                        bridge.get_nowait()
                    except _queue.Empty:
                        pass
                    bridge.put_nowait((event, data))

            self._stream_opened()
            cancelled = False
            seq = 0
            try:
                try:
                    work = self._enqueue(inp, qos=qos,
                                         push=push, notify=notify)
                except ServiceOverloaded as e:
                    yield StreamEvent("error", {
                        "code": "QUEUE_FULL", "message": str(e),
                        "model_id": self.model_id}, seq)
                    return
                except AdmissionError as e:
                    data = {"code": e.code, "message": str(e),
                            "model_id": self.model_id}
                    if getattr(e, "retry_after_s", None) is not None:
                        data["retry_after_s"] = e.retry_after_s
                    yield StreamEvent("error", data, seq)
                    return
                except PromptTooLong as e:
                    yield StreamEvent("error", {
                        "code": "PROMPT_TOO_LONG", "message": str(e),
                        "model_id": self.model_id}, seq)
                    return
                except MAXError as e:
                    yield StreamEvent("error", {
                        "code": "INVALID_INPUT", "message": str(e),
                        "model_id": self.model_id}, seq)
                    return
                box["request"] = work.request
                try:
                    while True:
                        try:
                            event, data = bridge.get(
                                timeout=self.request_timeout_s)
                        except _queue.Empty:
                            self.scheduler.cancel(work.request.id)
                            cancelled = True
                            yield StreamEvent("error", {
                                "code": "TIMEOUT",
                                "message": "no tokens for "
                                           f"{self.request_timeout_s}s",
                                "model_id": self.model_id}, seq)
                            return
                        ev = StreamEvent(event, data, seq)
                        seq += 1
                        yield ev
                        if event != "token":     # done | error: terminal
                            cancelled = data.get("code") == "CANCELLED" \
                                if event == "error" else False
                            return
                except GeneratorExit:
                    # consumer went away mid-stream: never pin the slot
                    if not work.event.is_set():
                        self.scheduler.cancel(work.request.id)
                    cancelled = True
                    raise
            finally:
                self._stream_closed(cancelled=cancelled)
        return gen()

    # -- worker ------------------------------------------------------------

    def _usage(self, work: _Work) -> Dict[str, Any]:
        req = work.request
        ttft_ms = None
        if req is not None and req.first_token_s is not None:
            ttft_ms = round((req.first_token_s - work.t0) * 1e3, 3)
        end = req.finished_at_s if req is not None \
            and req.finished_at_s is not None else _mono()
        usage = {"prompt_tokens": len(work.prompt),
                 "completion_tokens": len(req.output) if req else 0,
                 "ttft_ms": ttft_ms,
                 "latency_ms": round((end - work.t0) * 1e3, 3)}
        # phase durations from the scheduler's lifecycle stamps — all on
        # the one serving clock, each boundary shared by two phases, so
        # queue_ms + prefill_ms + decode_ms == retire - submit exactly
        phases = phases_ms(req.submitted_at_s or work.t0, req.admitted_at_s,
                           req.first_token_s, end)
        for key in ("queue_ms", "prefill_ms", "decode_ms"):
            usage[key] = phases[key]
        usage["sched_ticks"] = (req.finished_at_tick
                                - req.admitted_at_tick + 1) \
            if req.admitted_at_tick >= 0 and req.finished_at_tick >= 0 \
            else 0
        return usage

    def _finalize(self, work: _Work):
        req = work.request
        if req.error_code == "ENGINE_FAULT" and self._should_retry(work):
            # zero tokens delivered: the fault is invisible to the client,
            # so requeue with backoff instead of surfacing a 500. Greedy
            # decode makes the retried run token-identical to a fault-free
            # one — never silence, never duplicates.
            self._schedule_retry(work)
            return
        if req.error_code == "CANCELLED":
            # user cancel / client disconnect: a first-class outcome, not
            # an error — partial output is dropped, the slot already freed
            env = {"status": "cancelled", "code": "CANCELLED",
                   "error": req.error, "model_id": self.model_id}
        elif req.error_code is not None:        # shed by the controller
            env = self._error_envelope(req.error, req.error_code)
        else:
            try:
                preds = self.wrapper.format_generation(req.output,
                                                       len(work.prompt))
                env = {"status": "ok", "predictions": preds,
                       "model_id": self.model_id,
                       "latency_ms": round(
                           (_mono() - work.t0) * 1e3, 3)}
                self.metrics.inc("max_generated_tokens_total",
                                 len(req.output), model=self.model_id)
            except MAXError as e:
                env = self._error_envelope(str(e))
        work.envelope = env
        if req.error_code == "CANCELLED":
            self.batch_stats.cancelled += 1
        elif req.error_code not in ("DEADLINE_EXCEEDED", "ENGINE_FAULT"):
            # shed work never ran and faulted work never finished — both
            # are counted by their own scheduler stats ('shed' /
            # 'engine_faults'), not 'completed' (keeps service and
            # scheduler counts reconciled)
            self.batch_stats.completed += 1
        self._count_request(req.priority, env)
        usage = self._usage(work)
        self._observe_phases(req.priority, usage)
        if work.job is not None:
            self._finish_job(work.job, env, usage=usage)
        work.event.set()
        if work.notify is not None:
            try:
                work.notify(env, usage)
            # maxlint: allow[exception-safety] reason=notify is a caller-supplied stream callback; the envelope already carries the outcome and a broken subscriber must not fail the worker
            except Exception:
                pass

    def _reap(self):
        """Finalize done requests; flip jobs of admitted work to running."""
        with self._cv:
            done = [self._inflight.pop(rid)
                    for rid in [rid for rid, w in self._inflight.items()
                                if w.request.done]]
            for w in self._inflight.values():
                if (w.job is not None and w.job.state == "queued"
                        and w.request.admitted_at_tick >= 0):
                    w.job.state = "running"
        for work in done:
            self._finalize(work)

    def _fail_all(self, msg: str, code: str = "INTERNAL"):
        with self._cv:
            works = list(self._inflight.values())
            self._inflight.clear()
            works += [w for _, w in self._retry_q]   # backoff parking lot
            self._retry_q.clear()
        for work in works:
            work.envelope = self._error_envelope(msg, code)
            if work.job is not None:
                self._finish_job(work.job, work.envelope)
            work.event.set()
            if work.notify is not None:          # release stream consumers
                try:
                    work.notify(work.envelope, None)
                # maxlint: allow[exception-safety] reason=best-effort consumer release during fail-all; the error envelope is already recorded on the job
                except Exception:
                    pass

    # -- retry with backoff ------------------------------------------------

    def _queue_frac(self) -> float:
        """Queue pressure as a fraction of the per-class admission bound
        (the brownout controller's primary signal)."""
        return self.scheduler.queued_count() / max(1, self.max_queue)

    def _should_retry(self, work: _Work) -> bool:
        """A faulted request may requeue only while the fault is invisible
        (zero delivered tokens), attempts remain, the original deadline
        has not passed, and the service is still open."""
        if self._closed or work.delivered:
            return False
        if work.attempts >= self.max_retries:
            return False
        if work.deadline_at is not None and _mono() >= work.deadline_at:
            return False
        return True

    def _schedule_retry(self, work: _Work, *, locked: bool = False):
        """Park ``work`` for exponential-backoff resubmission. The worker
        drains due entries; its wait predicate wakes at the earliest due
        time, so a parked retry never waits on new traffic to arrive."""
        work.attempts += 1
        due = _mono() + self.retry_backoff_s * (2 ** (work.attempts - 1))
        self.retries += 1
        self.metrics.inc("max_retries_total", model=self.model_id)
        if work.request is not None and work.request.trace is not None:
            work.request.trace.event("retry", attempt=work.attempts)

        def park():
            self._retry_q.append((due, work))
            self._retry_q.sort(key=lambda t: t[0])
            self._cv.notify_all()
        if locked:
            park()
        else:
            with self._cv:
                park()

    def _retry_wait_locked(self) -> Optional[float]:
        """How long the idle worker may sleep (None = until notified)."""
        if not self._retry_q:
            return None
        return max(0.001, self._retry_q[0][0] - _mono())

    def _drain_due_retries_locked(self) -> List[_Work]:
        """Resubmit every due retry (``_cv`` held). Returns works whose
        resubmission failed terminally — the caller finalizes them outside
        the lock (finalizing fans out to job/stream callbacks)."""
        failed: List[_Work] = []
        now = _mono()
        while self._retry_q and self._retry_q[0][0] <= now:
            work = self._retry_q.pop(0)[1]
            qos = work.qos
            deadline_s = None
            if work.deadline_at is not None:
                deadline_s = max(0.0, work.deadline_at - _mono())
            work.last_tok_t = None
            try:
                work.request = self.scheduler.submit(
                    work.prompt, extra=work.extra,
                    priority=_qos_field(qos, "priority"),
                    client=_qos_field(qos, "client"),
                    deadline_s=deadline_s,
                    token_sink=work.sink, **work.gen_kw)
            except Exception as e:
                # admission rejected the retry (queue full / rate limit /
                # brownout): more backoff while attempts last, else the
                # original fault is terminal
                if self._should_retry(work):
                    self._schedule_retry(work, locked=True)
                else:
                    if work.request is not None:
                        work.request.error = (
                            f"{work.request.error}; retry rejected: {e}")
                    failed.append(work)
                continue
            if work.request.trace is not None:
                work.request.trace.event("retry_resubmit",
                                         attempt=work.attempts)
            if work.job is not None and self.tracer is not None:
                work.job.trace_id = work.request.id   # trace follows retry
            self._inflight[work.request.id] = work
        return failed

    # -- supervision -------------------------------------------------------

    def _observe_pressure(self):
        """Feed scheduler-stat deltas to metrics and the brownout
        controller — once per worker iteration, at an existing host sync
        cadence (never on the per-token path)."""
        ss = self.scheduler.stats
        df = ss.engine_faults - self._faults_seen
        if df > 0:
            self._faults_seen = ss.engine_faults
            self.metrics.inc("max_engine_faults_total", df,
                             model=self.model_id)
            if self._brownout is not None:
                self._brownout.note("fault", df)
        dp = ss.pool_exhausted - self._pool_exhausted_seen
        if dp > 0:
            self._pool_exhausted_seen = ss.pool_exhausted
            if self._brownout is not None:
                self._brownout.note("pool_exhausted", dp)
        if self._brownout is not None:
            self._brownout.observe(self._queue_frac())

    def _maybe_rebuild(self):
        if (self.rebuild_after_faults
                and self.scheduler.fault_streak >= self.rebuild_after_faults):
            self._rebuild_engine(
                f"{self.scheduler.fault_streak} consecutive engine faults")

    def _rebuild_engine(self, reason: str):
        """Recovery hammer: quarantine every active slot (their requests
        retry or fail as ENGINE_FAULT), rebuild all mutable engine state
        (pool, caches, jitted fns), and keep going. Queued admission work
        never touched the engine and rides through untouched."""
        self.scheduler.quarantine_active(f"engine rebuild: {reason}",
                                         site="rebuild")
        self.engine.reset()
        self.scheduler.fault_streak = 0
        self.engine_rebuilds += 1
        self.metrics.inc("max_engine_rebuilds_total", model=self.model_id)
        self._reap()                      # requeue/fail the quarantined work

    def _watchdog(self):
        """Supervision loop: detects ticks that blow the stall budget and
        worker threads that died (an escaped ``WorkerKill``, or any bug
        the per-batch isolation could not catch) and respawns them."""
        while True:
            time.sleep(self.watchdog_interval_s)
            if self._closed:
                return
            t0 = self._tick_started
            if (t0 is not None and not self._stall_flagged
                    and _mono() - t0 > self.stall_budget_s):
                self._stall_flagged = True
                self.tick_stalls += 1
                self.metrics.inc("max_tick_stalls_total",
                                 model=self.model_id)
                if self._brownout is not None:
                    self._brownout.note("stall")
            if not self._thread.is_alive() and not self._closed:
                self._respawn_worker()

    def _respawn_worker(self):
        """The worker is dead: whatever it was driving is lost mid-tick,
        so engine state is untrustworthy — quarantine active slots (their
        requests retry or fail; queued work persists), reset the engine,
        and start a fresh worker."""
        self.worker_restarts += 1
        self.metrics.inc("max_worker_restarts_total", model=self.model_id)
        self._tick_started = None
        self._stall_flagged = False
        try:
            self.scheduler.quarantine_active("worker died mid-batch",
                                             site="worker")
            self.engine.reset()
            self.scheduler.fault_streak = 0
        except Exception as e:
            self._worker_error = f"respawn recovery failed: {e}"
        self._reap()
        with self._cv:
            if self._closed:
                return
            self._thread = threading.Thread(
                target=self._worker, daemon=True,
                name=f"batched-{self.model_id}")
            self._thread.start()

    def _worker(self):
        """The serving thread. Its time is tiled by profiler spans:
        ``max.worker.wait`` (waiting for work, then the coalescing window),
        ``max.worker.between`` (retries, reaping, pressure and rebuild
        checks around each tick) and, nested in it, the scheduler's
        ``max.sched.*`` ticks."""
        while True:
            with span("max.worker.wait"), self._cv:
                while (not self.scheduler.has_work() and not self._closed
                       and not (self._retry_q
                                and self._retry_q[0][0] <= _mono())):
                    self._cv.wait(timeout=self._retry_wait_locked())
                if self._closed:
                    break
                with span("max.worker.between"):
                    failed = self._drain_due_retries_locked()
                # coalescing window: give simultaneous arrivals a chance to
                # share the first prefill/decode batch
                deadline = _mono() + self.batch_window_s
                while (self.scheduler.queued_count() < self.engine.max_batch
                       and not self._closed):
                    remaining = deadline - _mono()
                    if remaining <= 0:
                        break
                    self._cv.wait(timeout=remaining)
                if self._closed:
                    break
            with span("max.worker.between"):
                for work in failed:
                    self._finalize(work)
            try:
                self._run_batch()
            except WorkerKill as e:
                # injected worker death: leave without cleanup, exactly
                # like a crashed thread — the watchdog quarantines what we
                # held, resets the engine, and respawns
                self._worker_error = f"worker killed: {e}"
                return
            except Exception as e:              # fault isolation: the worker
                self._worker_error = str(e)     # must survive bad batches
                self._fail_all(f"batch failed: {e}", "INTERNAL")
        self._fail_all(f"service for {self.model_id!r} is closed", "INTERNAL")

    def _run_batch(self):
        """Tick the scheduler until it drains, admitting newcomers between
        ticks — later arrivals join the running batch (continuous
        batching); the controller decides who gets the next free slot."""
        sched = self.scheduler
        while not self._closed:
            # one span per iteration with the tick nested in it, so the
            # calls between the scheduler's spans (where the thread may
            # give up the GIL) are named too
            with span("max.worker.between"):
                with self._cv:
                    failed = self._drain_due_retries_locked()
                for work in failed:
                    self._finalize(work)
                if not sched.has_work():
                    break
                self._tick_started = _mono()  # the watchdog's stall clock
                sched.tick()
                self._tick_started = None
                self._stall_flagged = False
                self._reap()
                self._observe_pressure()
                self._maybe_rebuild()
        with span("max.worker.between"):
            self._reap()

    # -- fleet hooks (replica groups) --------------------------------------

    def load(self) -> int:
        """Dispatch-load signal for the fleet's least-loaded picker:
        queued + occupied decode slots + parked retries (point-in-time
        reads; never blocks behind the worker)."""
        return (self.scheduler.queued_count()
                + self.scheduler.active_count() + len(self._retry_q))

    def begin_drain(self):
        """Stop admitting new work (fleet scale-down): everything already
        accepted still runs to completion; fresh submissions raise
        :class:`ServiceOverloaded` so the dispatcher fails over to a
        surviving replica."""
        with self._cv:
            self._draining = True
            self._cv.notify_all()

    def idle(self) -> bool:
        """True when nothing is queued, active, or parked for retry."""
        with self._cv:
            return (not self._inflight and not self._retry_q
                    and not self.scheduler.has_work())

    def export_restartable(self) -> List["_Work"]:
        """Detach every zero-delivery in-flight work (queued, active, or
        parked for retry) so the fleet can resubmit it on a surviving
        replica. Safe for the same reason the fault-retry path is: no
        token has reached a client, and greedy decode makes the replayed
        run token-identical. Work that already delivered tokens stays
        behind to finish on this replica."""
        out: List[_Work] = []
        with self._cv:
            for rid in [rid for rid, w in self._inflight.items()
                        if not w.delivered]:
                out.append(self._inflight.pop(rid))
            out.extend(w for _, w in self._retry_q)
            self._retry_q.clear()
        for w in out:
            # retire the old scheduler entry (frees its slot / queue spot);
            # the _Work is no longer tracked here, so the CANCELLED retire
            # has nothing to finalize on this service
            if w.request is not None:
                self.scheduler.cancel(w.request.id)
        return out

    # -- introspection / lifecycle ----------------------------------------

    def health(self) -> Dict[str, Any]:
        """Liveness/readiness/degradation for ``GET /v2/health``: live
        while open; ready only with a live (or respawning) worker and the
        circuit closed. Load balancers route on ``ready`` and read
        ``Retry-After`` off the 503 the endpoint returns when it is not."""
        alive = self._thread.is_alive()
        state = "normal"
        if self._brownout is not None:
            state = self._brownout.observe(self._queue_frac())
        return {
            "live": not self._closed,
            "ready": (not self._closed and not self._draining
                      and alive and state != "hard"),
            "draining": self._draining,
            "degradation": state,
            "worker_alive": alive,
            "worker_restarts": self.worker_restarts,
            "tick_stalls": self.tick_stalls,
            "engine_faults": self.scheduler.stats.engine_faults,
            "engine_rebuilds": self.engine_rebuilds,
            "retry_pending": len(self._retry_q),
            "queue_depth": self.scheduler.queued_count(),
        }

    def stats(self) -> Dict[str, Any]:
        out = super().stats()
        bs, ss = self.batch_stats, self.scheduler.stats
        out.update({
            "submitted": bs.submitted,
            "completed": bs.completed,
            "rejected": bs.rejected,
            # every CANCELLED retire (jobs, streams, disconnects) — a
            # superset of the base class's job-only count
            "cancelled": bs.cancelled,
            "shed": ss.shed,
            "decode_steps": ss.decode_steps,
            "decode_chunks": ss.chunks,
            "decode_chunk": self.scheduler.decode_chunk,
            "cache_overflows": ss.cache_overflows,
            "pool_exhausted": ss.pool_exhausted,
            "kv_cache": self.engine.kv_stats(),
            "emitted_tokens": ss.emitted_tokens,
            # wall time accrues per tick, so this is real whichever loop
            # drives the scheduler (run() or the service worker)
            "tokens_per_s": round(ss.tokens_per_s, 2),
            "mean_batch_size": round(ss.mean_batch_size, 3),
            "max_batch_seen": ss.max_occupancy,
            "batch_window_s": self.batch_window_s,
            "queue_depth": self.scheduler.queued_count(),
            "engine_max_batch": self.engine.max_batch,
            # where the worker's tick time goes: host work vs the one
            # sync (wall_s == host_s + sync_wait_s), the worker's CPU time
            # over the host part, and the engine's admission host time
            "scheduler": {
                "ticks": ss.ticks, "wall_s": round(ss.wall_s, 6),
                "host_s": round(ss.host_s, 6),
                "sync_wait_s": round(ss.sync_wait_s, 6),
                "host_cpu_s": round(ss.host_cpu_s, 6),
                "kv_tokens_sum": ss.kv_tokens_sum,
                "inserts": self.engine.inserts,
                "insert_host_s": round(self.engine.insert_host_s, 6)},
        })
        if getattr(self.engine, "prefix_cache", None) is not None:
            # also nested under kv_cache; surfaced top-level so dashboards
            # need not know the KV layout to find hit rates
            out["prefix_cache"] = self.engine.prefix_stats()
        out["robustness"] = {
            "engine_faults": ss.engine_faults,
            "retries": self.retries,
            "retry_pending": len(self._retry_q),
            "worker_restarts": self.worker_restarts,
            "engine_rebuilds": self.engine_rebuilds,
            "tick_stalls": self.tick_stalls,
            "worker_alive": self._thread.is_alive(),
            "brownout": (self._brownout.stats() if self._brownout is not None
                         else {"state": "normal"}),
            "fault_injection": (self.fault_plane.stats()
                                if self.fault_plane is not None else None),
        }
        if self._worker_error:
            out["last_worker_error"] = self._worker_error
        return out

    def close(self):
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        # the worker exits at its next wait/tick boundary and fails
        # everything it still holds; the direct _fail_all below covers a
        # worker stuck past the join timeout (each work is popped exactly
        # once under the lock, so nothing double-finalizes)
        self._thread.join(timeout=5)
        self._watchdog_thread.join(timeout=2 * self.watchdog_interval_s + 1)
        self._fail_all(f"service for {self.model_id!r} is closed", "INTERNAL")
        super().close()


# ---------------------------------------------------------------------------
# Factory
# ---------------------------------------------------------------------------

def make_service(wrapper: MAXModelWrapper, mode: str = "auto",
                 **service_kw) -> InferenceService:
    """``mode``: 'sync' | 'batched' | 'auto' (batched iff the wrapper speaks
    the generation protocol — classifiers and other per-call models stay
    sync). ``qos`` / ``metrics`` / ``job_ttl_s`` and the tracing knobs
    (``trace`` / ``trace_buffer`` / ``slow_trace_ms``) apply to either
    kind; the remaining kwargs — including the robustness knobs
    (``faults`` / ``brownout`` / ``max_retries`` / ``stall_budget_s`` …)
    — are batched-service tuning and are ignored by sync services (a
    sync call has no worker to supervise or queue to shed)."""
    shared = {k: service_kw.pop(k)
              for k in ("qos", "metrics", "job_ttl_s",
                        "trace", "trace_buffer", "slow_trace_ms")
              if k in service_kw}
    if mode == "sync":
        return SyncService(wrapper, **shared)
    if mode == "batched":
        return BatchedService(wrapper, **service_kw, **shared)
    if mode == "auto":
        if wrapper.supports_generation():
            return BatchedService(wrapper, **service_kw, **shared)
        return SyncService(wrapper, **shared)
    raise ValueError(f"unknown service mode {mode!r} "
                     "(expected sync|batched|auto)")
