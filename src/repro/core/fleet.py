"""Replica groups: N engine replicas behind one replica-aware front door.

The paper's deployment story ("heavy traffic from millions of users")
needs horizontal scale for a *single* model, not just many models side by
side. A :class:`ReplicaSet` owns N :class:`~repro.core.service.BatchedService`
replicas — each with its own engine, KV pool, scheduler, worker thread,
watchdog, and brownout controller — placed on disjoint device slices by a
:class:`~repro.serving.replica.MeshPlacement`, and presents the exact
:class:`~repro.core.service.InferenceService` surface the API layer
already speaks, so every route works unchanged against a fleet.

Division of labor with QoS:

- *global* (front door): per-client token-bucket rate limiting — charged
  once here; each replica's controller runs with rates stripped
  (:meth:`QoSConfig.for_replica`) so dispatch never double-charges;
- *per replica*: queue bounds, DRR fairness, brownout, watchdog,
  engine rebuild — one faulty replica degrades alone, the fleet stays up.

Dispatch is least-loaded (queued + occupied slots + parked retries) by
default; requests carrying a client identity (``X-MAX-Client``) are
session-affine via rendezvous hashing, so a client's prefix-cache
locality survives fleet membership changes with minimal reshuffling. A
replica that rejects with QUEUE_FULL triggers failover to the next
replica (streams dispatch once — their error event is the retry signal).

Scaling down drains: the victim stops admitting (dispatch skips it
immediately), finishes what it holds, and anything still pending at the
drain deadline is *migrated* — zero-delivery work is detached through the
PR-8 safe-retry invariant (no token reached a client + greedy decode ⇒
token-identical replay) and resubmitted onto survivors; only then is the
replica closed and its slice freed.
"""

from __future__ import annotations

import hashlib
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterator, List, Optional, Tuple

from repro.core.router import StreamEvent
from repro.core.service import (
    BatchedService, InferenceService, Job, ServiceOverloaded, _qos_field,
)
from repro.core.wrapper import MAXModelWrapper
from repro.serving.metrics import LabelledRegistry, MetricsRegistry
from repro.serving.qos import AdmissionError, DEFAULT_CLIENT, QoSConfig
from repro.serving.replica import (
    MeshPlacement, MeshSliceError, ReplicaSlice, parse_mesh_slice,
)
from repro.serving.tracing import now as _now

_SEVERITY = {"normal": 0, "soft": 1, "hard": 2}
_SEVERITY_NAMES = {v: k for k, v in _SEVERITY.items()}


def _rendezvous_score(client: str, replica: str) -> int:
    digest = hashlib.blake2b(f"{client}|{replica}".encode(),
                             digest_size=8).digest()
    return int.from_bytes(digest, "big")


@dataclass
class _Replica:
    """One live replica: a batched service bound to a device slice."""

    index: int
    name: str                               # "r0", "r1", ...
    service: BatchedService
    slice_: Optional[ReplicaSlice] = None
    draining: bool = False
    created_at: float = field(default_factory=_now)


class ReplicaSet(InferenceService):
    """N batched-service replicas behind one InferenceService surface."""

    kind = "fleet"

    def __init__(self, factory: Callable[[], MAXModelWrapper], *,
                 replicas: int, placement: Optional[MeshPlacement] = None,
                 drain_timeout_s: float = 5.0, **service_kw):
        if not isinstance(replicas, int) or isinstance(replicas, bool) \
                or replicas < 1:
            raise ValueError(f"replicas must be a positive integer, "
                             f"got {replicas!r}")
        self._factory = factory
        self._placement = placement if placement is not None \
            else parse_mesh_slice(None, replicas=replicas)
        if self._placement.replicas != replicas:
            raise MeshSliceError(
                f"placement has {self._placement.replicas} slices for "
                f"{replicas} replicas")
        self.drain_timeout_s = drain_timeout_s
        # same kwarg split as make_service: shared knobs ride to every
        # replica; the rest is batched-service tuning
        shared = {k: service_kw.pop(k)
                  for k in ("qos", "metrics", "job_ttl_s",
                            "trace", "trace_buffer", "slow_trace_ms")
                  if k in service_kw}
        self._faults = service_kw.pop("faults", None)
        self._batched_kw = service_kw
        metrics = shared.get("metrics")
        self._base_metrics = metrics if metrics is not None \
            else MetricsRegistry()
        # when no QoS config is given, replicas must take the default
        # BatchedService path (so a bare ``max_queue`` override still
        # applies); when one is given, each replica runs it rate-stripped
        qos_cfg = shared.get("qos")
        self._qos_given = qos_cfg is not None
        self._qos = qos_cfg if isinstance(qos_cfg, QoSConfig) \
            else QoSConfig.from_json(qos_cfg)
        self._shared = dict(shared)
        self._fleet_lock = threading.RLock()    # replica list + job routes
        self._scale_lock = threading.Lock()     # serialize scale()/close()
        self._replicas: List[_Replica] = []
        self._jobmap: Dict[str, _Replica] = {}
        self.dispatched = {"least_loaded": 0, "affine": 0, "failover": 0}
        self.migrated = 0
        self.scale_events = 0
        try:
            for i in range(replicas):
                self._replicas.append(self._spawn(i))
        except Exception:
            for rep in self._replicas:      # no half-built fleets
                rep.service.close()
            raise
        # the front door: global client rate limiting on the full QoS
        # config (replicas run rate-stripped copies), fleet-wide metrics
        super().__init__(self._replicas[0].service.wrapper,
                         qos=self._qos, metrics=self._base_metrics,
                         job_ttl_s=shared.get("job_ttl_s"), trace=False)
        self.metrics.describe(
            "max_fleet_replicas", "Live replicas of this fleet deployment")
        # fleet-level aggregates replace the per-model gauges the base
        # init registered (per-replica series carry a replica label)
        self.metrics.register_gauge(
            "max_active_streams", self._streams_total, model=self.model_id)
        self.metrics.register_gauge(
            "max_queue_depth", self._queue_total, model=self.model_id)
        self.metrics.register_gauge(
            "max_fleet_replicas", lambda: float(self.size),
            model=self.model_id)

    # -- replica lifecycle -------------------------------------------------

    def _fault_for(self, index: int) -> Optional[Any]:
        """Fault-injection spec for replica ``index``: a dict arms every
        replica identically; a list arms per replica (short lists leave
        the tail unarmed) — how chaos tests kill exactly one replica."""
        if self._faults is None:
            return None
        if isinstance(self._faults, (list, tuple)):
            return self._faults[index] if index < len(self._faults) else None
        return self._faults

    def _build_on_slice(self, sl: Optional[ReplicaSlice]
                        ) -> MAXModelWrapper:
        """Build one replica's wrapper on the slice's lead device and
        commit its weights and KV cache there (compute follows committed
        operands, so the replica's decode runs there too). A slice that
        cannot be bound raises: a replica never silently lands on another
        device. On a single-device platform every slice binds that device
        (an oversubscribed placement)."""
        dev = None
        if sl is None:
            wrapper = self._factory()
        else:
            import jax
            dev = sl.bind(jax.devices())[0]
            with jax.default_device(dev):
                wrapper = self._factory()
        if not wrapper.supports_generation():
            raise ValueError(
                f"{wrapper.metadata.id!r} does not implement the "
                "generation protocol; replica groups require the batched "
                "service")
        if dev is not None:
            wrapper.place(dev)
        return wrapper

    def _spawn(self, index: int) -> _Replica:
        name = f"r{index}"
        sl = self._placement.slices[index] \
            if index < len(self._placement.slices) else None
        wrapper = self._build_on_slice(sl)
        kw: Dict[str, Any] = dict(self._batched_kw)
        kw["faults"] = self._fault_for(index)
        for k in ("job_ttl_s", "trace", "trace_buffer", "slow_trace_ms"):
            if k in self._shared:
                kw[k] = self._shared[k]
        if self._qos_given:
            kw["qos"] = self._qos.for_replica()
        svc = BatchedService(
            wrapper,
            metrics=LabelledRegistry(self._base_metrics, replica=name),
            **kw)
        if svc.tracer is not None:
            svc.tracer.replica = name
        return _Replica(index=index, name=name, service=svc, slice_=sl)

    @property
    def size(self) -> int:
        return len(self._replicas)

    @property
    def placement(self) -> MeshPlacement:
        return self._placement

    def replica_tracers(self) -> List[Tuple[str, Any]]:
        """(name, tracer) per replica — the Perfetto export renders one
        process group per replica from these."""
        with self._fleet_lock:
            reps = list(self._replicas)
        return [(r.name, r.service.tracer) for r in reps
                if r.service.tracer is not None]

    # -- dispatch ----------------------------------------------------------

    def _live(self) -> List[_Replica]:
        with self._fleet_lock:
            return [r for r in self._replicas if not r.draining]

    def _pick(self, qos: Optional[Dict[str, Any]],
              exclude: Tuple[_Replica, ...] = ()) -> _Replica:
        live = [r for r in self._live() if r not in exclude]
        if not live:
            raise ServiceOverloaded(
                f"no replica of {self.model_id!r} is accepting work")
        client = _qos_field(qos, "client")
        if client and not exclude:
            # rendezvous hashing: stable per client while membership
            # holds, minimal reshuffling when it changes — the client's
            # prefix-cache locality lives on its home replica.  blake2b,
            # not crc32: crc is linear, so client names differing in one
            # trailing character produce correlated scores and whole
            # client families collapse onto one replica
            rep = max(live, key=lambda r: _rendezvous_score(client, r.name))
            kind = "affine"
        else:
            rep = min(live, key=lambda r: (r.service.load(), r.index))
            kind = "failover" if exclude else "least_loaded"
        with self._fleet_lock:
            self.dispatched[kind] += 1
        return rep

    def _admit(self, inp: Any, qos: Optional[Dict[str, Any]]):
        """Global front-door admission: one token-bucket charge per
        request, fleet-wide. Raises AdmissionError."""
        self.admission.try_acquire(
            _qos_field(qos, "client") or DEFAULT_CLIENT,
            cost=self._request_cost(inp),
            priority=_qos_field(qos, "priority"))

    def _admission_envelope(self, e: Exception) -> Dict[str, Any]:
        env = {"status": "error", "error": str(e),
               "code": getattr(e, "code", "INTERNAL"),
               "model_id": self.model_id}
        ra = getattr(e, "retry_after_s", None)
        if ra is not None:
            env["retry_after_s"] = ra
        return env

    def _saturated_envelope(self, e: Exception) -> Dict[str, Any]:
        return {"status": "error", "error": str(e), "code": "QUEUE_FULL",
                "model_id": self.model_id, "retry_after_s": 1.0}

    # -- request paths -----------------------------------------------------

    def predict(self, inp: Any,
                qos: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        try:
            self._admit(inp, qos)
        except AdmissionError as e:
            return self._admission_envelope(e)
        tried: Tuple[_Replica, ...] = ()
        while True:
            try:
                rep = self._pick(qos, exclude=tried)
            except ServiceOverloaded as e:
                return self._saturated_envelope(e)
            env = rep.service.predict(inp, qos)
            if env.get("code") != "QUEUE_FULL":
                return env
            tried = tried + (rep,)      # failover past the full replica

    def predict_batch(self, inputs: List[Any],
                      qos: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
        """Enqueue everything first (spreading across replicas as load
        accrues), then await — concurrent inputs share decode batches on
        every replica at once instead of trickling through one."""
        staged: List[Tuple[Optional[_Replica], Any]] = []
        for inp in inputs:
            try:
                self._admit(inp, qos)
            except AdmissionError as e:
                staged.append((None, self._admission_envelope(e)))
                continue
            tried: Tuple[_Replica, ...] = ()
            while True:
                try:
                    rep = self._pick(qos, exclude=tried)
                except ServiceOverloaded as e:
                    staged.append((None, self._saturated_envelope(e)))
                    break
                w = rep.service._enqueue_or_error(inp, qos=qos)
                if isinstance(w, dict) and w.get("code") == "QUEUE_FULL":
                    tried = tried + (rep,)
                    continue
                staged.append((rep, w))
                break
        return [w if rep is None or isinstance(w, dict)
                else rep.service._await(w)
                for rep, w in staged]

    def _error_events(self, code: str, message: str,
                      retry_after_s: Optional[float] = None
                      ) -> Iterator[StreamEvent]:
        """Pre-stream rejection: the same flat error-event shape a
        replica's own pre-stream rejections use."""
        data: Dict[str, Any] = {"code": code, "message": message,
                                "model_id": self.model_id}
        if retry_after_s is not None:
            data["retry_after_s"] = retry_after_s
        yield StreamEvent("error", data, 0)

    def predict_stream(self, inp: Any,
                       qos: Optional[Dict[str, Any]] = None
                       ) -> Iterator[StreamEvent]:
        try:
            self._admit(inp, qos)
        except AdmissionError as e:
            return self._error_events(
                e.code, str(e), getattr(e, "retry_after_s", None))
        try:
            rep = self._pick(qos)
        except ServiceOverloaded as e:
            return self._error_events("QUEUE_FULL", str(e), 1.0)
        # streams dispatch exactly once: a replica-side rejection arrives
        # as the stream's error event (the client's retry signal) —
        # failing over after events may have flowed could duplicate them
        return rep.service.predict_stream(inp, qos)

    def submit_job(self, inp: Any,
                   qos: Optional[Dict[str, Any]] = None) -> Job:
        # admission/validation failures propagate exactly as a single
        # service's would: the API layer turns them into 429/400, never a
        # 202 with a dead job
        self._admit(inp, qos)
        tried: Tuple[_Replica, ...] = ()
        while True:
            rep = self._pick(qos, exclude=tried)   # ServiceOverloaded out
            try:
                job = rep.service.submit_job(inp, qos)
            except ServiceOverloaded:
                tried = tried + (rep,)      # queue full here: fail over
                continue
            with self._fleet_lock:
                self._jobmap[job.id] = rep
                self._prune_jobmap_locked()
            return job

    # -- job routing -------------------------------------------------------

    def _prune_jobmap_locked(self):
        """Bound the routing table: drop routes whose job record its
        replica has already GC'd (the replica's TTL/retention rules are
        the source of truth; the route is just a fast path)."""
        if len(self._jobmap) <= 2048:
            return
        for jid, rep in list(self._jobmap.items()):
            with rep.service._jobs_lock:
                known = jid in rep.service._jobs
            if not known:
                del self._jobmap[jid]

    def _route(self, job_id: str) -> Optional[_Replica]:
        with self._fleet_lock:
            return self._jobmap.get(job_id)

    def get_job(self, job_id: str) -> Job:
        rep = self._route(job_id)
        if rep is not None:
            try:
                return rep.service.get_job(job_id)
            except KeyError:
                pass                    # migrated or GC'd: fall through
        with self._fleet_lock:
            reps = list(self._replicas)
        for r in reps:
            try:
                return r.service.get_job(job_id)
            except KeyError:
                continue
        return super().get_job(job_id)  # fleet-level (rejected/orphaned)

    def cancel_job(self, job_id: str) -> bool:
        rep = self._route(job_id)
        if rep is not None and rep.service.cancel_job(job_id):
            return True
        with self._fleet_lock:
            reps = list(self._replicas)
        return any(r.service.cancel_job(job_id)
                   for r in reps if r is not rep)

    def delete_job(self, job_id: str) -> bool:
        rep = self._route(job_id)
        ok = rep is not None and rep.service.delete_job(job_id)
        if not ok:
            with self._fleet_lock:
                reps = list(self._replicas)
            ok = any(r.service.delete_job(job_id)
                     for r in reps if r is not rep)
        if not ok:
            ok = super().delete_job(job_id)
        if ok:
            with self._fleet_lock:
                self._jobmap.pop(job_id, None)
        return ok

    def get_trace(self, job_id: str) -> Dict[str, Any]:
        rep = self._route(job_id)
        if rep is None:
            with self._fleet_lock:
                reps = list(self._replicas)
            for r in reps:
                try:
                    r.service.get_job(job_id)
                except KeyError:
                    continue
                rep = r
                break
        if rep is not None:
            return rep.service.get_trace(job_id)
        self.get_job(job_id)            # KeyError if truly unknown
        raise KeyError(f"job {job_id!r} was rejected at the fleet front "
                       "door and has no trace record")

    # -- scaling -----------------------------------------------------------

    def scale(self, replicas: int, *,
              placement: Optional[MeshPlacement] = None,
              drain_timeout_s: Optional[float] = None):
        """Grow or shrink the fleet in place. Scale-up spawns fresh
        replicas on the new placement; scale-down drains the
        highest-index replicas onto the survivors (see module docstring)
        before freeing their slices. Raises MeshSliceError if the spec
        cannot be re-partitioned for the new count — validated before any
        replica is touched."""
        if not isinstance(replicas, int) or isinstance(replicas, bool) \
                or replicas < 1:
            raise ValueError(f"replicas must be a positive integer, "
                             f"got {replicas!r}")
        timeout = self.drain_timeout_s if drain_timeout_s is None \
            else drain_timeout_s
        with self._scale_lock:
            if placement is None:
                placement = parse_mesh_slice(self._placement.spec,
                                             replicas=replicas)
            if placement.replicas != replicas:
                raise MeshSliceError(
                    f"placement has {placement.replicas} slices for "
                    f"{replicas} replicas")
            cur = self.size
            if replicas == cur:
                self._placement = placement
                return
            self.scale_events += 1
            if replicas > cur:
                self._placement = placement
                for i in range(cur, replicas):
                    rep = self._spawn(i)
                    with self._fleet_lock:
                        self._replicas.append(rep)
                return
            with self._fleet_lock:      # dispatch skips victims at once
                victims = self._replicas[replicas:]
                for v in victims:
                    v.draining = True
            for v in victims:
                self._drain_and_retire(v, timeout)
            self._placement = placement

    def _drain_and_retire(self, victim: _Replica, timeout_s: float):
        svc = victim.service
        svc.begin_drain()
        deadline = _now() + max(0.0, timeout_s)
        while _now() < deadline and not svc.idle():
            time.sleep(0.005)
        if not svc.idle():
            # drain deadline passed: migrate what safe-retry allows, give
            # delivered-token work the rest of the window to finish
            for work in svc.export_restartable():
                self._migrate(work, victim)
            while _now() < deadline and not svc.idle():
                time.sleep(0.005)
        with self._fleet_lock:
            self._replicas.remove(victim)
        svc.close()     # whatever still holds on fails terminally here
        # finished-job records must outlive their replica (clients poll
        # after the scale-down): adopt them at the fleet level
        with svc._jobs_lock:
            orphans = dict(svc._jobs)
            svc._jobs.clear()
        if orphans:
            with self._jobs_lock:
                self._jobs.update(orphans)
        with self._fleet_lock:
            for jid in orphans:
                self._jobmap.pop(jid, None)

    def _migrate(self, work: Any, source: _Replica) -> bool:
        """Resubmit a detached zero-delivery work onto the least-loaded
        survivor (moving its job record along). Token-identical by the
        safe-retry argument; a stream's bridge callbacks move with it.
        If no survivor admits it, the work fails retryably (QUEUE_FULL)."""
        job = work.job
        orig_notify = work.notify

        def relay(env, usage):
            # a predict caller is still blocked on the ORIGINAL work's
            # event (the survivor built a fresh _Work): mirror the
            # terminal result back before releasing it
            if orig_notify is not None:
                try:
                    orig_notify(env, usage)
                # maxlint: allow[exception-safety] reason=caller-supplied stream callback; the envelope below still releases the waiter
                except Exception:
                    pass
            work.envelope = env
            work.event.set()

        tried: Tuple[_Replica, ...] = ()
        while True:
            live = [r for r in self._live() if r not in tried]
            if not live:
                break
            rep = min(live, key=lambda r: (r.service.load(), r.index))
            try:
                rep.service._enqueue(work.inp, job=job, qos=work.qos,
                                     push=work.push, notify=relay)
            except Exception:
                tried = tried + (rep,)
                continue
            if job is not None:
                with source.service._jobs_lock:
                    source.service._jobs.pop(job.id, None)
                with rep.service._jobs_lock:
                    rep.service._jobs[job.id] = job
                with self._fleet_lock:
                    self._jobmap[job.id] = rep
            with self._fleet_lock:
                self.migrated += 1
            return True
        env = self._saturated_envelope(ServiceOverloaded(
            "drained replica's work found no surviving replica with "
            "queue headroom; safe to retry"))
        if job is not None:
            source.service._finish_job(job, env)
        relay(env, None)
        return False

    # -- introspection / lifecycle ----------------------------------------

    def _streams_total(self) -> float:
        return float(sum(r.service._active_streams for r in self._live()))

    def _queue_total(self) -> float:
        return float(sum(r.service.scheduler.queued_count()
                         for r in self._live()))

    def health(self) -> Dict[str, Any]:
        """Fleet aggregate: live/ready if ANY replica is; degradation is
        the best state among ready replicas (capacity still available)
        — one replica's brownout or dead worker never marks the fleet
        down, which is the point of running a fleet."""
        with self._fleet_lock:
            reps = list(self._replicas)
        per: Dict[str, Any] = {}
        any_live = False
        best: Optional[int] = None
        ready_n = 0
        for r in reps:
            h = r.service.health()
            per[r.name] = h
            if h.get("live"):
                any_live = True
            if h.get("ready"):
                ready_n += 1
                sev = _SEVERITY.get(h.get("degradation", "normal"), 2)
                best = sev if best is None else min(best, sev)
        if best is None:
            states = [_SEVERITY.get(h.get("degradation", "normal"), 2)
                      for h in per.values()]
            best = max(states) if states else 2
        closed = getattr(self, "_closed", False)
        return {
            "live": any_live and not closed,
            "ready": ready_n > 0 and not closed,
            "degradation": _SEVERITY_NAMES[best],
            "fleet": {"size": len(reps),
                      "ready_replicas": ready_n,
                      "draining": sum(1 for r in reps if r.draining)},
            "replicas": per,
        }

    def stats(self) -> Dict[str, Any]:
        with self._fleet_lock:
            reps = list(self._replicas)
            dispatched = dict(self.dispatched)
        per: Dict[str, Any] = {}
        agg = {k: 0 for k in ("submitted", "completed", "rejected",
                              "cancelled", "shed", "emitted_tokens",
                              "queue_depth")}
        rob = {k: 0 for k in ("engine_faults", "retries",
                              "worker_restarts", "engine_rebuilds")}
        for r in reps:
            s = r.service.stats()
            s["replica"] = {"name": r.name, "draining": r.draining,
                            "slice": r.slice_.label if r.slice_ else None}
            per[r.name] = s
            for k in agg:
                agg[k] += s.get(k, 0) or 0
            for k in rob:
                rob[k] += (s.get("robustness") or {}).get(k, 0) or 0
        with self._jobs_lock:
            self._gc_jobs_locked()
            fleet_jobs = len(self._jobs)
        return {
            "kind": self.kind,
            "replicas": len(reps),
            "placement": self._placement.describe(),
            "mesh_slice": self._placement.spec,
            "oversubscribed": self._placement.oversubscribed,
            "dispatch": dispatched,
            "migrated_on_drain": self.migrated,
            "scale_events": self.scale_events,
            "orphaned_jobs": fleet_jobs,
            "qos": self.admission.stats(),
            "robustness": rob,
            "per_replica": per,
            **agg,
        }

    def close(self):
        with self._scale_lock:
            self._closed = True
            with self._fleet_lock:
                reps = list(self._replicas)
            for r in reps:
                r.service.close()
            super().close()
