"""Deployment units — the TPU-native adaptation of MAX's Docker containers.

The paper isolates each wrapped model in a Docker container so that
(1) conflicting runtimes coexist, (2) faults/security issues stay local,
(3) the system scales out. On a TPU pod there is no kernel namespace to
split; the equivalent isolation unit is a *deployment*:

- its own AOT-compiled XLA executables (program isolation — a bug in one
  model's compiled step cannot touch another's),
- its own parameter/cache arena (separately donated buffers),
- optionally its own mesh slice (disjoint chips — the direct analogue of
  CPU/memory quotas on a container).

A deployment carries an :class:`~repro.core.service.InferenceService`, not a
bare wrapper: the service decides HOW requests execute (per-call sync vs
continuous-batched on a worker thread) while the deployment stays the unit
of isolation, stats, and lifecycle.

The :class:`DeploymentManager` is the container orchestrator analogue:
deploy/undeploy/route, with per-deployment health and request stats. It is
safe under ``ThreadingHTTPServer``: stats updates are locked, and two
concurrent deploys of the same asset build the wrapper exactly once.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

from repro.core.fleet import ReplicaSet
from repro.core.registry import ModelRegistry, EXCHANGE
from repro.core.service import InferenceService, Job, make_service
from repro.core.wrapper import MAXModelWrapper
from repro.serving.metrics import MetricsRegistry
from repro.serving.replica import live_device_count, parse_mesh_slice
from repro.serving.tracing import gc_stats, now as _now
from repro.serving.qos import QoSConfig


@dataclass
class DeploymentStats:
    requests: int = 0
    errors: int = 0
    total_latency_s: float = 0.0
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    def record(self, latency_s: float, ok: bool):
        # += on a dataclass field is not atomic; ThreadingHTTPServer runs
        # one thread per connection, so take the lock
        with self._lock:
            self.requests += 1
            self.total_latency_s += latency_s
            if not ok:
                self.errors += 1

    @property
    def mean_latency_ms(self) -> float:
        return (self.total_latency_s / self.requests * 1e3) if self.requests else 0.0


@dataclass
class Deployment:
    asset_id: str
    service: InferenceService
    created_at: float = field(default_factory=_now)   # monotonic; used for uptime only
    mesh_slice: Optional[str] = None         # e.g. "pod0/rows0-7"
    stats: DeploymentStats = field(default_factory=DeploymentStats)

    @property
    def wrapper(self) -> MAXModelWrapper:    # v1 call sites use dep.wrapper
        return self.service.wrapper

    def _record(self, t0: float, env: Dict[str, Any]) -> Dict[str, Any]:
        self.stats.record(_now() - t0,
                          env.get("status") == "ok")
        return env

    def predict(self, inp: Any,
                qos: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        t0 = _now()
        return self._record(t0, self.service.predict(inp, qos))

    def predict_batch(self, inputs: List[Any],
                      qos: Optional[Dict[str, Any]] = None
                      ) -> List[Dict[str, Any]]:
        t0 = _now()
        envs = self.service.predict_batch(inputs, qos)
        per_input = (_now() - t0) / max(len(inputs), 1)
        for env in envs:
            self.stats.record(per_input, env.get("status") == "ok")
        return envs

    def submit_job(self, inp: Any,
                   qos: Optional[Dict[str, Any]] = None) -> Job:
        return self.service.submit_job(inp, qos)

    def predict_stream(self, inp: Any,
                       qos: Optional[Dict[str, Any]] = None):
        """Streaming predict with deployment-level accounting: the request
        counts once, when its stream terminates (done/error/disconnect)."""
        t0 = _now()

        def wrapped():
            ok = False
            try:
                for ev in self.service.predict_stream(inp, qos):
                    if ev.event == "done":
                        ok = True
                    yield ev
            finally:
                self.stats.record(_now() - t0, ok)
        return wrapped()


class DeploymentManager:
    def __init__(self, registry: Optional[ModelRegistry] = None, *,
                 service_mode: str = "auto",
                 service_kw: Optional[Dict[str, Any]] = None,
                 metrics: Optional[MetricsRegistry] = None):
        self.registry = registry if registry is not None else EXCHANGE
        self.service_mode = service_mode
        self.service_kw = service_kw or {}
        # one registry across all deployments: /v2/metrics is the whole
        # exchange's view, labelled per model/class/outcome
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        # the process's garbage-collection pauses (tracing counts them
        # while a service is live): a monotonic read per generation
        self.metrics.describe(
            "max_gc_pause_seconds_total",
            "Seconds the process stood in garbage collection, by generation")
        for g in range(3):
            self.metrics.register_gauge(
                "max_gc_pause_seconds_total",
                lambda g=g: round(gc_stats()["pause_s"][g], 6),
                generation=str(g))
        self._deployments: Dict[str, Deployment] = {}
        self._building: Dict[str, threading.Event] = {}
        self._lock = threading.Lock()

    def deploy(self, asset_id: str, *, mesh_slice: Optional[str] = None,
               replicas: Optional[int] = None,
               service_mode: Optional[str] = None,
               qos: Optional[Any] = None, force: bool = False,
               service_overrides: Optional[Dict[str, Any]] = None,
               **build_kw) -> Deployment:
        """``service_overrides`` are per-deploy service kwargs (e.g. the
        tracing knobs ``trace``/``trace_buffer``/``slow_trace_ms``) merged
        over the manager-wide ``service_kw`` — callers that pass them
        should also pass ``force=True`` so they take effect on a live
        deployment, mirroring the engine-knob rule.

        ``replicas: N`` (N > 1) deploys a :class:`ReplicaSet` — N batched
        replicas on disjoint ``mesh_slice`` partitions behind one
        replica-aware front door. Re-deploying a live fleet with a
        different N scales it in place (drain-and-migrate on the way
        down) instead of tearing it down, unless ``qos``/``force``/a
        concrete mode demand a rebuild. ``replicas: 1`` / ``None`` keeps
        the classic single-service path untouched."""
        if qos is not None and not isinstance(qos, QoSConfig):
            qos = QoSConfig.from_json(qos)    # validate before any teardown
        if replicas is not None and (isinstance(replicas, bool)
                                     or not isinstance(replicas, int)
                                     or replicas < 1):
            raise ValueError(
                f"replicas must be a positive integer, got {replicas!r}")
        # parse/validate the slice up front — a malformed or overlapping
        # spec must never tear down the running deployment first
        placement = None
        if replicas is not None and replicas > 1:
            if (service_mode or self.service_mode) == "sync":
                raise ValueError(
                    "replica groups require the batched service "
                    "(service_mode 'sync' cannot host a fleet)")
            placement = parse_mesh_slice(mesh_slice, replicas=replicas,
                                         device_count=live_device_count())
        elif mesh_slice is not None:
            parse_mesh_slice(mesh_slice, replicas=1,
                             device_count=live_device_count())
        while True:
            with self._lock:
                dep = self._deployments.get(asset_id)
            if dep is not None:
                cur = getattr(dep.service, "size", None) \
                    if dep.service.kind == "fleet" else None
                if (replicas is not None and cur is not None
                        and qos is None and not force
                        and service_mode in (None, "auto")):
                    # live fleet, compatible knobs: scale in place
                    if replicas != cur:
                        spec = mesh_slice if mesh_slice is not None \
                            else dep.service.placement.spec
                        dep.service.scale(
                            replicas,
                            placement=parse_mesh_slice(
                                spec, replicas=replicas,
                                device_count=live_device_count()))
                        if mesh_slice is not None:
                            dep.mesh_slice = mesh_slice
                    return dep
                replicas_ok = (replicas is None
                               or (replicas == 1 and cur is None))
                # an explicitly requested concrete mode replaces a
                # deployment of a different kind, and an explicit QoS
                # config — or ``force`` (explicit engine knobs like the
                # paged-KV layout) — always redeploys ("auto"/None accept
                # whatever is running) — silently returning the old
                # service would drop the operator's request
                if (replicas_ok and qos is None and not force
                        and (service_mode in (None, "auto")
                             or dep.service.kind == service_mode)):
                    return dep
                if ((service_mode == "batched"
                     or (replicas is not None and replicas > 1))
                        and not dep.wrapper.supports_generation()):
                    # reject BEFORE tearing down the healthy deployment
                    raise ValueError(
                        f"{asset_id!r} does not support the batched "
                        "service (no generation protocol)")
                self.undeploy(asset_id)
            with self._lock:
                if asset_id in self._deployments:
                    continue                    # someone redeployed first
                done = self._building.get(asset_id)
                if done is None:
                    done = self._building[asset_id] = threading.Event()
                    break                       # we are the builder
            # another thread is building this asset: wait, then re-check —
            # if its build failed we loop around and try to build ourselves
            done.wait()
        try:
            asset = self.registry.get(asset_id)
            service_kw = dict(self.service_kw)
            service_kw.setdefault("metrics", self.metrics)
            if qos is not None:
                service_kw["qos"] = qos             # per-deploy override
            if service_overrides:
                service_kw.update(service_overrides)
            if replicas is not None and replicas > 1:
                # each replica is its own "container start": the factory
                # builds one engine per slice inside ReplicaSet._spawn
                service: InferenceService = ReplicaSet(
                    lambda: asset.build(**build_kw),
                    replicas=replicas, placement=placement, **service_kw)
            else:
                wrapper = asset.build(**build_kw)   # the "container start"
                service = make_service(
                    wrapper, service_mode or self.service_mode,
                    **service_kw)
            dep = Deployment(asset_id, service, mesh_slice=mesh_slice)
            with self._lock:
                self._deployments[asset_id] = dep
            return dep
        finally:
            with self._lock:
                self._building.pop(asset_id, None)
            done.set()

    def undeploy(self, asset_id: str) -> bool:
        with self._lock:
            dep = self._deployments.pop(asset_id, None)
        if dep is None:
            return False
        dep.service.close()
        return True

    def get(self, asset_id: str) -> Deployment:
        try:
            return self._deployments[asset_id]
        except KeyError:
            raise KeyError(f"asset {asset_id!r} is not deployed") from None

    def deployed(self) -> List[str]:
        return sorted(self._deployments)

    def predict(self, asset_id: str, inp: Any) -> Dict[str, Any]:
        return self.get(asset_id).predict(inp)

    def health(self) -> Dict[str, Any]:
        return {
            aid: {
                "uptime_s": round(_now() - d.created_at, 1),
                "requests": d.stats.requests,
                "errors": d.stats.errors,
                "mean_latency_ms": round(d.stats.mean_latency_ms, 2),
                "mesh_slice": d.mesh_slice,
                "service": d.service.kind,
                "replicas": getattr(d.service, "size", 1),
            }
            for aid, d in list(self._deployments.items())
        }
