"""Request-lifecycle tracing: phase math, bounded rings, slow-request
capture, scheduler span threading, Chrome export, and the HTTP surface.

Acceptance anchors from the tracing PR:
- ``queue_ms + prefill_ms + decode_ms`` equals e2e latency (shared phase
  boundaries make the sum exact, not approximate);
- warm vs cold prefix-cache admissions are distinguishable from the
  prefill span's ``cached_hit_tokens`` attribute;
- ``/v2/trace/export`` validates against the Chrome trace-event schema;
- the fused==stepwise token-identity property holds with tracing enabled
  (tracing adds zero host syncs).
"""

import json
import time
import urllib.error
import urllib.request

import jax
import pytest

import repro.core.assets  # noqa: F401
from repro.configs import CONFIGS
from repro.core import MAXServer
from repro.models import build_model
from repro.serving import ContinuousBatchingScheduler, GenerationEngine
from repro.serving.qos import AdmissionController, AdmissionError, QoSConfig
from repro.serving.tracing import RequestTrace, Tracer, now


# -- unit: phase math --------------------------------------------------------

def test_phase_sum_is_exact():
    """Boundaries are shared timestamps, so the sum is exact by
    construction — not 'approximately e2e'."""
    tr = RequestTrace(1, submitted_at=100.0)
    tr.admitted(100.5, slot=0, tick=10)
    tr.first_token(100.7)
    t = Tracer()
    t._live[1] = tr
    t.finish(tr, outcome="ok", tick=14, completion_tokens=8, ts=101.0)
    p = tr.phases()
    assert p == {"queue_ms": 500.0, "prefill_ms": 200.0,
                 "decode_ms": 300.0, "e2e_ms": 1000.0, "sched_ticks": 5}
    assert p["queue_ms"] + p["prefill_ms"] + p["decode_ms"] == p["e2e_ms"]


def test_phases_of_request_that_never_ran():
    """A shed/rejected request spends its whole life queued: queue == e2e,
    no prefill/decode, zero scheduler ticks."""
    tr = RequestTrace(2, submitted_at=10.0)
    t = Tracer()
    t._live[2] = tr
    t.finish(tr, outcome="QUEUE_FULL", error_code="QUEUE_FULL", ts=10.25)
    p = tr.phases()
    assert p["queue_ms"] == p["e2e_ms"] == 250.0
    assert p["prefill_ms"] == p["decode_ms"] == 0.0
    assert p["sched_ticks"] == 0
    # the trace is complete: submit + retire bracket the timeline
    names = [e["name"] for e in tr.to_json()["events"]]
    assert names[0] == "submit" and names[-1] == "retire"


def test_first_token_is_idempotent():
    tr = RequestTrace(3, submitted_at=0.0)
    tr.first_token(1.0)
    tr.first_token(2.0)
    assert tr.first_token_at == 1.0
    assert sum(1 for _, n, _ in tr.events if n == "first_token") == 1


# -- unit: ring bounds + slow-request capture --------------------------------

def _finish_one(tracer, tid, *, e2e_s, chunks=3):
    t0 = 1000.0 + tid
    tr = tracer.start(tid, submitted_at=t0)
    tr.admitted(t0 + e2e_s * 0.25, slot=0, tick=tid)
    tr.first_token(t0 + e2e_s * 0.5)
    for i in range(chunks):
        tr.event("chunk", t0 + e2e_s * 0.6 + i * 1e-4, n=1, k=4, occupancy=1)
    tracer.finish(tr, outcome="ok", tick=tid, completion_tokens=chunks,
                  ts=t0 + e2e_s)


def test_finished_ring_is_bounded_fifo():
    tracer = Tracer(capacity=4)
    for tid in range(10):
        _finish_one(tracer, tid, e2e_s=0.01)
    st = tracer.snapshot_stats()
    assert st["finished"] == 4 and st["live"] == 0
    assert st["dropped"] == 6
    assert tracer.get(0) is None          # oldest evicted
    assert tracer.get(9) is not None      # newest retained


def test_slow_request_capture_compacts_fast_traces():
    """Under ring pressure, requests below slow_trace_ms lose per-chunk
    detail but keep their lifecycle skeleton; slow ones keep everything."""
    tracer = Tracer(capacity=2, slow_trace_ms=50.0)
    _finish_one(tracer, 0, e2e_s=0.001)           # fills ring (no pressure)
    _finish_one(tracer, 1, e2e_s=0.001)
    _finish_one(tracer, 2, e2e_s=0.001)           # fast, under pressure
    _finish_one(tracer, 3, e2e_s=0.200)           # slow, under pressure
    fast, slow = tracer.get(2), tracer.get(3)
    assert fast["compacted"] is True
    fast_names = {e["name"] for e in fast["events"]}
    assert "chunk" not in fast_names
    assert {"submit", "admit", "first_token", "retire"} <= fast_names
    # phases survive compaction (they live on the trace, not the events)
    assert fast["phases"]["e2e_ms"] == 1.0
    assert slow["compacted"] is False
    assert any(e["name"] == "chunk" for e in slow["events"])
    assert tracer.snapshot_stats()["compacted"] == 1   # only the fast one


def test_sync_trace_ids_do_not_collide_with_scheduler_ids():
    tracer = Tracer()
    assert tracer.next_id() >= (1 << 30)
    assert tracer.next_id() > (1 << 30)


# -- unit: Chrome export schema ----------------------------------------------

def _validate_chrome_events(events):
    """The subset of the Chrome trace-event schema the export uses."""
    assert isinstance(events, list) and events
    json.dumps(events)                     # must be JSON-serializable
    for ev in events:
        assert ev["ph"] in ("X", "C", "M", "i"), ev
        assert isinstance(ev["pid"], int)
        assert isinstance(ev["tid"], int)
        assert isinstance(ev["name"], str) and ev["name"]
        if ev["ph"] == "X":
            assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
            assert isinstance(ev["dur"], (int, float)) and ev["dur"] > 0
        elif ev["ph"] == "C":
            assert isinstance(ev["ts"], (int, float))
            assert ev["args"], "counter events need a value in args"
        elif ev["ph"] == "i":
            assert ev["s"] in ("t", "p", "g")


def test_chrome_export_schema_unit():
    tracer = Tracer(model="m")
    t = now()
    tracer.tick(1, t, t + 0.002, k=4, active=2, emitted=8,
                kv_blocks_in_use=5, prefix_cached_pages=3)
    _finish_one(tracer, 7, e2e_s=0.05)
    events = tracer.to_chrome(pid=3, process_name="demo")
    _validate_chrome_events(events)
    assert all(ev["pid"] == 3 for ev in events)
    by_ph = {ph: [e for e in events if e["ph"] == ph]
             for ph in ("M", "X", "C")}
    assert {e["name"] for e in by_ph["C"]} == {"kv_pool_blocks_in_use",
                                               "prefix_cache_pages"}
    # metadata names the process and the lanes
    meta = {(e["name"], e["tid"]): e["args"]["name"] for e in by_ph["M"]}
    assert meta[("process_name", 0)] == "demo"
    assert meta[("thread_name", 1)] == "queue"
    assert meta[("thread_name", 1000)] == "slot 0"
    # the request renders as queue -> prefill -> decode complete spans
    cats = [e["cat"] for e in by_ph["X"] if e["cat"] != "scheduler"]
    assert cats == ["queue", "prefill", "decode"]


# -- scheduler integration ---------------------------------------------------

@pytest.fixture(scope="module")
def small_engine():
    cfg = CONFIGS["max-sentiment"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return GenerationEngine(model, params, max_batch=3, max_seq=64)


def test_scheduler_traces_are_complete(small_engine):
    tracer = Tracer(capacity=64)
    sched = ContinuousBatchingScheduler(small_engine, tracer=tracer)
    reqs = [sched.submit([1 + i], max_new_tokens=4) for i in range(6)]
    sched.run()
    for r in reqs:
        tj = tracer.get(r.id)
        assert tj is not None and tj["outcome"] == "ok"
        assert tj["completion_tokens"] == len(r.output) == 4
        p = tj["phases"]
        assert p["queue_ms"] + p["prefill_ms"] + p["decode_ms"] \
            == pytest.approx(p["e2e_ms"], abs=0.005)
        assert p["sched_ticks"] >= 1
        names = [e["name"] for e in tj["events"]]
        assert names[0] == "submit" and names[-1] == "retire"
        assert "admit" in names and "first_token" in names
        assert any(e["name"] == "chunk" for e in tj["events"])
        # cold admission on a non-paged engine: no hits, no pages
        assert tj["admission"] == {"prompt_tokens": 1,
                                   "cached_hit_tokens": 0,
                                   "pages_allocated": 0, "cow": False}
    # tick lanes recorded and the whole export validates
    _validate_chrome_events(tracer.to_chrome())
    assert any(e["cat"] == "scheduler" for e in tracer.to_chrome()
               if e["ph"] == "X")


def test_tracing_does_not_change_tokens(small_engine):
    """Token identity with tracing on vs off — the zero-new-host-syncs
    claim, observed from the outside."""
    def run(tracer):
        sched = ContinuousBatchingScheduler(small_engine, seed=0,
                                            tracer=tracer)
        reqs = [sched.submit([i + 1, i + 2], max_new_tokens=5)
                for i in range(5)]
        sched.run()
        return [r.output for r in reqs]

    assert run(None) == run(Tracer())


def test_cancelled_request_trace_is_complete(small_engine):
    tracer = Tracer()
    sched = ContinuousBatchingScheduler(small_engine, tracer=tracer)
    keep = sched.submit([1], max_new_tokens=3)
    dead = sched.submit([2], max_new_tokens=3)
    assert sched.cancel(dead.id)
    sched.run()
    tj = tracer.get(dead.id)
    assert tj is not None and tj["outcome"] == "CANCELLED"
    assert tj["error_code"] == "CANCELLED"
    names = [e["name"] for e in tj["events"]]
    assert "cancel" in names and names[-1] == "retire"
    assert tracer.get(keep.id)["outcome"] == "ok"


def test_shed_request_trace_is_complete(small_engine):
    """Admission rejection happens on the submitting thread, before the
    decode loop — the trace must still finish with the rejection code."""
    tracer = Tracer()
    ctl = AdmissionController(QoSConfig(max_queue=1))
    sched = ContinuousBatchingScheduler(small_engine, admission=ctl,
                                        tracer=tracer)
    sched.submit([1], max_new_tokens=2)
    with pytest.raises(AdmissionError):
        sched.submit([2], max_new_tokens=2)
    done = [t for t in tracer._done.values()]
    assert len(done) == 1
    tj = done[0].to_json()
    assert tj["outcome"] == "QUEUE_FULL"
    assert [e["name"] for e in tj["events"]][-1] == "retire"
    sched.run()      # drain the admitted request


def test_qos_grant_events_carry_class_and_client(small_engine):
    tracer = Tracer()
    ctl = AdmissionController(QoSConfig())
    sched = ContinuousBatchingScheduler(small_engine, admission=ctl,
                                        tracer=tracer)
    r = sched.submit([1], max_new_tokens=2, priority="interactive",
                     client="alice")
    sched.run()
    tj = tracer.get(r.id)
    assert tj["priority"] == "interactive" and tj["client"] == "alice"
    ev = {e["name"]: e.get("attrs", {}) for e in tj["events"]}
    assert ev["qos_enqueue"]["class"] == "interactive"
    assert ev["qos_grant"]["client"] == "alice"


def test_warm_vs_cold_prefix_admission_distinguishable():
    """The acceptance criterion: a warm (prefix-cache hit) admission and a
    cold prefill are distinguishable from the trace's admission attrs."""
    cfg = CONFIGS["max-sentiment"]
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = GenerationEngine(model, params, max_batch=2, max_seq=64,
                           paged=True, page_size=8, prefix_cache=True)
    prompt = list(range(1, 25))           # 24 tokens = 3 full pages

    tracer = Tracer()
    sched = ContinuousBatchingScheduler(eng, tracer=tracer)
    cold = sched.submit(prompt, max_new_tokens=2)
    sched.run()
    warm = sched.submit(prompt, max_new_tokens=2)   # prefix cached at retire
    sched.run()

    adm_cold = tracer.get(cold.id)["admission"]
    adm_warm = tracer.get(warm.id)["admission"]
    assert adm_cold["cached_hit_tokens"] == 0
    assert adm_warm["cached_hit_tokens"] > 0
    assert adm_warm["pages_allocated"] < adm_cold["pages_allocated"]
    # the prefill span carries the same attrs (what Perfetto shows)
    spans = {s["name"]: s for s in tracer.get(warm.id)["spans"]}
    assert spans["prefill"]["attrs"]["cached_hit_tokens"] \
        == adm_warm["cached_hit_tokens"]
    # tokens are identical warm vs cold (tracing + cache change nothing)
    assert cold.output == warm.output


# -- HTTP surface ------------------------------------------------------------

BUILD_KW = {"max_seq": 64, "max_batch": 4}
SERVICE_KW = {"batch_window_s": 0.02}


@pytest.fixture(scope="module")
def server():
    with MAXServer(build_kw=BUILD_KW, service_kw=SERVICE_KW) as s:
        yield s


def _req(server, method, path, payload=None):
    data = json.dumps(payload).encode() if payload is not None else None
    req = urllib.request.Request(server.url + path, data,
                                 {"Content-Type": "application/json"},
                                 method=method)
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def _run_job(server, model, payload):
    code, sub = _req(server, "POST", f"/v2/model/{model}/jobs",
                     {"input": payload})
    assert code == 202, sub
    job_id = sub["job"]["id"]
    deadline = time.time() + 30
    while time.time() < deadline:
        code, env = _req(server, "GET", f"/v2/jobs/{job_id}")
        if env["job"]["state"] in ("done", "error", "cancelled"):
            return job_id, env["job"]
        time.sleep(0.05)
    raise AssertionError("job did not finish")


def _read_done_usage(server, job_id):
    """Replay a finished job's SSE buffer and return the terminal event's
    usage record."""
    req = urllib.request.Request(
        server.url + f"/v2/jobs/{job_id}/events?from_seq=0")
    with urllib.request.urlopen(req, timeout=10) as r:
        body = r.read().decode()
    for block in body.split("\n\n"):
        lines = dict(ln.split(": ", 1) for ln in block.splitlines()
                     if ": " in ln)
        if lines.get("event") == "done":
            return json.loads(lines["data"])["usage"]
    raise AssertionError(f"no done event in stream: {body!r}")


def test_v2_done_usage_reports_phase_latencies(server):
    job_id, job = _run_job(server, "qwen3-4b",
                           {"text": "hello", "max_new_tokens": 4})
    assert job["state"] == "done"
    u = _read_done_usage(server, job_id)
    for k in ("queue_ms", "prefill_ms", "decode_ms", "sched_ticks",
              "latency_ms"):
        assert k in u, f"usage missing {k}"
    # phase sum ~= e2e (within a scheduler tick of bookkeeping skew)
    assert u["queue_ms"] + u["prefill_ms"] + u["decode_ms"] \
        == pytest.approx(u["latency_ms"], abs=25.0)
    assert u["sched_ticks"] >= 1


def test_job_trace_endpoint(server):
    job_id, job = _run_job(server, "qwen3-4b",
                           {"text": "trace me", "max_new_tokens": 4})
    assert job["state"] == "done"
    code, env = _req(server, "GET", f"/v2/jobs/{job_id}/trace")
    assert code == 200 and env["status"] == "ok"
    tr = env["trace"]
    assert tr["outcome"] == "ok"
    assert [s["name"] for s in tr["spans"]] == ["queue", "prefill", "decode"]
    names = [e["name"] for e in tr["events"]]
    assert names[0] == "submit" and names[-1] == "retire"
    p = tr["phases"]
    assert p["queue_ms"] + p["prefill_ms"] + p["decode_ms"] \
        == pytest.approx(p["e2e_ms"], abs=0.005)


def test_trace_export_endpoint(server):
    # ensure at least one traced request exists
    _run_job(server, "qwen3-4b", {"text": "export", "max_new_tokens": 3})
    code, body = _req(server, "GET", "/v2/trace/export")
    assert code == 200
    assert body["displayTimeUnit"] == "ms"
    _validate_chrome_events(body["traceEvents"])
    cats = {e.get("cat") for e in body["traceEvents"] if e["ph"] == "X"}
    assert {"scheduler", "queue", "prefill", "decode"} <= cats


def test_trace_of_unknown_job_is_404(server):
    code, env = _req(server, "GET", "/v2/jobs/nope/trace")
    assert code == 404 and env["error"]["code"] == "JOB_NOT_FOUND"


def test_stats_reports_tracing(server):
    code, env = _req(server, "GET", "/v2/model/qwen3-4b/stats")
    assert code == 200
    tr = env["service"]["tracing"]
    assert tr["enabled"] is True and tr["capacity"] >= 1


def test_deploy_trace_knob_validation(server):
    bad = [{"trace": "yes"}, {"trace_buffer": 0}, {"trace_buffer": True},
           {"slow_trace_ms": -5}, {"trace": False, "trace_buffer": 16},
           {"trace": False, "slow_trace_ms": 10}]
    for body in bad:
        code, env = _req(server, "POST", "/v2/model/max-sentiment/deploy",
                         body)
        assert code == 400 and env["error"]["code"] == "INVALID_INPUT", body


def test_deploy_trace_disabled_then_enabled(server):
    model = "max-sentiment"
    code, env = _req(server, "POST", f"/v2/model/{model}/deploy",
                     {"trace": False})
    assert code == 200, env
    job_id, job = _run_job(server, model, ["fine"])
    assert job["state"] == "done"
    code, env = _req(server, "GET", f"/v2/jobs/{job_id}/trace")
    assert code == 404 and env["error"]["code"] == "TRACE_NOT_FOUND"
    assert "disabled" in env["error"]["message"]

    # redeploy with tracing on: sync-service requests get traces too
    code, env = _req(server, "POST", f"/v2/model/{model}/deploy",
                     {"trace": True, "trace_buffer": 8,
                      "slow_trace_ms": 1000})
    assert code == 200, env
    job_id, job = _run_job(server, model, ["good stuff"])
    assert job["state"] == "done"
    code, env = _req(server, "GET", f"/v2/jobs/{job_id}/trace")
    assert code == 200, env
    tr = env["trace"]
    assert tr["outcome"] == "ok"
    assert tr["trace_id"] >= (1 << 30)     # sync-service id space
    p = tr["phases"]
    assert p["queue_ms"] + p["prefill_ms"] + p["decode_ms"] \
        == pytest.approx(p["e2e_ms"], abs=0.005)


def test_phase_histograms_in_metrics(server):
    _run_job(server, "qwen3-4b", {"text": "hist", "max_new_tokens": 3})
    code, m = _req(server, "GET", "/v2/metrics")
    assert code == 200
    hists = m["metrics"]["histograms"] if "metrics" in m else \
        m["histograms"]
    joined = " ".join(hists)
    for fam in ("max_phase_queue_seconds", "max_phase_prefill_seconds",
                "max_decode_per_token_seconds", "max_e2e_latency_seconds"):
        assert fam in joined, f"{fam} missing from {sorted(hists)[:8]}..."


def test_trace_export_carries_tick_parts_and_a_clock_pair(server):
    _run_job(server, "qwen3-4b", {"text": "parts", "max_new_tokens": 3})
    code, body = _req(server, "GET", "/v2/trace/export")
    assert code == 200
    clocks = body["metadata"]["clocks"]
    assert set(clocks) == {"serving_clock_s", "profiler_clock_ns"}
    names = {e["name"] for e in body["traceEvents"] if e["ph"] == "X"}
    assert {"max.sched.admit", "max.sched.dispatch", "max.sched.sync",
            "max.sched.deliver"} <= names
    # the pair is read now: the export's last tick lies before it
    ticks = [e for e in body["traceEvents"]
             if e["ph"] == "X" and e["name"].startswith("tick ")]
    assert max(e["ts"] + e["dur"] for e in ticks) <= \
        clocks["serving_clock_s"] * 1e6 + 1.0


def test_stats_report_the_tick_split(server):
    _run_job(server, "qwen3-4b", {"text": "split", "max_new_tokens": 3})
    code, env = _req(server, "GET", "/v2/model/qwen3-4b/stats")
    assert code == 200
    s = env["service"]["scheduler"]
    assert s["ticks"] >= 1 and s["inserts"] >= 1
    assert s["host_s"] + s["sync_wait_s"] == pytest.approx(s["wall_s"],
                                                           abs=2e-6)
    assert 0 <= s["host_cpu_s"] <= s["host_s"] + 1e-6
    assert s["insert_host_s"] > 0 and s["kv_tokens_sum"] > 0


def test_metrics_report_gc_pauses(server):
    import gc
    gc.collect()          # a service is live: the collection is counted
    code, m = _req(server, "GET", "/v2/metrics")
    assert code == 200
    gauges = m["metrics"]["gauges"]
    pauses = {k: v for k, v in gauges.items()
              if k.startswith("max_gc_pause_seconds_total")}
    assert len(pauses) == 3
    assert pauses['max_gc_pause_seconds_total{generation="2"}'] > 0
