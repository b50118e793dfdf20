"""Fleet-scale serving: mesh-slice parsing, replica groups, the
replica-aware front door, drain-and-migrate scale-down, and per-replica
fault isolation."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

import repro.core.assets  # noqa: F401 — populates EXCHANGE
from repro.core import EXCHANGE, MAXServer
from repro.core.deployment import DeploymentManager
from repro.core.fleet import ReplicaSet
from repro.serving.replica import (
    MeshSliceError, live_device_count, parse_mesh_slice,
)

BUILD_KW = {"max_seq": 64, "max_batch": 4}
MODEL = "qwen3-4b"


def _wait_jobs(svc, jobs, timeout_s=30.0):
    deadline = time.monotonic() + timeout_s
    terminal = ("done", "error", "cancelled")
    while time.monotonic() < deadline:
        if all(svc.get_job(j.id).state in terminal for j in jobs):
            return [svc.get_job(j.id) for j in jobs]
        time.sleep(0.02)
    raise AssertionError(
        f"jobs not terminal: {[svc.get_job(j.id).state for j in jobs]}")


# -- mesh-slice parser -------------------------------------------------------

def test_parse_auto_partitions_all_devices():
    p = parse_mesh_slice(None, replicas=3, device_count=8)
    assert p.replicas == 3 and not p.oversubscribed
    chips = [c for sl in p.slices for c in sl.chips]
    assert sorted(chips) == list(range(8))          # disjoint, exhaustive
    assert {len(sl.chips) for sl in p.slices} == {3, 2}   # near-even

def test_parse_auto_oversubscribes_single_device():
    p = parse_mesh_slice("auto", replicas=4, device_count=1)
    assert p.replicas == 4 and p.oversubscribed
    assert all(sl.chips == (0,) for sl in p.slices)


def test_parse_physical_ranges_per_replica():
    p = parse_mesh_slice("devices:0-3,devices:4-7", replicas=2,
                         device_count=8)
    assert [sl.chips for sl in p.slices] == [tuple(range(4)),
                                             tuple(range(4, 8))]
    assert [sl.label for sl in p.slices] == ["devices:0-3", "devices:4-7"]


def test_parse_single_atom_partitioned_across_replicas():
    p = parse_mesh_slice("devices:0-7", replicas=2, device_count=8)
    assert [sl.chips for sl in p.slices] == [tuple(range(4)),
                                             tuple(range(4, 8))]


def test_parse_topology_atom_is_logical():
    p = parse_mesh_slice("pod0/rows0-7", replicas=2)
    assert all(sl.logical for sl in p.slices)
    # the atom spans 8 rows x 16 chips; each replica gets a disjoint half
    chips = [set(sl.chips) for sl in p.slices]
    assert sum(len(c) for c in chips) == 8 * 16
    assert len(chips[0] & chips[1]) == 0
    assert len(chips[0]) == len(chips[1]) == 4 * 16
    # logical slices fold onto however many devices are live
    devs = list(range(live_device_count()))
    assert p.slices[0].bind(devs)[0] in devs


@pytest.mark.parametrize("spec", [
    "devices:",                 # empty range
    "devices:3-1",              # inverted range
    "devices:0;4",              # bad separator
    "pod9/rows0-1",             # pod out of topology
    "pod0/rows12-99",           # rows out of topology
    "rows0-3",                  # missing pod
    "devices:0-3,",             # trailing empty atom
    "devices:0-1,devices:2-3,devices:4-5",   # 3 atoms for 2 replicas
    "devices:0-3,pod0/rows0-1",              # physical + topology mix
])
def test_parse_rejects_malformed_specs(spec):
    with pytest.raises(MeshSliceError):
        parse_mesh_slice(spec, replicas=2, device_count=8)


def test_parse_rejects_overlap_and_out_of_range():
    with pytest.raises(MeshSliceError, match="overlap"):
        parse_mesh_slice("devices:0-4,devices:4-7", replicas=2,
                         device_count=8)
    with pytest.raises(MeshSliceError, match="device"):
        parse_mesh_slice("devices:0-15", replicas=2, device_count=8)


# -- replica set: dispatch, affinity, failover -------------------------------

@pytest.fixture(scope="module")
def fleet():
    asset = EXCHANGE.get(MODEL)
    rs = ReplicaSet(lambda: asset.build(**BUILD_KW), replicas=2,
                    batch_window_s=0.01)
    yield rs
    rs.close()


def test_fleet_serves_and_aggregates(fleet):
    env = fleet.predict({"text": "hello fleet", "max_new_tokens": 4})
    assert env["status"] == "ok"
    s = fleet.stats()
    assert s["kind"] == "fleet" and s["replicas"] == 2
    assert set(s["per_replica"]) == {"r0", "r1"}
    assert s["submitted"] == sum(
        r["submitted"] for r in s["per_replica"].values())
    h = fleet.health()
    assert h["ready"] and h["fleet"]["size"] == 2
    assert set(h["replicas"]) == {"r0", "r1"}


def test_fleet_session_affinity_and_spread(fleet):
    base = {n: r["submitted"] for n, r in
            fleet.stats()["per_replica"].items()}
    for _ in range(6):
        env = fleet.predict({"text": "affine", "max_new_tokens": 2},
                            qos={"client": "alice"})
        assert env["status"] == "ok"
    after = {n: r["submitted"] for n, r in
             fleet.stats()["per_replica"].items()}
    grew = [n for n in after if after[n] > base[n]]
    assert len(grew) == 1           # all six landed on alice's home replica
    # distinct clients spread: rendezvous hashing is uniform enough that
    # 8 distinct names never all collapse onto one replica
    for i in range(8):
        fleet.predict({"text": "spread", "max_new_tokens": 2},
                      qos={"client": f"client-{i}"})
    final = {n: r["submitted"] for n, r in
             fleet.stats()["per_replica"].items()}
    assert all(final[n] > after[n] for n in final)
    assert fleet.stats()["dispatch"]["affine"] >= 14


def test_fleet_streams_and_jobs_route_to_owner(fleet):
    events = list(fleet.predict_stream(
        {"text": "stream me", "max_new_tokens": 3}))
    assert events[-1].event == "done"
    assert sum(1 for e in events if e.event == "token") >= 1
    job = fleet.submit_job({"text": "job me", "max_new_tokens": 3})
    (done,) = _wait_jobs(fleet, [job])
    assert done.state == "done"
    # job polling routes through the owning replica's record
    assert fleet.get_job(job.id).result["status"] == "ok"
    assert fleet.delete_job(job.id)
    with pytest.raises(KeyError):
        fleet.get_job(job.id)


def test_fleet_batch_spreads_over_replicas(fleet):
    envs = fleet.predict_batch(
        [{"text": f"b{i}", "max_new_tokens": 2} for i in range(6)])
    assert all(e["status"] == "ok" for e in envs)


def test_replica_state_committed_to_its_slice_device():
    """Each replica's weights and KV cache are committed to the device its
    slice binds, so its programs run there (also after an engine reset)."""
    import jax
    asset = EXCHANGE.get(MODEL)
    rs = ReplicaSet(lambda: asset.build(**BUILD_KW), replicas=2,
                    batch_window_s=0.01)
    try:
        env = rs.predict({"text": "placed", "max_new_tokens": 3})
        assert env["status"] == "ok"
        devices = jax.devices()
        for rep in rs._replicas:
            want = rep.slice_.bind(devices)[0]
            eng = rep.service.wrapper.engine
            eng.reset()
            for leaf in jax.tree.leaves((eng.params, eng._cache,
                                         eng._next_tok)):
                assert leaf.committed and leaf.devices() == {want}
    finally:
        rs.close()


def test_unbindable_slice_raises_instead_of_default_device():
    """A slice that cannot be bound fails the build; the replica is never
    built on some other device."""
    from repro.serving.replica import MeshPlacement, ReplicaSlice
    asset = EXCHANGE.get(MODEL)
    bad = MeshPlacement(spec="devices:4096", slices=(
        ReplicaSlice(label="devices:4096", chips=(4096,)),))
    with pytest.raises(IndexError):
        ReplicaSet(lambda: asset.build(**BUILD_KW), replicas=1,
                   placement=bad)


def test_bench_fleet_refuses_when_parent_holds_an_accelerator(monkeypatch):
    """The fleet harness child would run on the CPU while the parent holds
    the chip: it refuses instead of reporting CPU numbers."""
    import importlib.util
    import pathlib

    import jax
    path = pathlib.Path(__file__).resolve().parents[1] / "benchmarks/run.py"
    spec = importlib.util.spec_from_file_location("bench_run", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(SystemExit, match="tpu"):
        bench.bench_fleet(out_path="/nonexistent/BENCH.json", quick=True)


# -- scaling: up, and drain-without-loss down --------------------------------

def test_scale_up_then_drain_down_loses_nothing():
    asset = EXCHANGE.get(MODEL)
    rs = ReplicaSet(lambda: asset.build(**BUILD_KW), replicas=1,
                    batch_window_s=0.01)
    try:
        rs.scale(3)
        assert rs.size == 3 and rs.stats()["replicas"] == 3
        # land work on every replica (distinct clients), then scale down
        # while it is still in flight: accepted work must all terminate,
        # migrated zero-delivery work replays token-identically
        jobs = [rs.submit_job({"text": f"drain {i}", "max_new_tokens": 6},
                              qos={"client": f"c{i}"})
                for i in range(9)]
        rs.scale(1, drain_timeout_s=0.05)   # force the migrate path
        assert rs.size == 1
        done = _wait_jobs(rs, jobs)
        assert all(j.state == "done" for j in done), \
            [(j.state, j.error) for j in done]
        ref = rs.predict({"text": "drain 0", "max_new_tokens": 6})
        assert (done[0].result["predictions"]
                == ref["predictions"])          # greedy replay, same tokens
        s = rs.stats()
        assert s["scale_events"] == 2
        assert list(s["per_replica"]) == ["r0"]
    finally:
        rs.close()


def test_deploy_manager_scales_fleet_in_place():
    mgr = DeploymentManager()
    dep = mgr.deploy(MODEL, replicas=2, **BUILD_KW)
    try:
        assert dep.service.kind == "fleet" and dep.service.size == 2
        # redeploy with a different count scales the SAME service
        dep2 = mgr.deploy(MODEL, replicas=3, **BUILD_KW)
        assert dep2 is dep and dep.service.size == 3
        dep3 = mgr.deploy(MODEL, replicas=1, **BUILD_KW)
        assert dep3 is dep and dep.service.size == 1
        assert mgr.health()[MODEL]["replicas"] == 1
    finally:
        mgr.undeploy(MODEL)


def test_replicas_1_uses_classic_single_service():
    mgr = DeploymentManager()
    dep = mgr.deploy(MODEL, replicas=1, **BUILD_KW)
    try:
        assert dep.service.kind == "batched"    # not a fleet-of-one
    finally:
        mgr.undeploy(MODEL)


# -- fault isolation ---------------------------------------------------------

def test_one_replica_fault_leaves_survivors_token_identical():
    asset = EXCHANGE.get(MODEL)
    clean = ReplicaSet(lambda: asset.build(**BUILD_KW), replicas=1,
                       batch_window_s=0.01)
    ref = clean.predict({"text": "isolate", "max_new_tokens": 6})
    clean.close()
    assert ref["status"] == "ok"
    # replica 0 armed (every chunk faults until max_faults), replica 1 clean
    rs = ReplicaSet(
        lambda: asset.build(**BUILD_KW), replicas=2,
        batch_window_s=0.01,
        faults=[{"chunk_rate": 1.0, "seed": 7, "max_faults": 3}, None])
    try:
        envs = [rs.predict({"text": "isolate", "max_new_tokens": 6},
                           qos={"client": f"iso-{i}"}) for i in range(8)]
        assert all(e["status"] == "ok" for e in envs)
        # token identity: faulted-and-retried and clean-replica runs all
        # reproduce the reference generation exactly (greedy decode)
        assert all(e["predictions"] == ref["predictions"] for e in envs)
        s = rs.stats()
        per = s["per_replica"]
        assert per["r0"]["robustness"]["fault_injection"] is not None
        assert per["r1"]["robustness"]["fault_injection"] is None
        assert per["r0"]["robustness"]["engine_faults"] >= 1
        assert per["r1"]["robustness"]["engine_faults"] == 0
        assert s["robustness"]["engine_faults"] >= 1    # aggregate view
        # the fleet stayed ready the whole time; per-replica health shows
        # where the damage landed
        h = rs.health()
        assert h["ready"] and h["fleet"]["ready_replicas"] == 2
        assert h["replicas"]["r0"]["engine_faults"] >= 1
        assert h["replicas"]["r1"]["engine_faults"] == 0
    finally:
        rs.close()


def test_replica_kill_is_contained_and_visible():
    asset = EXCHANGE.get(MODEL)
    rs = ReplicaSet(
        lambda: asset.build(**BUILD_KW), replicas=2,
        batch_window_s=0.01, watchdog_interval_s=0.02,
        faults=[{"script": [{"tick": 1, "site": "kill"}]}, None])
    try:
        envs = [rs.predict({"text": f"kill {i}", "max_new_tokens": 4},
                           qos={"client": f"k-{i}"}) for i in range(6)]
        assert all(e["status"] == "ok" for e in envs)   # retries absorb it
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            per = rs.stats()["per_replica"]
            if per["r0"]["robustness"]["worker_restarts"] >= 1:
                break
            time.sleep(0.05)
        assert per["r0"]["robustness"]["worker_restarts"] >= 1
        assert per["r1"]["robustness"]["worker_restarts"] == 0
        assert rs.health()["ready"]     # fleet never went down
    finally:
        rs.close()


# -- HTTP surface ------------------------------------------------------------

@pytest.fixture(scope="module")
def server():
    with MAXServer(build_kw=BUILD_KW, auto_deploy=False) as s:
        yield s


def _req(server, method, path, payload=None, headers=None):
    data = json.dumps(payload).encode() if payload is not None else None
    hdrs = {"Content-Type": "application/json"}
    hdrs.update(headers or {})
    req = urllib.request.Request(server.url + path, data, hdrs,
                                 method=method)
    try:
        with urllib.request.urlopen(req) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def test_v2_deploy_fleet_and_serve(server):
    code, env = _req(server, "POST", f"/v2/model/{MODEL}/deploy",
                     {"replicas": 2})
    assert code == 200 and env["service"] == "fleet" and env["replicas"] == 2
    code, env = _req(server, "POST", f"/v2/model/{MODEL}/predict",
                     {"input": {"text": "via http", "max_new_tokens": 3}})
    assert code == 200 and env["status"] == "ok"
    # affinity via the X-MAX-Client header
    for _ in range(3):
        code, env = _req(server, "POST", f"/v2/model/{MODEL}/predict",
                         {"input": {"text": "hdr", "max_new_tokens": 2}},
                         headers={"X-MAX-Client": "header-client"})
        assert code == 200 and env["status"] == "ok"
    code, stats = _req(server, "GET", f"/v2/model/{MODEL}/stats")
    assert code == 200
    svc = stats["service"]
    assert svc["kind"] == "fleet" and set(svc["per_replica"]) == {"r0", "r1"}
    assert svc["dispatch"]["affine"] >= 3
    # health aggregates per replica
    code, h = _req(server, "GET", "/v2/health")
    assert code == 200 and h["deployments"][MODEL]["fleet"]["size"] == 2
    assert set(h["deployments"][MODEL]["replicas"]) == {"r0", "r1"}
    # metrics carry the replica dimension
    code, m = _req(server, "GET", "/v2/metrics")
    assert code == 200
    labelled = [k for k in m["metrics"]["counters"]
                if 'replica="r' in k]
    assert labelled, "no replica-labelled series in /v2/metrics"


def test_v2_trace_export_has_one_process_per_replica(server):
    _req(server, "POST", f"/v2/model/{MODEL}/predict",
         {"input": {"text": "traced", "max_new_tokens": 2}})
    code, doc = _req(server, "GET", "/v2/trace/export")
    assert code == 200
    names = {e["args"]["name"] for e in doc["traceEvents"]
             if e.get("ph") == "M" and e.get("name") == "process_name"}
    assert f"{MODEL}/r0" in names and f"{MODEL}/r1" in names


def test_v2_invalid_mesh_slice_is_structured_400(server):
    for bad in ("devices:9-4", "devices:0-1,devices:1-2", "nonsense!!"):
        code, env = _req(server, "POST", f"/v2/model/{MODEL}/deploy",
                         {"replicas": 2, "mesh_slice": bad})
        assert code == 400, (bad, env)
        assert env["error"]["code"] == "INVALID_MESH_SLICE"
    # the running fleet survived every rejected deploy
    code, h = _req(server, "GET", "/v2/health")
    assert code == 200 and h["deployments"][MODEL]["fleet"]["size"] == 2


def test_v2_bad_replicas_and_fault_list_validation(server):
    code, env = _req(server, "POST", f"/v2/model/{MODEL}/deploy",
                     {"replicas": 0})
    assert code == 400 and env["error"]["code"] == "INVALID_INPUT"
    code, env = _req(server, "POST", f"/v2/model/{MODEL}/deploy",
                     {"replicas": 2, "faults": [{"wat": 1}, None]})
    assert code == 400 and env["error"]["code"] == "INVALID_INPUT"
    code, env = _req(server, "POST", f"/v2/model/{MODEL}/deploy",
                     {"faults": [{"chunk_rate": 0.5}]})
    assert code == 400 and env["error"]["code"] == "INVALID_INPUT"


def test_v2_scale_down_via_redeploy(server):
    code, env = _req(server, "POST", f"/v2/model/{MODEL}/deploy",
                     {"replicas": 1})
    assert code == 200 and env["replicas"] == 1 and env["service"] == "fleet"
    code, env = _req(server, "POST", f"/v2/model/{MODEL}/predict",
                     {"input": {"text": "post scale", "max_new_tokens": 2}})
    assert code == 200 and env["status"] == "ok"
