"""The program's spans read back from a profiler trace: a live CPU trace
of a toy ``BatchedService`` (the tick's parts nest in it and tile it, the
program's spans and the benchmark's window share one clock, a collection
shows as ``max.gc``), the readers of the metrics they feed, on that trace
and on hand-made runs, and the split of the device's idle time by span."""

import gc
import shutil
import sys
import threading
from pathlib import Path

import jax
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from pbench import spans, spec  # noqa: E402
from pbench import trace as trace_mod  # noqa: E402
from pbench.run_cell import RunData  # noqa: E402

MS = 1_000_000
NEW_METRICS = ("sched_host_ms.tok_s", "sched_cpu_share.tok_s",
               "gc_pause_ms_per_s.tok_s", "kv_in_use_share.tok_s",
               "insert_host_ms.ttft", "decode_step_ms.tok_s",
               "decode_step_ms.tpot")
PARTS = ("max.sched.admit", "max.sched.dispatch", "max.sched.sync",
         "max.sched.deliver")


def read(name, run):
    return spec.load_reader(BENCH, name)(run)


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """The directory of a traced window in which a toy BatchedService
    serves six requests at once, then collects garbage once."""
    import repro.core.assets  # noqa: F401
    from repro.core import BatchedService, EXCHANGE
    d = tmp_path_factory.mktemp("trace")
    wrapper = EXCHANGE.get("qwen3-4b").build(max_seq=64, max_batch=4)
    svc = BatchedService(wrapper, batch_window_s=0.0)
    try:
        svc.predict({"text": "warm", "max_new_tokens": 8})
        shutil.rmtree(d, ignore_errors=True)
        jax.profiler.start_trace(str(d))
        with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
            calls = [threading.Thread(
                target=svc.predict,
                args=({"text": f"hello {i}", "max_new_tokens": 12},))
                for i in range(6)]
            for c in calls:
                c.start()
            for c in calls:
                c.join()
            gc.collect()
        jax.profiler.stop_trace()
    finally:
        svc.close()
    return d


def _kids(all_spans, tick):
    s, e = tick[1], tick[1] + tick[2]
    return [sp for sp in all_spans
            if sp[0] in PARTS and s <= sp[1] and sp[1] + sp[2] <= e]


def test_sched_spans_nest_in_a_tick(recorded):
    got = spans.extract(recorded)
    ticks = [sp for sp in got["spans"] if sp[0] == spans.TICK]
    parts = [sp for sp in got["spans"] if sp[0] in PARTS]
    assert ticks and len(parts) == 4 * len(ticks)
    for tick in ticks:
        assert [sp[0] for sp in _kids(got["spans"], tick)] == list(PARTS)


def test_tick_parts_cover_the_tick(recorded):
    got = spans.extract(recorded)
    ticks = [sp for sp in got["spans"] if sp[0] == spans.TICK]
    covered = sum(sum(k[2] for k in _kids(got["spans"], t)) for t in ticks)
    assert covered >= 0.99 * sum(t[2] for t in ticks)
    # the tick's counters ride on its span
    assert sum(t[3]["host_s"] + t[3]["sync_s"] for t in ticks) == \
        pytest.approx(sum(t[2] for t in ticks) / 1e9, rel=0.02)
    assert all(0 <= t[3]["cpu_s"] <= t[3]["host_s"] for t in ticks)


def test_program_spans_share_the_window_clock(recorded):
    """Both readers of one file see the same window, and the requests
    served inside it put every tick inside it too."""
    got = spans.extract(recorded)
    tr = trace_mod.extract(str(recorded))
    assert got["window"] == tr["window"]
    w0, w1 = got["window"]
    ticks = [sp for sp in got["spans"] if sp[0] == spans.TICK]
    assert all(w0 <= t[1] and t[1] + t[2] <= w1 for t in ticks)
    assert len(spans.in_window(got, spans.TICK)) == len(ticks)


def test_an_induced_collection_shows_as_max_gc(recorded):
    got = spans.extract(recorded)
    full = [sp for sp in spans.in_window(got, spans.GC)
            if sp[3].get("generation") == 2]
    assert full and all(sp[2] > 0 for sp in full)


def _run(trace=None, sched=None, max_batch=4):
    cell = spec.load_cell("deepseek-67b-s6.batch")
    return RunData(cell=cell, seconds=10.0, t0=100.0, t1=110.0, setup_s=1.0,
                   outcomes=[], sched=sched or {}, max_batch=max_batch,
                   device_kind="TPU v5 lite", trace=trace)


def test_readers_on_the_recorded_trace(recorded, monkeypatch):
    monkeypatch.setattr(spans, "TRACE_DIR", recorded)
    run = _run(trace=trace_mod.extract(str(recorded)))
    assert read("sched_host_ms.tok_s", run) > 0
    assert 0 < read("sched_cpu_share.tok_s", run) <= 100
    assert read("gc_pause_ms_per_s.tok_s", run) > 0
    assert 0 < read("kv_in_use_share.tok_s", run) <= 100
    assert read("insert_host_ms.ttft", run) > 0
    # another run's trace on disk is not read
    other = dict(run.trace, window=[0, 1])
    assert read("sched_host_ms.tok_s", _run(trace=other)) is None


def hand_made():
    """A 1 s window: two ticks inside it (one starts before), two
    admissions, 30 ms of collection of which 10 ms outside."""
    w = [0, 1000 * MS]
    tick = "max.sched.tick"
    return {"window": w, "spans": [
        [tick, -20 * MS, 30 * MS, {"host_s": 0.5, "sync_s": 0.01,
                                   "cpu_s": 0.5, "steps": 9,
                                   "kv_tokens": 9}],
        [tick, 100 * MS, 40 * MS, {"host_s": 0.010, "sync_s": 0.030,
                                   "cpu_s": 0.006, "steps": 4,
                                   "kv_tokens": 4 * 1024}],
        [tick, 500 * MS, 40 * MS, {"host_s": 0.020, "sync_s": 0.020,
                                   "cpu_s": 0.010, "steps": 4,
                                   "kv_tokens": 4 * 2048}],
        ["max.engine.prefill_prep", 101 * MS, 2 * MS, {}],
        ["max.engine.prefill_dispatch", 103 * MS, 1 * MS, {}],
        ["max.engine.prefill_prep", 501 * MS, 4 * MS, {}],
        ["max.engine.prefill_dispatch", 505 * MS, 3 * MS, {}],
        ["max.gc", 200 * MS, 20 * MS, {"generation": 0}],
        ["max.gc", 990 * MS, 20 * MS, {"generation": 2}],
    ]}


@pytest.mark.parametrize("name,want", [
    ("sched_host_ms.tok_s", 15.0),              # (10 + 20) / 2 ticks
    ("sched_cpu_share.tok_s", 100 * 16 / 30),
    ("gc_pause_ms_per_s.tok_s", 30.0),          # 20 + 10 ms in 1 s
    ("kv_in_use_share.tok_s", 100 * 3 * 4 * 1024 / (8 * 4 * 2048)),
    ("insert_host_ms.ttft", 5.0),               # (3 + 7) / 2 admissions
])
def test_reader_on_hand_made_spans(name, want, monkeypatch):
    got = hand_made()
    monkeypatch.setattr(spans, "of_run", lambda run: got)
    run = _run(trace={"window": got["window"], "devices": {}, "host": []},
               max_batch=4)
    # the batch cell's max_seq is 2048: the share is over 4 x 2048 per step
    assert run.config["serve"]["max_seq"] == 2048
    assert read(name, run) == pytest.approx(want)


@pytest.mark.parametrize("name", ["decode_step_ms.tok_s",
                                  "decode_step_ms.tpot"])
def test_decode_step_by_the_named_module(name):
    t = {"window": [0, 1000 * MS], "host": [], "devices": {
        "/device:TPU:0": {"ops": [["while.1", 0, 600 * MS]], "modules": [
            ["jit_decode_chunk(12)", 0, 300 * MS],
            ["jit_decode_chunk(12)", 400 * MS, 300 * MS],
            ["jit__prefill_impl(3)", 300 * MS, 100 * MS]]}}}
    sched = {"before": {"decode_steps": 10, "occupancy_sum": 0},
             "after": {"decode_steps": 40, "occupancy_sum": 0}}
    assert read(name, _run(trace=t, sched=sched)) == pytest.approx(20.0)
    # a program whose chunk is not named (jit__unknown) reads nothing
    t["devices"]["/device:TPU:0"]["modules"][0][0] = "jit__unknown(12)"
    t["devices"]["/device:TPU:0"]["modules"][1][0] = "jit__unknown(12)"
    assert read(name, _run(trace=t, sched=sched)) is None


@pytest.mark.parametrize("name", NEW_METRICS)
def test_readers_read_nothing_without_the_program_spans(name, monkeypatch,
                                                        tmp_path):
    """An untraced run, and a traced one of a program that opens no
    ``max.*`` span and names no chunk (as before this change)."""
    sched = {"before": {"decode_steps": 0, "occupancy_sum": 0},
             "after": {"decode_steps": 8, "occupancy_sum": 8}}
    assert read(name, _run(sched=sched)) is None
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("scheduler.tick"):
            jax.numpy.ones(4).block_until_ready()
    jax.profiler.stop_trace()
    run = _run(trace=trace_mod.extract(str(tmp_path)), sched=sched)
    assert read(name, run) is None


def test_idle_split_by_the_innermost_span():
    """Device busy [0,2] and [6,10] ms of a 10 ms window; the host is in a
    tick [1,9] whose sync [3,5] holds a collection [3.5,4.5]."""
    tr = {"window": [0, 10 * MS], "host": [["scheduler.tick", 0, 10 * MS]],
          "devices": {"/device:TPU:0": {
              "ops": [["a", 0, 2 * MS], ["b", 6 * MS, 4 * MS]],
              "modules": []}}}
    got = {"window": tr["window"], "spans": [
        ["max.sched.tick", 1 * MS, 8 * MS, {}],
        ["max.sched.sync", 3 * MS, 2 * MS, {}],
        ["max.gc", 3 * MS + MS // 2, 1 * MS, {}]]}
    split = spans.idle_by_span(tr, got)
    # the gap [2,6]: tick [2,3] + [5,6], sync [3,3.5] + [4.5,5], gc
    assert split == pytest.approx({"max.sched.tick": 0.002,
                                   "max.sched.sync": 0.001,
                                   "max.gc": 0.001, "idle": 0.0})
    assert spans.idle_gaps(tr, got) == [["max.gc", pytest.approx(0.004)]]
