"""The reduction from a profiler trace to device numbers: on a small
trace recorded on a TPU v5e (kept as a fixture), on a hand-made one
whose answers are known, and on a trace this process records."""

import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from pbench import trace  # noqa: E402

FIXTURES = Path(__file__).resolve().parent / "fixtures"
MS = 1_000_000


def hand_made():
    """10 ms window; ops cover [1,3] u [2,4] u [6,8] ms, a fusion nested
    in the while, and one op that sticks out past the end; host spans
    cover the gaps."""
    return {
        "window": [0, 10 * MS],
        "devices": {"/device:TPU:0": {
            "ops": [["fusion.1", 1 * MS, 2 * MS], ["fusion.2", 3 * MS, 1 * MS],
                    ["while.3", 6 * MS, 2 * MS], ["fusion.4", 6 * MS, 1 * MS],
                    ["copy", 9 * MS, 3 * MS]],
            "modules": [["jit__prefill_impl(7)", 1 * MS, 3 * MS],
                        ["jit__unknown(9)", 6 * MS, 2 * MS]]}},
        "host": [["scheduler.tick", 0, 10 * MS],
                 ["engine.prefill", 4 * MS, 2 * MS]],
    }


def test_busy_is_the_union_clipped_to_the_window():
    t = hand_made()
    # [1,4] + [6,8] + [9,10] = 6 ms
    assert trace.busy_s(t) == pytest.approx(0.006)
    assert trace.window_s(t) == pytest.approx(0.010)


def test_module_time_by_name():
    t = hand_made()
    assert trace.module_s(t, "_prefill_impl") == pytest.approx(0.003)
    assert trace.module_s(t, "jit__unknown") == pytest.approx(0.002)
    assert trace.module_s(t, "_nothing_") == 0.0


def test_breakdown():
    t = hand_made()
    ops = trace.top_ops(t)
    # the nested fusion.4 is the while's, not counted again
    assert ops == [["jit__prefill_impl/fusion", pytest.approx(0.003)],
                   ["jit__unknown/while", pytest.approx(0.002)],
                   ["?/copy", pytest.approx(0.001)]]
    gaps = trace.idle_gaps(t)
    # gaps: [0,1], [4,6], [8,9]: the 2 ms one first, named by the
    # innermost span over its middle
    assert gaps[0] == ["engine.prefill", pytest.approx(0.002)]
    assert gaps[1] == ["scheduler.tick", pytest.approx(0.001)]
    assert gaps[2] == ["scheduler.tick", pytest.approx(0.001)]


def test_no_device_ops_reads_nothing():
    t = hand_made()
    t["devices"] = {"/device:TPU:0": {"ops": [], "modules": []}}
    assert trace.busy_s(t) is None
    assert trace.idle_gaps(t) == []


def test_recorded_chip_trace():
    """400 ms of a deepseek-67b-s6.batch window on one TPU v5e."""
    t = json.loads((FIXTURES / "trace_tpu_v5e.json").read_text())
    busy = trace.busy_s(t)
    assert 0 < busy <= trace.window_s(t)
    ops = trace.top_ops(t)
    assert ops == sorted(ops, key=lambda kv: -kv[1]) and len(ops) <= 10
    assert sum(v for _, v in ops) <= busy + 1e-9
    gaps = trace.idle_gaps(t)
    idle = trace.window_s(t) - busy
    assert sum(v for _, v in gaps) <= idle + 1e-9
    # the fused decode chunk is a jit of a functools.partial: the trace
    # names its module jit__unknown, so no reader can find it by name yet
    assert trace.module_s(t, "jit__unknown") > 0


def test_extract_reads_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(trace.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("scheduler.tick"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    t = trace.extract(str(tmp_path))
    w0, w1 = t["window"]
    assert w1 > w0
    ticks = [h for h in t["host"] if h[0] == "scheduler.tick"]
    assert len(ticks) == 1 and w0 <= ticks[0][1] <= w1
