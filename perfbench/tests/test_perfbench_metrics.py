"""The metric arithmetic: due-time TTFT, per-request TPOT, tails over all
requests of the window, the rate over the whole window, and the readers
found by name."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from pbench import flops, spec, stats  # noqa: E402
from pbench.client import Outcome  # noqa: E402
from pbench.run_cell import RunData  # noqa: E402
from pbench.traffic import Request  # noqa: E402


def _out(due, events, prompt=10, status="ok", usage=None):
    """A finished request: ``events`` are (arrival, tokens) pairs."""
    o = Outcome(req=Request(idx=0, prompt=[256] * prompt, text="",
                            max_new_tokens=64), due=due, sent=due)
    o.events = list(events)
    o.tokens = [1] * sum(n for _, n in events)
    o.status = status
    o.usage = usage or {}
    return o


def _run(outcomes, t0=100.0, seconds=10.0, **kw):
    cell = spec.load_cell("deepseek-67b-s6.docqa")
    return RunData(cell=cell, seconds=seconds, t0=t0, t1=t0 + seconds,
                   setup_s=kw.pop("setup_s", 31.5), outcomes=outcomes,
                   sched=kw.pop("sched", {}), max_batch=kw.pop("max_batch", 4),
                   device_kind="TPU v5 lite", **kw)


def read(name, run):
    return spec.load_reader(BENCH, name)(run)


def test_ttft_counts_from_the_due_time():
    # sent late: the wait before sending is part of its TTFT
    o = _out(due=100.0, events=[(100.5, 1)])
    o.sent = 100.3
    assert stats.ttft_s(o) == pytest.approx(0.5)


def test_tpot_is_per_request_not_per_gap():
    # 1 token, then chunks of 8 at 0.4 s intervals: 17 tokens in 0.8 s
    o = _out(due=0.0, events=[(1.0, 1), (1.4, 8), (1.8, 8)])
    assert stats.tpot_s(o) == pytest.approx(0.8 / 16)
    assert stats.tpot_s(_out(0.0, [(1.0, 1)])) is None


def test_tails_over_all_requests_of_the_window():
    outs = [_out(due=100.0 + i * 0.1, events=[(100.0 + i * 0.1 + (i + 1) * 0.01,
                                               1), (101.0 + i, 1)])
            for i in range(20)]
    outs.append(_out(due=99.0, events=[(150.0, 1)]))      # due before: out
    run = _run(outs)
    ttfts = [(i + 1) * 0.01 for i in range(20)]
    assert read("ttft_p90_ms", run) == pytest.approx(
        1e3 * stats.percentile(ttfts, 90))
    assert read("tpot_p90_ms", run) == pytest.approx(
        1e3 * stats.percentile([1.0 + i - (i + 1) * 0.01 - i * 0.1
                                for i in range(20)], 90))


def test_rate_is_over_the_whole_window():
    outs = [_out(due=100.0, events=[(100.5, 1), (105.0, 8), (111.0, 8)]),
            _out(due=99.0, events=[(100.0, 4)])]
    run = _run(outs, t0=100.0, seconds=10.0)
    assert read("output_tok_s", run) == pytest.approx((1 + 8 + 4) / 10.0)


def test_silence_and_slices_show_where_delivery_stopped():
    outs = [_out(due=100.0, events=[(100.5, 1), (101.0, 8), (107.0, 8)]),
            _out(due=100.0, events=[(101.5, 2), (109.5, 3), (111.0, 9)])]
    assert stats.longest_silence(outs, 100.0, 110.0) == (
        pytest.approx(5.5), pytest.approx(1.5))
    assert stats.tokens_per_slice(outs, 100.0, 110.0, 5.0) == [11, 11]
    assert stats.longest_silence([], 100.0, 110.0) == (
        pytest.approx(10.0), pytest.approx(0.0))


def test_occupancy_and_setup():
    run = _run([], sched={"before": {"decode_steps": 10, "occupancy_sum": 30},
                          "after": {"decode_steps": 30, "occupancy_sum": 90}},
               max_batch=4)
    assert read("batch_occupancy.tok_s", run) == pytest.approx(75.0)
    assert read("setup_s", run) == 31.5


def test_phase_span_tails():
    outs = [_out(due=100.0, events=[(101.0, 1)],
                 usage={"queue_ms": float(i), "prefill_ms": 2.0 * i})
            for i in range(11)]
    outs.append(_out(due=100.0, events=[], status="QUEUE_FULL"))
    run = _run(outs)
    assert read("queue_ms_p90", run) == pytest.approx(9.0)
    assert read("prefill_ms_p90", run) == pytest.approx(18.0)


def test_mfu_counts_each_token_at_its_context():
    c = spec.load_cell("deepseek-67b-s6.docqa").config
    o = _out(due=100.0, events=[(100.5, 1), (101.0, 2)], prompt=100)
    run = _run([o])
    work = (flops.prefill_flops(c, 100) + flops.decode_flops(c, 101)
            + flops.decode_flops(c, 102))
    assert read("mfu.tok_s", run) == pytest.approx(
        100.0 * work / (10.0 * 197e12))


def test_flops_of_a_layer():
    c = {"d_model": 8, "num_heads": 2, "num_kv_heads": 1, "head_dim": 4,
         "d_ff": 16, "num_layers": 3, "vocab_size": 10}
    lp = 8 * 8 + 2 * 8 * 4 + 8 * 8 + 3 * 8 * 16
    assert flops.layer_params(c) == lp
    assert flops.decode_flops(c, 5) == 2 * 3 * lp + 4 * 2 * 4 * 3 * 5 + 160
    assert flops.prefill_flops(c, 2) == 2 * 3 * lp * 2 + 4 * 2 * 4 * 3 * 3 + 160


def test_peak_table_refuses_an_unknown_chip():
    assert flops.peak("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peak("TPU v9 imaginary")


def test_readers_find_nothing_without_a_trace():
    run = _run([_out(due=100.0, events=[(101.0, 1)])])
    assert read("mfu.ttft", run) is None
    assert read("device_idle_share.tpot", run) is None
