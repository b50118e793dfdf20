"""Spans and counters inside the serving loop: the tick's wall time split
into host work and the one sync, the worker's CPU time over the host
part, the engine's admission counters, the live-KV counter against the
host length mirror, garbage-collection counters, the tick's parts in the
Perfetto export, and stable names for the device programs and kernels.
(The profiler trace itself is read back in
``perfbench/tests/test_perfbench_spans.py``.)"""

import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.kernels.decode_attention import (
    decode_attention, paged_decode_attention,
)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gmm import gmm
from repro.kernels.rglru import rglru_scan
from repro.kernels.rwkv6 import wkv_scan
from repro.models import build_model
from repro.serving import ContinuousBatchingScheduler, GenerationEngine
from repro.serving import tracing
from repro.serving.tracing import TICK_PARTS, Tracer, now, phases_ms


@pytest.fixture(scope="module")
def model_and_params():
    model = build_model(CONFIGS["max-sentiment"])
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model_and_params, **kw):
    model, params = model_and_params
    return GenerationEngine(model, params, max_batch=3, max_seq=64, **kw)


def _serve(sched, prompts, max_new):
    reqs = [sched.submit(p, max_new_tokens=n)
            for p, n in zip(prompts, max_new)]
    sched.run()
    return reqs


PROMPTS = [[1, 2, 3], [4] * 9, [5, 6], [7] * 17, [8] * 4]
MAX_NEW = [5, 9, 3, 12, 7]


def test_tick_wall_is_host_plus_sync(model_and_params):
    sched = ContinuousBatchingScheduler(_engine(model_and_params),
                                        tracer=Tracer())
    _serve(sched, PROMPTS, MAX_NEW)
    s = sched.stats
    assert s.ticks > 0 and s.sync_wait_s > 0 and s.host_s > 0
    assert s.host_s + s.sync_wait_s == pytest.approx(s.wall_s, rel=1e-12,
                                                      abs=1e-12)
    # the CPU-time reads sit inside the wall-time reads of each part
    assert 0 < s.host_cpu_s <= s.host_s


def test_inserts_advance_once_per_admission(model_and_params):
    eng = _engine(model_and_params)
    sched = ContinuousBatchingScheduler(eng)
    for p, n in zip(PROMPTS, MAX_NEW):
        sched.submit(p, max_new_tokens=n)
    seen = []
    while sched.has_work():
        before = (eng.inserts, eng.insert_host_s, sched.stats.prefills)
        sched.tick()
        d_ins = eng.inserts - before[0]
        seen.append(d_ins)
        assert d_ins == sched.stats.prefills - before[2]
        assert (eng.insert_host_s > before[1]) == (d_ins > 0)
    assert eng.inserts == sum(seen) == len(PROMPTS)


@pytest.mark.parametrize("chunk", [1, 4, 8])
def test_kv_tokens_sum_is_the_length_mirror_per_step(model_and_params,
                                                     chunk):
    """Each decode step adds the live context of every slot it ran: a
    request of P prompt tokens and N outputs (the first from prefill)
    adds P+1 ... P+N-1, whatever the chunking."""
    eng = _engine(model_and_params, decode_chunk=chunk)
    sched = ContinuousBatchingScheduler(eng, decode_chunk=chunk)
    reqs = _serve(sched, PROMPTS, MAX_NEW)
    assert all(len(r.output) == n for r, n in zip(reqs, MAX_NEW))
    want = sum((n - 1) * len(p) + (n - 1) * n // 2
               for p, n in zip(PROMPTS, MAX_NEW))
    assert sched.stats.kv_tokens_sum == want


def test_kv_tokens_sum_per_tick_reads_the_mirror(model_and_params):
    """One step per tick: the tick's share is the sum of the host length
    mirror over the slots that decoded, read after the commit."""
    eng = _engine(model_and_params, decode_chunk=1)
    sched = ContinuousBatchingScheduler(eng, decode_chunk=1)
    reqs = [sched.submit(p, max_new_tokens=n)
            for p, n in zip(PROMPTS, MAX_NEW)]
    while sched.has_work():
        kv0, steps0 = sched.stats.kv_tokens_sum, sched.stats.decode_steps
        tick = sched.stats.ticks
        before = [len(r.output) for r in reqs]
        sched.tick()
        # growth past the first token (from the prefill of an admission
        # this tick) is the step's
        decoded = [r for r, n in zip(reqs, before)
                   if len(r.output) - n - (r.admitted_at_tick == tick) > 0]
        assert sched.stats.decode_steps - steps0 == (1 if decoded else 0)
        assert sched.stats.kv_tokens_sum - kv0 == sum(
            eng.context_len(r.slot) for r in decoded)


def test_gc_is_counted_while_watched():
    users = tracing._GC._users
    tracing.watch_gc()
    try:
        before = tracing.gc_stats()
        gc.collect()
        after = tracing.gc_stats()
        assert after["collections"][2] == before["collections"][2] + 1
        assert after["pause_s"][2] > before["pause_s"][2]
        assert after["max_pause_s"][2] > 0
    finally:
        tracing.unwatch_gc()
    assert tracing._GC._users == users
    assert (tracing._GC.on_gc in gc.callbacks) == (users > 0)


def test_phases_share_their_boundaries():
    p = phases_ms(10.0, 10.25, 10.5, 11.0)
    assert p == {"queue_ms": 250.0, "prefill_ms": 250.0, "decode_ms": 500.0,
                 "e2e_ms": 1000.0}
    # never admitted: all queue
    assert phases_ms(10.0, None, None, 10.1) == {
        "queue_ms": 100.0, "prefill_ms": 0.0, "decode_ms": 0.0,
        "e2e_ms": 100.0}


def test_export_nests_the_tick_parts():
    tracer = Tracer(model="m")
    t = now()
    tracer.tick(3, t, t + 0.010, k=4, active=2, emitted=8,
                parts=(t + 0.001, t + 0.003, t + 0.009))
    xs = [e for e in tracer.to_chrome() if e["ph"] == "X"]
    tick, parts = xs[0], xs[1:]
    assert tick["name"] == "tick 3"
    assert [e["name"] for e in parts] == list(TICK_PARTS)
    assert all(e["cat"] == "scheduler" and e["tid"] == tick["tid"]
               for e in parts)
    assert parts[0]["ts"] == tick["ts"]
    assert sum(e["dur"] for e in parts) == pytest.approx(tick["dur"])
    assert [e["dur"] for e in parts] == pytest.approx([1e3, 2e3, 6e3, 1e3])


def test_decode_programs_have_stable_names(model_and_params):
    model, params = model_and_params
    eng = GenerationEngine(model, params, max_batch=2, max_seq=64,
                           paged=True, page_size=8, prefix_cache=True)
    sched = ContinuousBatchingScheduler(eng)
    _serve(sched, [[1] * 20, [1] * 20], [6, 6])   # the second hits
    assert eng._chunk_jit and eng._fill_jit
    assert {f.__name__ for f in eng._chunk_jit.values()} == {"decode_chunk"}
    assert {f.__name__ for f in eng._fill_jit.values()} == {"prefix_fill"}


def _pallas_names(fn, *args):
    names = []

    def walk(jaxpr):
        for e in jaxpr.eqns:
            if e.primitive.name == "pallas_call":
                names.append(e.params["name"])
            for v in e.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


def _ones(*shape):
    return jnp.ones(shape, jnp.float32)


KERNELS = {
    "flash_attention": (
        lambda q: flash_attention(q, q, q, interpret=True),
        (_ones(1, 2, 128, 64),)),
    "decode_attention": (
        lambda q, k: decode_attention(q, k, k, jnp.asarray([3], jnp.int32),
                                      interpret=True),
        (_ones(1, 2, 64), _ones(1, 256, 2, 64))),
    "paged_decode_attention": (
        lambda q, kp: paged_decode_attention(
            q, kp, kp, jnp.asarray([[0, 1]], jnp.int32),
            jnp.asarray([9], jnp.int32), interpret=True),
        (_ones(1, 2, 16), _ones(4, 8, 1, 16))),
    "gmm": (lambda x, w: gmm(x, w, interpret=True),
            (_ones(2, 128, 256), _ones(2, 256, 128))),
    "rglru_scan": (lambda a: rglru_scan(a, a, interpret=True),
                   (_ones(1, 128, 256),)),
    "rwkv6_wkv": (lambda r, u: wkv_scan(r, r, r, r, u, interpret=True),
                  (_ones(1, 2, 64, 64), _ones(2, 64))),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_each_pallas_call_is_named(name):
    fn, args = KERNELS[name]
    assert _pallas_names(fn, *args) == [name]


def test_clock_pair_is_read_together():
    a = tracing.clock_pair()
    b = tracing.clock_pair()
    # both clocks advance by the same elapsed time between two readings
    d_serving = b["serving_clock_s"] - a["serving_clock_s"]
    d_profiler = (b["profiler_clock_ns"] - a["profiler_clock_ns"]) / 1e9
    assert d_serving >= 0 and d_profiler == pytest.approx(d_serving,
                                                          abs=0.05)
    assert np.isfinite(a["serving_clock_s"])
