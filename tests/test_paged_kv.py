"""Paged KV cache: block-table kernel parity, pool allocation/exhaustion,
paged-vs-contiguous token identity, and the PR's satellite bugfixes
(FIFO sweep race, PROMPT_TOO_LONG validation, generate() EOS release,
ring-family logical usage accounting).
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import CONFIGS
from repro.core.wrapper import PromptTooLong
from repro.kernels import ref
from repro.kernels.decode_attention import (
    paged_decode_attention as pallas_paged,
)
from repro.models import build_model
from repro.serving import ContinuousBatchingScheduler, GenerationEngine

P = 8           # small page so tests straddle boundaries cheaply


# ---------------------------------------------------------------------------
# paged Pallas kernel vs the gather oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lens", [
    (1, P - 1, P),                 # inside / at the first page boundary
    (P + 1, 2 * P, 2 * P + 1),     # straddling the second
    (31, 32, 1),                   # full table next to a near-empty one
])
def test_paged_kernel_parity(lens, nprng):
    B, H, KV, hd, N, nb = len(lens), 4, 2, 16, 10, 4
    q = jnp.asarray(nprng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(nprng.normal(size=(N, P, KV, hd)), jnp.float32)
    vp = jnp.asarray(nprng.normal(size=(N, P, KV, hd)), jnp.float32)
    # distinct non-contiguous pages per slot, trailing sentinel entries
    table = np.full((B, nb), N, np.int32)
    free = list(nprng.permutation(N))
    for b, ln in enumerate(lens):
        for i in range(-(-ln // P)):
            table[b, i] = free.pop()
    table = jnp.asarray(table)
    lengths = jnp.asarray(lens, jnp.int32)
    out = pallas_paged(q, kp, vp, table, lengths, interpret=True)
    want = ref.paged_decode_attention_ref(q, kp, vp, table, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


def test_paged_kernel_unallocated_pages_exact(nprng):
    """Garbage in pool pages a sequence does not own — including the pages
    its sentinel table entries clamp to — must not perturb the output."""
    B, H, KV, hd, N, nb = 2, 2, 1, 16, 8, 4
    q = jnp.asarray(nprng.normal(size=(B, H, hd)), jnp.float32)
    kp = jnp.asarray(nprng.normal(size=(N, P, KV, hd)), jnp.float32)
    vp = jnp.asarray(nprng.normal(size=(N, P, KV, hd)), jnp.float32)
    table = np.full((B, nb), N, np.int32)
    table[0, :1] = [3]
    table[1, :3] = [0, 6, 2]
    table = jnp.asarray(table)
    lengths = jnp.asarray([P, 2 * P + 3], jnp.int32)
    base = pallas_paged(q, kp, vp, table, lengths, interpret=True)
    # poison every page neither sequence owns with huge values
    owned = jnp.zeros((N,), bool).at[jnp.asarray([3, 0, 6, 2])].set(True)
    kp2 = jnp.where(owned[:, None, None, None], kp, 1e9)
    vp2 = jnp.where(owned[:, None, None, None], vp, -1e9)
    # and poison the tail of the last partially-filled page of slot 1
    kp2 = kp2.at[2, 3:].set(1e9)
    vp2 = vp2.at[2, 3:].set(-1e9)
    out = pallas_paged(q, kp2, vp2, table, lengths, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(out))


# ---------------------------------------------------------------------------
# engine + scheduler on the paged path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sentiment():
    cfg = CONFIGS["max-sentiment"]
    model = build_model(cfg)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(sentiment, *, paged, max_batch=2, max_seq=64, pool=None, K=4,
            eos_id=None):
    model, params = sentiment
    return GenerationEngine(model, params, max_batch=max_batch,
                            max_seq=max_seq, decode_chunk=K, eos_id=eos_id,
                            paged=paged, page_size=P, kv_pool_blocks=pool)


def test_paged_matches_contiguous_tokens(sentiment):
    """Greedy generations are identical whichever cache layout backs them
    — paging changes memory, never tokens."""
    def run(paged):
        eng = _engine(sentiment, paged=paged)
        sched = ContinuousBatchingScheduler(eng)
        reqs = [sched.submit([1 + i] * (1 + i % 3), max_new_tokens=5 + i % 4)
                for i in range(6)]
        stats = sched.run()
        assert stats.completed == 6
        return [r.output for r in reqs]

    assert run(False) == run(True)


def test_paged_fused_matches_stepwise(sentiment):
    """Fused K-step chunks and K single steps driven with the same RNG
    chain emit identical tokens on the paged path (sampled, non-greedy)."""
    K = 4
    budgets = np.asarray([K, K], np.int32)
    temps = np.asarray([0.9, 0.0], np.float32)
    prompts = [[1, 2, 3], [9]]
    rng = jax.random.PRNGKey(7)

    ef = _engine(sentiment, paged=True, K=K)
    firsts_f = [int(ef.insert_request(p, i)) for i, p in enumerate(prompts)]
    toks, emitted = ef.step_chunk(rng, temps, budgets, K)
    toks, emitted = np.asarray(toks), np.asarray(emitted)
    fused = [[int(t) for t in toks[b, :emitted[b].sum()]] for b in range(2)]

    es = _engine(sentiment, paged=True, K=K)
    firsts_s = [int(es.insert_request(p, i)) for i, p in enumerate(prompts)]
    last = np.asarray(firsts_s, np.int32)
    stepwise = [[], []]
    r = rng
    for _ in range(K):
        r, sub = jax.random.split(r)
        nxt = es.step(last, sub, temps)
        for b in range(2):
            stepwise[b].append(int(nxt[b]))
            last[b] = int(nxt[b])
    assert firsts_f == firsts_s
    assert fused == stepwise


def test_paged_chunk_interpret_backend_matches_ref(sentiment):
    """On non-oracle backends the fused chunk skips the layout
    translation and drives the block-table kernel against the pool in
    place — tokens must match the oracle path exactly."""
    from repro.kernels import ops

    def run():
        eng = _engine(sentiment, paged=True, max_seq=32, K=4)
        firsts = [int(eng.insert_request(p, i))
                  for i, p in enumerate([[1, 2, 3], [9]])]
        toks, emitted = eng.step_chunk(
            jax.random.PRNGKey(3), 0.0, np.asarray([4, 4], np.int32), 4)
        toks, emitted = np.asarray(toks), np.asarray(emitted)
        return firsts, toks[emitted].tolist()

    want = run()
    with ops.use_backend("interpret"):
        got = run()
    assert got == want


def test_pool_exhaustion_defers_admission_no_slot_leak(sentiment):
    """A pool too small for two co-resident prompts admits them one at a
    time: nothing is lost, nothing leaks, every page returns."""
    eng = _engine(sentiment, paged=True, pool=3, max_seq=64)
    sched = ContinuousBatchingScheduler(eng)
    # each prompt needs ceil((15+1)/8) = 2 pages; pool holds 3 -> strictly
    # serialized admission even though 2 slots are free
    reqs = [sched.submit(list(range(1, 16)), max_new_tokens=3)
            for _ in range(3)]
    stats = sched.run()
    assert stats.completed == 3
    assert all(len(r.output) == 3 and r.error_code is None for r in reqs)
    # admissions were serialized by the block gate
    ticks = sorted(r.admitted_at_tick for r in reqs)
    assert ticks[0] < ticks[1] < ticks[2]
    assert eng.free_blocks() == eng.kv_pool_blocks
    assert not eng._active.any()


def test_mid_decode_pool_exhaustion_retires_cleanly(sentiment):
    eng = _engine(sentiment, paged=True, pool=4, max_seq=64)
    sched = ContinuousBatchingScheduler(eng)
    # greedy: 8-token prompt = 2 pages (prefill + first-write headroom),
    # grows a page per 8 generated; small: 6-token prompt + 2 tokens stays
    # inside its single page
    greedy = sched.submit(list(range(1, 9)), max_new_tokens=40)
    small = sched.submit(list(range(1, 7)), max_new_tokens=2)
    stats = sched.run()
    # the greedy request outgrew the pool and retired cleanly with its
    # partial output; the co-batched request was untouched
    assert greedy.error_code == "KV_POOL_EXHAUSTED"
    assert greedy.done and 0 < len(greedy.output) < 40
    assert "KV pool exhausted" in greedy.error
    assert small.done and small.error_code is None
    assert len(small.output) == 2
    assert stats.pool_exhausted == 1
    # free-on-retire returned every page; the engine can serve again
    assert eng.free_blocks() == 4
    again = sched.submit([5], max_new_tokens=2)
    sched.run()
    assert again.done and again.error_code is None


def test_cancel_frees_every_block(sentiment):
    eng = _engine(sentiment, paged=True, max_seq=64)
    sched = ContinuousBatchingScheduler(eng)
    run = sched.submit(list(range(1, 12)), max_new_tokens=30)
    queued = sched.submit([1, 2], max_new_tokens=30)
    sched.tick()                       # run admitted, decoding
    assert eng.blocks_in_use() > 0
    assert sched.cancel(run.id) and sched.cancel(queued.id)
    sched.run()
    assert run.error_code == "CANCELLED" and queued.error_code == "CANCELLED"
    assert eng.free_blocks() == eng.kv_pool_blocks
    assert not eng._active.any()


def test_qos_path_defers_on_block_exhaustion(sentiment):
    """With an admission controller, granted tickets that cannot get pool
    blocks park in the deferred queue (keeping their grant order) instead
    of being dropped — and cancellation reaches them there."""
    from repro.serving.qos import AdmissionController, QoSConfig
    eng = _engine(sentiment, paged=True, pool=3, max_seq=64)
    sched = ContinuousBatchingScheduler(
        eng, admission=AdmissionController(QoSConfig()))
    reqs = [sched.submit(list(range(1, 16)), max_new_tokens=3,
                         priority="interactive") for _ in range(3)]
    stats = sched.run()
    assert stats.completed == 3
    assert [r.error_code for r in reqs] == [None] * 3
    ticks = sorted(r.admitted_at_tick for r in reqs)
    assert ticks[0] < ticks[1] < ticks[2]      # serialized by the pool
    assert eng.free_blocks() == 3
    # cancellation reaches a deferred request without touching a slot
    sched.submit(list(range(1, 16)), max_new_tokens=20,
                 priority="interactive")
    waiting = sched.submit(list(range(1, 16)), max_new_tokens=3,
                           priority="interactive")
    sched.tick()
    assert len(sched._deferred) == 1
    assert sched.cancel(waiting.id)
    sched.run()
    assert waiting.error_code == "CANCELLED" and waiting.slot == -1
    assert eng.free_blocks() == 3


def test_never_admissible_prompt_retires(sentiment):
    """A prompt needing more pages than the whole pool must not spin in
    the queue forever."""
    eng = _engine(sentiment, paged=True, pool=2, max_seq=64)
    sched = ContinuousBatchingScheduler(eng)
    req = sched.submit(list(range(1, 30)), max_new_tokens=2)  # 4 pages > 2
    sched.run()
    assert req.done and req.error_code == "KV_POOL_EXHAUSTED"
    assert sched.stats.pool_exhausted == 1


def test_kv_stats_accounting(sentiment):
    """Paged memory is charged per page in use; contiguous per slot
    capacity — the whole point of the refactor, asserted in bytes."""
    paged = _engine(sentiment, paged=True, max_seq=64)
    cont = _engine(sentiment, paged=False, max_seq=64)
    for eng in (paged, cont):
        eng.insert_request([1, 2, 3], 0)         # 3 + headroom -> 1 page
    ps, cs = paged.kv_stats(), cont.kv_stats()
    assert ps["paged"] and not cs["paged"]
    assert ps["active_tokens"] == cs["active_tokens"] == 3
    assert ps["kv_bytes_per_token"] == cs["kv_bytes_per_token"] > 0
    assert ps["blocks_in_use"] == 1
    assert ps["kv_bytes_in_use"] == P * ps["kv_bytes_per_token"]
    # contiguous charges the full max_seq for the one occupied slot
    assert cs["kv_bytes_in_use"] == 64 * cs["kv_bytes_per_token"]
    assert ps["kv_bytes_per_active_token"] < cs["kv_bytes_per_active_token"]
    paged.release_slot(0)
    assert paged.kv_stats()["blocks_in_use"] == 0


def test_insert_reserves_first_decode_page(sentiment):
    """A prompt filling its last page exactly still reserves the page its
    first decode write lands in — a fresh admission can never be starved
    by co-tenants before its first chunk."""
    eng = _engine(sentiment, paged=True, max_seq=64)
    eng.insert_request(list(range(1, 9)), 0)     # 8 tokens == 1 full page
    assert len(eng._slot_blocks[0]) == 2         # prefill page + write page
    assert eng.capacity_left(0) > 0


# ---------------------------------------------------------------------------
# satellite: FIFO sweep must not rotate the queue under concurrent submits
# ---------------------------------------------------------------------------

def test_sweep_cancelled_preserves_fifo_order(sentiment):
    eng = _engine(sentiment, paged=False)
    sched = ContinuousBatchingScheduler(eng)
    reqs = [sched.submit([1 + i], max_new_tokens=2) for i in range(6)]
    reqs[1].cancelled = True
    reqs[4].cancelled = True
    with sched._lock:
        sched._sweep_cancelled()
    assert [r.id for r in sched.queue] == [reqs[i].id for i in (0, 2, 3, 5)]
    assert reqs[1].error_code == "CANCELLED"
    assert reqs[4].error_code == "CANCELLED"


def test_sweep_cancelled_concurrent_submit_keeps_position(sentiment):
    """Regression for the popleft/append rotation: an arrival landing
    mid-sweep must keep its FIFO position (the queue stays id-ordered when
    all submits come from one thread), and no request may be lost."""
    eng = _engine(sentiment, paged=False)
    sched = ContinuousBatchingScheduler(eng)
    total = 400
    submitted = []
    stop = threading.Event()

    def submitter():
        for i in range(total):
            submitted.append(sched.submit([1], max_new_tokens=1))
        stop.set()

    t = threading.Thread(target=submitter)
    t.start()
    swept = 0
    while not stop.is_set() or swept == 0:
        # cancel the third-from-front entry (if any) and sweep while the
        # submitter is appending
        q = list(sched.queue)
        if len(q) > 3:
            q[2].cancelled = True
        with sched._lock:
            sched._sweep_cancelled()
        swept += 1
        ids = [r.id for r in list(sched.queue)]
        assert ids == sorted(ids), "sweep broke FIFO order"
    t.join()
    with sched._lock:
        sched._sweep_cancelled()
    ids = [r.id for r in sched.queue]
    assert ids == sorted(ids)
    cancelled = {r.id for r in submitted if r.done}
    # conservation: every submitted request is either still queued (in
    # order) or retired as cancelled
    assert len(ids) + len(cancelled) == total
    assert all(r.error_code == "CANCELLED" for r in submitted if r.done)


# ---------------------------------------------------------------------------
# satellite: PROMPT_TOO_LONG at validation, before admission
# ---------------------------------------------------------------------------

def test_fits_prompt_requires_headroom(sentiment):
    model, params = sentiment
    eng = GenerationEngine(model, params, max_batch=2, max_seq=64)
    assert eng.fits_prompt(63) and not eng.fits_prompt(64)
    assert eng.max_prompt_len() == 63
    # non-power-of-two max_seq: the advertised longest prompt must itself
    # be admissible (a 99-token prompt would pad to a 128 bucket > 100)
    odd = GenerationEngine(model, params, max_batch=2, max_seq=100)
    assert odd.max_prompt_len() == 64
    assert odd.fits_prompt(odd.max_prompt_len())
    assert not odd.fits_prompt(65)


def test_deferred_request_sheds_on_deadline(sentiment):
    """A granted ticket parked for pool blocks still honors its deadline
    (the controller only enforces it up to the grant)."""
    from repro.serving.qos import AdmissionController, QoSConfig
    eng = _engine(sentiment, paged=True, pool=3, max_seq=64)
    sched = ContinuousBatchingScheduler(
        eng, admission=AdmissionController(QoSConfig()))
    import time as _time
    hog = sched.submit(list(range(1, 16)), max_new_tokens=30,
                       priority="interactive")
    late = sched.submit(list(range(1, 16)), max_new_tokens=2,
                        priority="interactive", deadline_s=0.15)
    sched.tick()                       # hog placed; late granted, deferred
    assert len(sched._deferred) == 1
    _time.sleep(0.2)                   # deadline expires while deferred
    sched.run()
    assert late.error_code == "DEADLINE_EXCEEDED" and late.slot == -1
    assert hog.done


def test_ring_bucket_equal_max_seq_rejected():
    """Ring families pad to the bucket: a prompt whose bucket equals
    max_seq has zero KV headroom and must be rejected up front, not after
    burning a prefill + slot."""
    from repro.configs import ASSIGNED
    from repro.configs.base import reduce_for_smoke
    cfg = reduce_for_smoke(ASSIGNED["rwkv6-7b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    eng = GenerationEngine(model, params, max_batch=2, max_seq=32)
    assert eng.max_prompt_len() == 16
    assert eng.fits_prompt(16)
    assert not eng.fits_prompt(17)     # buckets to 32 == max_seq


def test_scheduler_retires_too_long_prompt(sentiment):
    """Defense-in-depth: a raw submit of an inadmissible prompt retires
    with PROMPT_TOO_LONG instead of queueing forever."""
    eng = _engine(sentiment, paged=False, max_seq=64)
    sched = ContinuousBatchingScheduler(eng)
    req = sched.submit(list(range(64)), max_new_tokens=4)
    ok = sched.submit([1], max_new_tokens=2)
    stats = sched.run()
    assert req.done and req.error_code == "PROMPT_TOO_LONG"
    assert not req.output               # never touched a slot
    assert stats.rejected == 1
    assert ok.done and ok.error_code is None


def test_service_rejects_too_long_prompt_structured():
    import repro.core.assets  # noqa: F401
    from repro.core import EXCHANGE
    from repro.core.service import BatchedService
    wrapper = EXCHANGE.get("qwen3-4b").build(max_seq=32, max_batch=2)
    svc = BatchedService(wrapper)
    try:
        # bypass the wrapper's own truncation to hit validation directly
        wrapper.prepare_generation = lambda inp: (
            list(range(1, 33)), {"max_new_tokens": 2, "temperature": 0.0},
            None)
        with pytest.raises(PromptTooLong):
            svc._enqueue({"text": "x"})
        env = svc.predict({"text": "x"})
        assert env["status"] == "error"
        assert env["code"] == "PROMPT_TOO_LONG"
        assert svc.scheduler.stats.prefills == 0   # never touched admission
    finally:
        svc.close()


# ---------------------------------------------------------------------------
# satellite: generate() releases EOS'd slots (no wasted decode / drift)
# ---------------------------------------------------------------------------

def test_generate_releases_done_slots(sentiment):
    model, params = sentiment
    probe = GenerationEngine(model, params, max_batch=2, max_seq=64)
    stream = probe.generate([[1, 2, 3], [9]], max_new_tokens=12)[0].tokens
    eos = stream[2]                    # slot 0 hits EOS at its 3rd token
    eng = GenerationEngine(model, params, max_batch=2, max_seq=64,
                           eos_id=eos)
    res = eng.generate([[1, 2, 3], [9]], max_new_tokens=12)
    n0 = len(res[0].tokens)
    assert res[0].tokens[-1] == eos and n0 < 12
    # cache length froze when the slot hit EOS: prefill len + one KV write
    # per post-first token — NOT one per co-tenant step
    assert int(eng._lengths[0]) == 3 + (n0 - 1)
    assert len(res[1].tokens) == 12
    assert int(eng._lengths[1]) == 1 + 11


# ---------------------------------------------------------------------------
# satellite: ring families report logical prompt length in usage/stats
# ---------------------------------------------------------------------------

def test_ring_logical_usage_accounting():
    import repro.core.assets  # noqa: F401
    from repro.core import EXCHANGE
    wrapper = EXCHANGE.get("rwkv6-7b").build(max_seq=64, max_batch=2)
    eng = wrapper.engine
    eng.insert_request([1, 2, 3], 0)
    # physical (cache bookkeeping) charges the padded bucket; logical
    # (usage/stats) charges what the user sent
    assert eng.context_len(0) == 16
    assert eng.logical_len(0) == 3
    assert eng.kv_stats()["active_tokens"] == 3
    eng.release_slot(0)


def test_batched_service_stats_expose_kv_cache():
    import repro.core.assets  # noqa: F401
    from repro.core import EXCHANGE
    from repro.core.service import BatchedService
    # deepseek-67b (reduced): dense, NO sliding window — a genuinely
    # linear cache, so paged does not fall back
    wrapper = EXCHANGE.get("deepseek-67b").build(
        max_seq=64, max_batch=2, paged=True, page_size=P)
    svc = BatchedService(wrapper)
    try:
        env = svc.predict({"text": "hello", "max_new_tokens": 3})
        assert env["status"] == "ok"
        st = svc.stats()
        kv = st["kv_cache"]
        assert kv["paged"] and kv["pool_blocks"] > 0
        assert kv["free_blocks"] == kv["pool_blocks"]   # drained -> all free
        assert st["pool_exhausted"] == 0
        snap = svc.metrics.to_json()
        assert any(k.startswith("max_kv_pool_blocks_in_use")
                   for k in snap["gauges"])
    finally:
        svc.close()


def test_deploy_body_paged_knobs():
    import repro.core.assets  # noqa: F401
    from repro.core.api import MAXServer
    server = MAXServer(build_kw={"max_seq": 64, "max_batch": 2},
                       auto_deploy=False)
    try:
        resp = server.dispatch(
            "POST", "/v2/model/deepseek-67b/deploy",
            {"service": "batched", "paged": True, "page_size": 16,
             "kv_pool_blocks": 8})
        assert resp.status == 200, resp.body
        assert resp.body["kv_cache"]["paged"] is True
        assert resp.body["kv_cache"]["page_size"] == 16
        assert resp.body["kv_cache"]["pool_blocks"] == 8
        bad = server.dispatch("POST", "/v2/model/deepseek-67b/deploy",
                              {"page_size": -3})
        assert bad.status == 400
    finally:
        for aid in server.manager.deployed():
            server.manager.undeploy(aid)
