"""Benchmark harness — one benchmark per paper claim/figure.

The MAX paper (CIKM'19 demo) has no quantitative tables; its claims are
architectural. Each benchmark below pins one of them to a number:

  fig3_wrapper_overhead   the wrapper abstraction adds ~zero cost over a
                          raw jit'd call (pre/post + envelope)
  fig1_registry_scale     catalogue operations stay O(ms) with 12+ assets
  fig1_deploy_latency     "container start" (build + first compile) per asset
  fig2_api_roundtrip      HTTP predict round-trip on the demo models
  serving_throughput      continuous batching vs one-request-at-a-time
  serving_http            requests/s + p50/p95 latency through the REAL
                          HTTP stack, sync vs batched service (also
                          written to BENCH_serving.json for trend lines)
  qos_overload            2 greedy `batch` clients flood the queue while 1
                          `interactive` client keeps sending small
                          requests: interactive p95 under QoS admission
                          (priority + per-client fairness) vs plain FIFO
                          (also into BENCH_serving.json; `--quick` runs
                          this scenario in <30s and exits nonzero on
                          regression)
  decode_fastpath         fused multi-step decode (one host sync per
                          decode_chunk tokens) vs the per-token-sync
                          baseline (decode_chunk=1) through the same
                          scheduler on the same config — the dispatch-
                          bound regime the fast path eliminates (also
                          into BENCH_serving.json; part of `--quick`,
                          fails when fused loses its >=1.2x edge over
                          per-token sync)
  streaming               SSE streaming TTFT vs full-completion latency
                          for a 64-token generation — the first `token`
                          event must land in < 0.5x the non-streaming
                          predict time (also into BENCH_serving.json;
                          part of `--quick`)
  paged_kv                paged (block-table) vs contiguous KV cache on a
                          mixed-length co-batch: tokens/s parity (>=0.9x)
                          at a >=2x reduction in measured KV bytes per
                          active token (also into BENCH_serving.json;
                          part of `--quick`)
  robustness              fault-injected serving (~5% per-chunk engine
                          faults) vs a fault-free twin: completion rate
                          via quarantine+retry, token identity (greedy
                          decode replays exactly), and goodput ratio
                          (also into BENCH_serving.json; part of
                          `--quick`; `--chaos-quick` runs ONLY this
                          fault smoke)
  fleet_rps_scaling       replica-group scaling: requests/s at 2 replicas
                          vs 1 under a deterministic per-tick stall
                          profile, forced-8-device subprocess harness
                          (also into BENCH_serving.json; part of
                          `--quick`, fails when 2 replicas lose the
                          >=1.5x rps edge)
  kernel_<name>           Pallas kernel (interpret) vs jnp oracle allclose +
                          oracle timing (CPU container: correctness-scale)
  roofline_terms          derived from the dry-run records (see
                          EXPERIMENTS.md §Roofline for the full table)

Output: ``name,us_per_call,derived`` CSV on stdout. Every pass/fail
bound goes through :func:`gate`, so a failing ``--quick`` run prints
EVERY failing gate with its measured value against the bound — not
just the first.
"""

from __future__ import annotations

import json
import os
import time

ROWS = []
GATES = []      # (name, ok, measured, bound) — every bound checked this run


def row(name: str, us_per_call: float, derived: str = ""):
    ROWS.append((name, us_per_call, derived))
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def gate(name: str, ok: bool, measured, bound: str) -> bool:
    """Record one pass/fail bound. ``main`` prints ALL failing gates with
    measured-vs-bound at the end, so a multi-gate regression shows every
    violated bound in one run instead of one per rerun."""
    GATES.append((name, bool(ok), measured, bound))
    return bool(ok)


def failing_gates():
    return [g for g in GATES if not g[1]]


def print_gate_report():
    failed = failing_gates()
    for name, _, measured, bound in failed:
        print(f"# GATE FAIL {name}: measured {measured}, bound {bound}",
              flush=True)
    if GATES and not failed:
        print(f"# all {len(GATES)} gates passed", flush=True)


def _time(fn, n=20, warmup=3):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


def _merge_bench(out_path: str, update: dict):
    """Merge ``update`` into the shared report file — each bench owns its
    keys, siblings written by other benches survive."""
    report = {}
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                report = json.load(f)
        except Exception:
            report = {}
    report.update(update)
    with open(out_path, "w") as f:
        json.dump(report, f, indent=1)


def bench_wrapper_overhead():
    import jax
    import jax.numpy as jnp
    import repro.core.assets  # noqa: F401
    from repro.core import EXCHANGE

    wrapper = EXCHANGE.get("max-sentiment").build(max_seq=64, max_batch=2)
    toks = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
    fwd = jax.jit(wrapper.model.forward)
    fwd(wrapper.params, {"tokens": toks})[0].block_until_ready()

    raw = _time(lambda: fwd(wrapper.params, {"tokens": toks})[0]
                .block_until_ready())
    wrapped = _time(lambda: wrapper.predict_envelope("abc"))
    row("fig3_wrapper_raw_forward", raw)
    row("fig3_wrapper_predict_envelope", wrapped,
        f"overhead_x={wrapped / raw:.2f}")


def bench_registry():
    import repro.core.assets  # noqa: F401
    from repro.core import EXCHANGE, build_swagger

    row("fig1_registry_list", _time(lambda: EXCHANGE.list(), n=200),
        f"assets={len(EXCHANGE)}")
    row("fig1_swagger_build", _time(lambda: build_swagger(EXCHANGE), n=50))


def bench_deploy_latency():
    from repro.core import DeploymentManager

    mgr = DeploymentManager()
    for asset_id in ("max-sentiment", "rwkv6-7b"):
        t0 = time.perf_counter()
        dep = mgr.deploy(asset_id, max_seq=32, max_batch=2)
        dep.predict({"text": "warm", "max_new_tokens": 2}
                    if asset_id != "max-sentiment" else ["warm"])
        dt = (time.perf_counter() - t0) * 1e6
        row(f"fig1_deploy_{asset_id}", dt, "build+first_compile")


def bench_api_roundtrip():
    import urllib.request

    from repro.core import MAXServer

    with MAXServer(build_kw={"max_seq": 64, "max_batch": 2}) as s:
        payload = json.dumps({"input": ["benchmark"]}).encode()

        def call():
            req = urllib.request.Request(
                s.url + "/model/max-sentiment/predict", payload,
                {"Content-Type": "application/json"})
            urllib.request.urlopen(req).read()

        call()
        row("fig2_api_roundtrip", _time(call, n=20))


def bench_serving_throughput():
    import jax

    from repro.configs import ASSIGNED
    from repro.configs.base import reduce_for_smoke
    from repro.models import build_model
    from repro.serving import ContinuousBatchingScheduler, GenerationEngine

    # a heavier (reduced qwen3) model so compute, not Python dispatch,
    # dominates the tick — the regime continuous batching targets
    cfg = reduce_for_smoke(ASSIGNED["qwen3-4b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))

    def run(max_batch):
        eng = GenerationEngine(model, params, max_batch=max_batch, max_seq=64)
        eng.generate([[1]], max_new_tokens=2)     # warm compile caches
        sched = ContinuousBatchingScheduler(eng)
        for i in range(16):
            sched.submit([1 + i % 30], max_new_tokens=8)
        return sched.run()

    seq = run(1)
    bat = run(8)
    row("serving_sequential_tok_s", 1e6 / max(seq.tokens_per_s, 1e-9),
        f"tok/s={seq.tokens_per_s:.1f}")
    row("serving_continuous_tok_s", 1e6 / max(bat.tokens_per_s, 1e-9),
        f"tok/s={bat.tokens_per_s:.1f} speedup_x="
        f"{bat.tokens_per_s / max(seq.tokens_per_s, 1e-9):.2f}")


def bench_serving_http(out_path: str = "BENCH_serving.json"):
    """The API hot path end-to-end: concurrent clients through the real
    ThreadingHTTPServer into each service kind. The batched service should
    hold throughput as concurrency grows (decode batches), the sync one
    degrade toward thread-count scaling."""
    import json as _json
    import statistics
    import threading
    import urllib.request

    import repro.core.assets  # noqa: F401 — populate the exchange
    from repro.core import MAXServer

    model = "qwen3-4b"
    n_clients, n_requests = 4, 16
    payload = _json.dumps(
        {"input": {"text": "benchmark", "max_new_tokens": 4}}).encode()
    report = {"model": model, "clients": n_clients,
              "requests": n_requests, "modes": {}}

    for mode in ("sync", "batched"):
        with MAXServer(build_kw={"max_seq": 64, "max_batch": n_clients},
                       service_mode=mode,
                       service_kw={"batch_window_s": 0.01}) as s:
            url = f"{s.url}/v2/model/{model}/predict"

            def call():
                req = urllib.request.Request(
                    url, payload, {"Content-Type": "application/json"})
                urllib.request.urlopen(req).read()

            call()                                  # build + compile
            latencies, lock = [], threading.Lock()

            def client(k):
                for _ in range(n_requests // n_clients):
                    t0 = time.perf_counter()
                    call()
                    dt = time.perf_counter() - t0
                    with lock:
                        latencies.append(dt)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(n_clients)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            wall = time.perf_counter() - t0
            latencies.sort()
            q = statistics.quantiles(latencies, n=20)
            stats = {
                "requests_per_s": round(len(latencies) / wall, 2),
                "p50_ms": round(q[9] * 1e3, 1),
                "p95_ms": round(q[18] * 1e3, 1),
                "wall_s": round(wall, 2),
            }
            if mode == "batched":
                svc = s.manager.get(model).service.stats()
                stats["mean_batch_size"] = svc["mean_batch_size"]
                stats["max_batch_seen"] = svc["max_batch_seen"]
            report["modes"][mode] = stats
            row(f"serving_http_{mode}", 1e6 * wall / len(latencies),
                f"rps={stats['requests_per_s']} p50={stats['p50_ms']}ms "
                f"p95={stats['p95_ms']}ms")

    sync_rps = report["modes"]["sync"]["requests_per_s"]
    bat_rps = report["modes"]["batched"]["requests_per_s"]
    report["speedup_x"] = round(bat_rps / max(sync_rps, 1e-9), 2)
    # merge: other benches (qos_overload, decode_fastpath) own sibling keys
    _merge_bench(out_path, report)
    row("serving_http_speedup", 0.0,
        f"batched/sync={report['speedup_x']}x -> {out_path}")


def bench_qos_overload(out_path: str = "BENCH_serving.json",
                       quick: bool = False) -> bool:
    """The QoS acceptance scenario: under sustained overload from two
    greedy ``batch`` clients, an ``interactive`` client's p95 latency with
    the deficit-weighted-priority controller must beat plain FIFO
    admission. Returns True when it does (the ``--quick`` gate also
    accepts qos_p95 within 2x of the uncontended baseline)."""
    import threading

    import repro.core.assets  # noqa: F401 — populate the exchange
    from repro.core import BatchedService, EXCHANGE, QoSConfig
    from repro.serving.metrics import percentile

    n_interactive = 6 if quick else 14
    greedy_batch, greedy_tokens = (6, 6) if quick else (8, 8)
    wrapper = EXCHANGE.get("qwen3-4b").build(max_seq=64, max_batch=2)
    scenario_out: dict = {"greedy_clients": 2, "greedy_batch": greedy_batch,
                          "policies": {}}

    def pctl(lat, q):
        # same nearest-rank estimator /v2/metrics reports, so benchmark
        # p95s stay comparable with the server's own numbers
        return percentile(sorted(lat), q)

    def interactive_call(svc, i):
        t0 = time.perf_counter()
        env = svc.predict({"text": f"ui {i}", "max_new_tokens": 2},
                          qos={"priority": "interactive", "client": "ui"})
        assert env["status"] == "ok", env
        return time.perf_counter() - t0

    solo_p95 = None
    for policy in ("fifo", "drr"):
        svc = BatchedService(wrapper, batch_window_s=0.005,
                             qos=QoSConfig(policy=policy, max_queue=256))
        try:
            # 16 tokens decompose as chunks 8+4+2+1: one call compiles the
            # prefill and every pow2 chunk program the scenario will use
            svc.predict({"text": "warm", "max_new_tokens": 16})  # compile
            if solo_p95 is None:      # uncontended baseline, once
                solo = [interactive_call(svc, -1 - k) for k in range(3)]
                solo_p95 = pctl(solo, 0.95)
            stop = threading.Event()

            def greedy(name):
                while not stop.is_set():
                    svc.predict_batch(
                        [{"text": f"{name} {i}",
                          "max_new_tokens": greedy_tokens}
                         for i in range(greedy_batch)],
                        qos={"priority": "batch", "client": name})

            threads = [threading.Thread(target=greedy, args=(f"greedy{i}",))
                       for i in range(2)]
            for t in threads:
                t.start()
            time.sleep(0.3)                       # let the backlog build
            lat = [interactive_call(svc, i) for i in range(n_interactive)]
            stop.set()
            for t in threads:
                t.join()
            stats = svc.stats()
            scenario_out["policies"][policy] = {
                "interactive_p50_ms": round(pctl(lat, 0.50) * 1e3, 1),
                "interactive_p95_ms": round(pctl(lat, 0.95) * 1e3, 1),
                "completed": stats["completed"],
                "mean_batch_size": stats["mean_batch_size"],
            }
            row(f"qos_overload_{policy}_interactive", pctl(lat, 0.95) * 1e6,
                f"p50={scenario_out['policies'][policy]['interactive_p50_ms']}ms "
                f"p95={scenario_out['policies'][policy]['interactive_p95_ms']}ms")
        finally:
            svc.close()

    fifo_p95 = scenario_out["policies"]["fifo"]["interactive_p95_ms"]
    qos_p95 = scenario_out["policies"]["drr"]["interactive_p95_ms"]
    scenario_out["solo_p95_ms"] = round(solo_p95 * 1e3, 1)
    scenario_out["speedup_x"] = round(fifo_p95 / max(qos_p95, 1e-9), 2)
    ok = gate("qos_interactive_p95",
              qos_p95 < fifo_p95 or qos_p95 <= 2 * scenario_out["solo_p95_ms"],
              f"{qos_p95}ms",
              f"< fifo {fifo_p95}ms or <= 2x solo "
              f"{scenario_out['solo_p95_ms']}ms")
    # merge into the serving report so trend lines keep one file
    _merge_bench(out_path, {"qos_overload": scenario_out})
    row("qos_overload_speedup", 0.0,
        f"fifo/qos={scenario_out['speedup_x']}x "
        f"solo_p95={scenario_out['solo_p95_ms']}ms -> {out_path}")
    return ok


def bench_decode_fastpath(out_path: str = "BENCH_serving.json",
                          quick: bool = False) -> bool:
    """Fused-chunk decode vs per-token host sync, same model/config/load.

    ``decode_chunk=1`` is the per-token-sync baseline (one dispatch + one
    device->host read per generated token — PR 2's loop);
    ``decode_chunk=16`` is the fused path (one ``lax.scan`` dispatch + one
    read per 16 tokens; the serving default is 8, which trades a little
    amortization for tighter admission latency). Best-of-N wall clock per
    mode (this container's CPU is noisy).

    Gate (``--quick``): the fused/stepwise ratio must hold at >= 1.2x
    within the run. Comparing the ratio (not absolute tokens/s) keeps the
    gate machine-independent — a slower container shifts both numbers, but
    the fused path regressing toward per-token cost still fails.
    """

    import jax

    from repro.configs import CONFIGS
    from repro.models import build_model
    from repro.serving import ContinuousBatchingScheduler, GenerationEngine

    cfg = CONFIGS["max-sentiment"]     # small-model serving: the regime
    model = build_model(cfg)           # where dispatch, not compute, binds
    params = model.init(jax.random.PRNGKey(0))
    CHUNK = 16
    # max_new_tokens = n*CHUNK + 1: after the prefill token every budget
    # is a multiple of the chunk, so the fused run measures whole chunks
    # (budget-aligned chunking would otherwise spend the tail in
    # 8/4/2/1-step chunks at stepwise cadence)
    n_req, new_toks, trials = (8, CHUNK + 1, 2) if quick \
        else (16, 2 * CHUNK + 1, 3)

    def engine(chunk):
        eng = GenerationEngine(model, params, max_batch=4, max_seq=64,
                               decode_chunk=chunk)
        warm = ContinuousBatchingScheduler(eng)   # compile prefill + every
        warm.submit([1], max_new_tokens=2 * chunk)  # pow2 chunk program
        warm.run()
        return eng

    def measure(eng):
        sched = ContinuousBatchingScheduler(eng)
        for i in range(n_req):
            sched.submit([1 + i % 30], max_new_tokens=new_toks)
        stats = sched.run()
        assert stats.completed == n_req
        return stats

    e1, eK = engine(1), engine(CHUNK)
    step_best = max(measure(e1).tokens_per_s for _ in range(trials))
    fused_stats = max((measure(eK) for _ in range(trials)),
                      key=lambda s: s.tokens_per_s)
    fused_best = fused_stats.tokens_per_s

    entry = {
        "decode_chunk": CHUNK,
        "max_batch": 4,
        "requests": n_req,
        "max_new_tokens": new_toks,
        "stepwise_tok_s": round(step_best, 1),
        "fused_tok_s": round(fused_best, 1),
        "fused_syncs_per_token": round(
            fused_stats.chunks / max(fused_stats.emitted_tokens, 1), 4),
        "speedup_x": round(fused_best / max(step_best, 1e-9), 2),
    }

    # quick mode runs a lighter load, so it records its own entry — its
    # tokens/s are not comparable to the full run's
    key = "decode_fastpath_quick" if quick else "decode_fastpath"
    # within-run ratio gate: machine-independent (absolute tok/s would
    # fail on any container slower than the one that wrote the file)
    ok = gate("decode_fused_speedup", fused_best >= 1.2 * step_best,
              f"{entry['speedup_x']}x", ">= 1.2x stepwise")
    _merge_bench(out_path, {key: entry})
    row("decode_fastpath_stepwise", 1e6 / max(step_best, 1e-9),
        f"tok/s={entry['stepwise_tok_s']}")
    row("decode_fastpath_fused", 1e6 / max(fused_best, 1e-9),
        f"tok/s={entry['fused_tok_s']} speedup_x={entry['speedup_x']} "
        f"-> {out_path}")
    return ok


def bench_paged_kv(out_path: str = "BENCH_serving.json",
                   quick: bool = False) -> bool:
    """Paged vs contiguous KV cache on a mixed-length co-batch.

    The contiguous layout charges every occupied slot the full ``max_seq``
    cache, so device KV memory scales with *capacity*; the paged layout
    charges pool pages actually allocated, so it scales with *actual
    context*. On a co-batch of mostly-short prompts next to a long one the
    measured KV bytes per active token should drop by roughly
    ``max_seq / mean_context`` while tokens/s stays put (same kernels,
    same schedule — only the memory layout changed).

    Gate (``--quick``): paged tokens/s >= 0.8x contiguous (0.9x in the
    full run, which uses a heavier load where the chunk-boundary
    translation amortizes further) AND KV bytes per active token reduced
    >= 2x. Ratios, not absolutes, keep the gate machine-independent; the
    best PAIRED ratio keeps it robust to this container's timing swings.
    The quick bound sits at 0.8 with 6 paired trials: running fifth in
    the quick sequence (heap + compile pressure from the earlier
    benches), the unmodified engine's best-paired parity measures
    0.83-0.90 on this container, so 0.85 flaked on noise rather than
    regressions.
    """
    import jax

    from repro.configs import ASSIGNED
    from repro.configs.base import reduce_for_smoke
    from repro.models import build_model
    from repro.serving import ContinuousBatchingScheduler, GenerationEngine

    # a dense no-window config (reduced): the chunk-boundary layout
    # translation is near-fixed cost, so the model must be big enough for
    # chunk compute to dominate — as it does on any real deployment
    cfg = reduce_for_smoke(ASSIGNED["deepseek-67b"])
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    MAX_SEQ, MB, PAGE = 128, 4, 16
    short_len, long_len = 4, 48
    # new_toks spans multiple chunks so the per-tick kv_stats sample
    # catches slots mid-generation (a 1-chunk budget retires within the
    # tick and samples nothing but drained pools)
    n_req, new_toks, trials = (8, 17, 6) if quick else (12, 17, 4)

    def engine(paged):
        eng = GenerationEngine(model, params, max_batch=MB, max_seq=MAX_SEQ,
                               decode_chunk=8, paged=paged, page_size=PAGE)
        warm = ContinuousBatchingScheduler(eng)     # compile prefill buckets
        warm.submit([1] * short_len, max_new_tokens=new_toks)
        warm.submit([1] * long_len, max_new_tokens=new_toks)
        warm.run()
        return eng

    def measure(eng):
        sched = ContinuousBatchingScheduler(eng)
        for i in range(n_req):
            plen = long_len if i % 4 == 0 else short_len
            sched.submit([1 + (i + j) % 30 for j in range(plen)],
                         max_new_tokens=new_toks)
        samples = []
        while sched.has_work():
            sched.tick()
            ks = eng.kv_stats()
            if ks["active_tokens"]:
                samples.append(ks["kv_bytes_per_active_token"])
        stats = sched.stats
        assert stats.completed == n_req, stats
        return stats.tokens_per_s, sum(samples) / max(len(samples), 1)

    # both engines warm up front, then trials INTERLEAVE as (contiguous,
    # paged) pairs and the gate takes the best PAIRED ratio: a parity gate
    # sits at ~1.0, and this container's CPU timing swings +-25% — a real
    # paging regression drags every pair down together, while noise
    # cannot fail all of them
    e_cont, e_paged = engine(False), engine(True)
    cont_tok_s = cont_bpt = paged_tok_s = paged_bpt = 0.0
    ratio = 0.0
    for _ in range(trials):
        tc, bc = measure(e_cont)
        tp, bp = measure(e_paged)
        ratio = max(ratio, tp / max(tc, 1e-9))
        if tc > cont_tok_s:
            cont_tok_s, cont_bpt = tc, bc
        if tp > paged_tok_s:
            paged_tok_s, paged_bpt = tp, bp

    entry = {
        "page_size": PAGE,
        "pool_blocks": MB * MAX_SEQ // PAGE,
        "max_seq": MAX_SEQ,
        "max_batch": MB,
        "requests": n_req,
        "prompt_lens": [long_len, short_len],
        "max_new_tokens": new_toks,
        "contiguous_tok_s": round(cont_tok_s, 1),
        "paged_tok_s": round(paged_tok_s, 1),
        # best paired-trial ratio (not best-of/best-of): the two sides of
        # a pair ran back to back, so the ratio cancels machine drift
        "tok_s_ratio": round(ratio, 3),
        "contiguous_kv_bytes_per_active_token": round(cont_bpt, 1),
        "paged_kv_bytes_per_active_token": round(paged_bpt, 1),
        "kv_bytes_reduction_x": round(cont_bpt / max(paged_bpt, 1e-9), 2),
    }
    key = "paged_kv_quick" if quick else "paged_kv"
    parity = 0.8 if quick else 0.9
    ok_parity = gate("paged_kv_tok_s_ratio",
                     entry["tok_s_ratio"] >= parity,
                     entry["tok_s_ratio"], f">= {parity}x contiguous")
    ok_bytes = gate("paged_kv_bytes_reduction",
                    entry["kv_bytes_reduction_x"] >= 2.0,
                    f"{entry['kv_bytes_reduction_x']}x", ">= 2x")
    ok = ok_parity and ok_bytes
    _merge_bench(out_path, {key: entry})
    row("paged_kv_contiguous", 1e6 / max(cont_tok_s, 1e-9),
        f"tok/s={entry['contiguous_tok_s']} "
        f"kv_bytes/tok={entry['contiguous_kv_bytes_per_active_token']}")
    row("paged_kv_paged", 1e6 / max(paged_tok_s, 1e-9),
        f"tok/s={entry['paged_tok_s']} "
        f"kv_bytes/tok={entry['paged_kv_bytes_per_active_token']} "
        f"ratio={entry['tok_s_ratio']} "
        f"reduction={entry['kv_bytes_reduction_x']}x -> {out_path}")
    return ok


def bench_prefix_cache(out_path: str = "BENCH_serving.json",
                       quick: bool = False) -> bool:
    """Prefix cache on a repeated-system-prompt workload.

    Every request shares one long system prefix and differs only in a
    1-token tail — the agent/RAG serving shape the prefix cache targets.
    Two paired metrics against a cold (prefix-cache-off) twin engine:

    - admission prefill tok/s: prompt tokens admitted per second of
      ``insert_request`` -> first-token sync. Warm admission installs the
      cached prefix pages by reference and force-feeds only the tail, so
      it skips the whole prefix prefill.
    - KV bytes per active token with all requests co-seated: shared
      pages are charged once, so device KV memory stops scaling with the
      number of prefix copies.

    The prefix is 240 tokens (not a chat-sized 48): on this container's
    CPU oracle backend any single dispatch costs at least one full sweep
    of the weights, so a 64-token-bucket prefill and the warm path's one
    fused tail step are both ~one sweep and the speedup would measure
    ~1x regardless of the cache. At 240 tokens prefill is compute-bound
    and the skipped work is visible. Real accelerator deployments sit in
    that regime at ordinary system-prompt lengths.

    Gate (``--quick``): best paired warm/cold prefill tok/s ratio >= 2x
    AND KV bytes per active token reduced >= 2x. Ratios, not absolutes,
    keep the gate machine-independent; paired trials cancel drift.
    """
    import dataclasses

    import jax

    from repro.configs import ASSIGNED
    from repro.configs.base import reduce_for_smoke
    from repro.models import build_model
    from repro.serving import GenerationEngine

    # dense, no sliding window (ring families pad prompts and cannot share
    # pages); scaled up from the smoke config so prefill compute dominates
    # the per-dispatch floor (see docstring)
    cfg = dataclasses.replace(
        reduce_for_smoke(ASSIGNED["llama3-405b"]),
        num_layers=4, d_model=1024, d_ff=4096,
        num_heads=8, num_kv_heads=4, head_dim=128)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    MAX_SEQ, MB, PAGE = 512, 4, 16
    POOL = 68          # 4 cold seats (64) + slack; warm needs far fewer
    PREFIX_LEN, N_PROMPTS = 240, 4
    trials = 2 if quick else 4
    prefix = [1 + (7 * j) % 30 for j in range(PREFIX_LEN)]
    prompts = [prefix + [31 + t] for t in range(N_PROMPTS)]

    def engine(prefixed):
        eng = GenerationEngine(model, params, max_batch=MB, max_seq=MAX_SEQ,
                               decode_chunk=8, paged=True, page_size=PAGE,
                               kv_pool_blocks=POOL, prefix_cache=prefixed)
        # compiles the prefill bucket (and, prefixed, the tail-fill
        # program) and seeds the cache: every measured warm insert hits
        int(eng.insert_request(prompts[0], 0))
        eng.release_slot(0, tokens=prompts[0] if prefixed else None)
        return eng

    def admit_all(eng, prefixed):
        t0 = time.perf_counter()
        for p in prompts:
            int(eng.insert_request(p, 0))         # sync: first token ready
            eng.release_slot(0, tokens=p if prefixed else None)
        dt = time.perf_counter() - t0
        return sum(len(p) for p in prompts) / dt

    # warm up front, then trials interleave as (cold, warm) pairs and the
    # gate takes the best PAIRED ratio (same rationale as bench_paged_kv)
    e_cold, e_warm = engine(False), engine(True)
    cold_tok_s = warm_tok_s = ratio = 0.0
    for _ in range(trials):
        tc = admit_all(e_cold, False)
        tw = admit_all(e_warm, True)
        ratio = max(ratio, tw / max(tc, 1e-9))
        cold_tok_s = max(cold_tok_s, tc)
        warm_tok_s = max(warm_tok_s, tw)

    # co-seat every prompt on both engines: the warm block tables share
    # the prefix pages, the cold ones hold private copies
    for i, p in enumerate(prompts):
        int(e_cold.insert_request(p, i))
        int(e_warm.insert_request(p, i))
    cold_bpt = e_cold.kv_stats()["kv_bytes_per_active_token"]
    warm_bpt = e_warm.kv_stats()["kv_bytes_per_active_token"]
    pstats = e_warm.prefix_stats()
    for i, p in enumerate(prompts):
        e_cold.release_slot(i)
        e_warm.release_slot(i, tokens=p)

    entry = {
        "model": "llama3-405b (4L d1024 bench scale)",
        "page_size": PAGE,
        "pool_blocks": POOL,
        "max_seq": MAX_SEQ,
        "max_batch": MB,
        "prefix_tokens": PREFIX_LEN,
        "tail_tokens": 1,
        "prompts": N_PROMPTS,
        "cold_prefill_tok_s": round(cold_tok_s, 1),
        "warm_prefill_tok_s": round(warm_tok_s, 1),
        # best paired-trial ratio — the two sides ran back to back
        "prefill_tok_s_ratio": round(ratio, 3),
        "cold_kv_bytes_per_active_token": round(cold_bpt, 1),
        "warm_kv_bytes_per_active_token": round(warm_bpt, 1),
        "kv_bytes_reduction_x": round(cold_bpt / max(warm_bpt, 1e-9), 2),
        "hit_tokens": pstats["hit_tokens"],
        "shared_pages": pstats["shared_pages"],
        "cow_copies": pstats["cow_copies"],
    }
    key = "prefix_cache_quick" if quick else "prefix_cache"
    ok_prefill = gate("prefix_cache_prefill_ratio",
                      entry["prefill_tok_s_ratio"] >= 2.0,
                      f"{entry['prefill_tok_s_ratio']}x", ">= 2x cold")
    ok_bytes = gate("prefix_cache_bytes_reduction",
                    entry["kv_bytes_reduction_x"] >= 2.0,
                    f"{entry['kv_bytes_reduction_x']}x", ">= 2x")
    ok = ok_prefill and ok_bytes
    _merge_bench(out_path, {key: entry})
    row("prefix_cache_cold", 1e6 / max(cold_tok_s, 1e-9),
        f"prefill_tok/s={entry['cold_prefill_tok_s']} "
        f"kv_bytes/tok={entry['cold_kv_bytes_per_active_token']}")
    row("prefix_cache_warm", 1e6 / max(warm_tok_s, 1e-9),
        f"prefill_tok/s={entry['warm_prefill_tok_s']} "
        f"ratio={entry['prefill_tok_s_ratio']} "
        f"reduction={entry['kv_bytes_reduction_x']}x -> {out_path}")
    return ok


def bench_streaming(out_path: str = "BENCH_serving.json",
                    quick: bool = False) -> bool:
    """The streaming acceptance scenario: for a long (64-token) generation,
    the SSE stream's first ``token`` event must arrive well before the
    full completion — streamed TTFT < 0.5x the non-streaming latency
    (best-of-N on both sides; the ratio keeps the gate machine-independent).
    Also records the streamed total so the overhead of the event bridge is
    visible next to the plain predict path."""

    import repro.core.assets  # noqa: F401 — populate the exchange
    from repro.core import BatchedService, EXCHANGE

    new_toks = 64
    inp = {"text": "stream benchmark", "max_new_tokens": new_toks}
    svc = BatchedService(EXCHANGE.get("qwen3-4b").build(max_seq=256,
                                                        max_batch=2),
                         batch_window_s=0.0)
    trials = 2 if quick else 3
    try:
        # one full-budget call compiles prefill + every chunk program the
        # 64-token budget decomposes into
        warm = svc.predict(inp)
        assert warm["status"] == "ok", warm

        full_best = streamed_best = ttft_best = None
        for _ in range(trials):
            t0 = time.perf_counter()
            env = svc.predict(inp)
            full = time.perf_counter() - t0
            assert env["status"] == "ok", env
            full_best = min(full, full_best or full)

            t0 = time.perf_counter()
            ttft = total = None
            for ev in svc.predict_stream(inp):
                if ev.event == "token" and ttft is None:
                    ttft = time.perf_counter() - t0
                elif ev.event == "done":
                    total = time.perf_counter() - t0
                    assert (ev.data["usage"]["completion_tokens"]
                            == new_toks), ev.data
            assert ttft is not None and total is not None
            ttft_best = min(ttft, ttft_best or ttft)
            streamed_best = min(total, streamed_best or total)
    finally:
        svc.close()

    ratio = ttft_best / max(full_best, 1e-9)
    ok = gate("streaming_ttft_ratio", ratio < 0.5,
              round(ratio, 3), "< 0.5x full completion")
    entry = {
        "model": "qwen3-4b",
        "max_new_tokens": new_toks,
        "full_latency_ms": round(full_best * 1e3, 1),
        "streamed_ttft_ms": round(ttft_best * 1e3, 1),
        "streamed_total_ms": round(streamed_best * 1e3, 1),
        "ttft_ratio": round(ratio, 3),
    }
    _merge_bench(out_path, {"streaming": entry})
    row("streaming_full_completion", full_best * 1e6,
        f"latency={entry['full_latency_ms']}ms")
    row("streaming_ttft", ttft_best * 1e6,
        f"ttft={entry['streamed_ttft_ms']}ms "
        f"ratio={entry['ttft_ratio']} -> {out_path}")
    return ok


def bench_observability(out_path: str = "BENCH_serving.json",
                        quick: bool = False) -> bool:
    """Fused decode throughput with request-lifecycle tracing on vs off.

    Tracing claims zero new host syncs: every span stamp lands at a point
    the scheduler already touches host state (submit, admission, the
    tick's single sync, retire), so its cost is a few list appends per
    CHUNK tokens — not per token. This bench holds it to that claim on
    the fused path, where one extra sync per chunk would be immediately
    visible in tokens/s.

    Gate (``--quick``): traced tokens/s >= 0.95x untraced, best PAIRED
    ratio across trials (ratio, not absolutes, keeps the gate
    machine-independent; pairing absorbs this container's timing swings).
    """
    import jax

    from repro.configs import CONFIGS
    from repro.models import build_model
    from repro.serving import ContinuousBatchingScheduler, GenerationEngine
    from repro.serving.tracing import Tracer

    cfg = CONFIGS["max-sentiment"]     # dispatch-bound regime: the worst
    model = build_model(cfg)           # case for any per-chunk overhead
    params = model.init(jax.random.PRNGKey(0))
    CHUNK = 16
    n_req, new_toks, trials = (8, CHUNK + 1, 4) if quick \
        else (16, 2 * CHUNK + 1, 5)

    eng = GenerationEngine(model, params, max_batch=4, max_seq=64,
                           decode_chunk=CHUNK)
    warm = ContinuousBatchingScheduler(eng)     # compile prefill + chunks
    warm.submit([1], max_new_tokens=2 * CHUNK)
    warm.run()

    def measure(tracer):
        sched = ContinuousBatchingScheduler(eng, tracer=tracer)
        for i in range(n_req):
            sched.submit([1 + i % 30], max_new_tokens=new_toks)
        stats = sched.run()
        assert stats.completed == n_req
        return stats.tokens_per_s

    off_best = on_best = best_ratio = 0.0
    for _ in range(trials):
        off = measure(None)                     # paired: same heap/thermal
        on = measure(Tracer(capacity=2 * n_req))
        off_best, on_best = max(off_best, off), max(on_best, on)
        best_ratio = max(best_ratio, on / max(off, 1e-9))

    entry = {
        "decode_chunk": CHUNK,
        "requests": n_req,
        "max_new_tokens": new_toks,
        "untraced_tok_s": round(off_best, 1),
        "traced_tok_s": round(on_best, 1),
        "traced_ratio": round(best_ratio, 3),
    }
    ok = gate("observability_traced_ratio", best_ratio >= 0.95,
              round(best_ratio, 3), ">= 0.95x untraced")
    key = "observability_quick" if quick else "observability"
    _merge_bench(out_path, {key: entry})
    row("observability_untraced", 1e6 / max(off_best, 1e-9),
        f"tok/s={entry['untraced_tok_s']}")
    row("observability_traced", 1e6 / max(on_best, 1e-9),
        f"tok/s={entry['traced_tok_s']} "
        f"ratio={entry['traced_ratio']} -> {out_path}")
    return ok


def bench_robustness(out_path: str = "BENCH_serving.json",
                     quick: bool = False) -> bool:
    """The fault-tolerance acceptance scenario: chaos vs fault-free twin.

    The chaos run arms the deterministic fault-injection plane at ~5%
    per-chunk engine faults (seeded, so every run injects the same
    schedule). Each fault quarantines one victim slot mid-generation; the
    service's safe-retry path must resubmit it and — because decode is
    greedy at temperature 0 — reproduce the exact fault-free tokens.

    Gates (all through :func:`gate`): completion >= 99% of requests,
    token identity on every completed request vs the fault-free twin,
    and goodput (ok-tokens/s) >= 0.9x fault-free, best PAIRED ratio
    across trials (pairing cancels this container's timing swings; a
    real retry-path regression drags every pair down together).
    """
    import repro.core.assets  # noqa: F401 — populate the exchange
    from repro.core import BatchedService, EXCHANGE

    # enough requests that a retried one re-joins a still-busy batch
    # instead of decoding alone at the tail (goodput would then measure
    # lost parallelism, not retry overhead)
    new_toks = 8
    n_req, trials = (16, 3) if quick else (24, 3)
    chaos_spec = {"chunk_rate": 0.05, "seed": 7}
    wrapper = EXCHANGE.get("qwen3-4b").build(max_seq=64, max_batch=4)
    inputs = [{"text": f"chaos {i}", "max_new_tokens": new_toks}
              for i in range(n_req)]

    def run(faults):
        svc = BatchedService(wrapper, batch_window_s=0.0, faults=faults,
                             max_retries=5, retry_backoff_s=0.01)
        try:
            warm = svc.predict({"text": "warm", "max_new_tokens": new_toks})
            assert warm["status"] == "ok", warm
            t0 = time.perf_counter()
            envs = svc.predict_batch(inputs)
            wall = time.perf_counter() - t0
            texts = [e["predictions"][0].get("generated_text")
                     if e.get("status") == "ok" else None for e in envs]
            ok_toks = sum(new_toks for t in texts if t is not None)
            rob = svc.stats()["robustness"]
        finally:
            svc.close()
        return texts, ok_toks / max(wall, 1e-9), rob

    # correctness metrics take the WORST trial (they must hold every
    # time); the goodput ratio takes the best paired trial (timing noise)
    completion = identity = 1.0
    goodput_ratio = 0.0
    injected = {}
    for _ in range(trials):             # paired: fault-free, then chaos
        free_texts, free_goodput, _ = run(None)
        chaos_texts, chaos_goodput, rob = run(chaos_spec)
        done = sum(1 for t in chaos_texts if t is not None)
        same = sum(1 for tc, tf in zip(chaos_texts, free_texts)
                   if tc is not None and tc == tf)
        completion = min(completion, done / n_req)
        identity = min(identity, same / n_req)
        goodput_ratio = max(goodput_ratio,
                            chaos_goodput / max(free_goodput, 1e-9))
        injected = rob

    entry = {
        "model": "qwen3-4b",
        "requests": n_req,
        "max_new_tokens": new_toks,
        "chunk_fault_rate": chaos_spec["chunk_rate"],
        "completion_rate": round(completion, 4),
        "token_identity_rate": round(identity, 4),
        "goodput_ratio": round(goodput_ratio, 3),
        "engine_faults": injected.get("engine_faults"),
        "retries": injected.get("retries"),
        "engine_rebuilds": injected.get("engine_rebuilds"),
    }
    key = "robustness_quick" if quick else "robustness"
    ok_comp = gate("robustness_completion", completion >= 0.99,
                   round(completion, 4), ">= 0.99")
    ok_ident = gate("robustness_token_identity", identity >= 0.99,
                    round(identity, 4), ">= 0.99 (greedy replay exact)")
    # quick margin 0.85 vs 0.9 full (same precedent as the paged-kv quick
    # gate: a 16-request wall clock on this container swings the paired
    # ratio by ~5% on noise alone; the full run's 24x3 holds 0.9)
    good_bound = 0.85 if quick else 0.9
    ok_good = gate("robustness_goodput", goodput_ratio >= good_bound,
                   f"{entry['goodput_ratio']}x",
                   f">= {good_bound}x fault-free")
    _merge_bench(out_path, {key: entry})
    row("robustness_chaos", 0.0,
        f"completion={entry['completion_rate']} "
        f"identity={entry['token_identity_rate']} "
        f"goodput={entry['goodput_ratio']}x "
        f"faults={entry['engine_faults']} retries={entry['retries']} "
        f"-> {out_path}")
    return ok_comp and ok_ident and ok_good


def bench_fleet(out_path: str = "BENCH_serving.json",
                quick: bool = False) -> bool:
    """Replica-group rps scaling: 1 vs 2 replicas under stall faults.

    Runs in a subprocess with ``--xla_force_host_platform_device_count=8``
    so the 2-replica placement lands on real (forced) multi-device slices
    — the parent process already initialized jax with this container's
    single device and cannot re-init. The harness interleaves paired
    1-vs-2-replica trials; see ``fleet_harness.py`` for the scenario
    design (why the gate lives on the stall scenario, not the fault-free
    one, on a 1-core container).

    Gate (``--quick``): stall-scenario rps at 2 replicas >= 1.5x the
    1-replica rps, best paired trial.

    The child runs on the CPU backend and its numbers are CPU numbers. On
    a machine with an accelerator the parent already holds the device
    (earlier benches initialised JAX), so a child could only fall back to
    the CPU: this refuses instead of reporting that as a device result.
    """
    import subprocess
    import sys

    import jax

    platform = jax.default_backend()
    if platform != "cpu":
        raise SystemExit(
            f"bench_fleet: this process holds the {platform} device, and "
            "its child would run on the CPU; the fleet scaling harness is "
            "a CPU behaviour check (run it with JAX_PLATFORMS=cpu)")
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + " --xla_force_host_platform_device_count=8").strip()
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (os.path.join(here, "..", "src"),
                    env.get("PYTHONPATH")) if p)
    cmd = [sys.executable, os.path.join(here, "fleet_harness.py")]
    if quick:
        cmd.append("--quick")
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          timeout=900)
    lines = [ln for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    if proc.returncode != 0 or not lines:
        gate("fleet_rps_scaling", False,
             f"harness exit {proc.returncode}", ">= 1.5x (harness failed)")
        row("fleet_rps_scaling", 0.0,
            f"harness failed: {proc.stderr.strip()[-200:]}")
        return False
    rep = json.loads(lines[-1])
    ratio = rep["stall"]["ratio"]
    entry = {
        "platform": "cpu",
        "devices": rep["devices"],
        "requests": rep["requests"],
        "stall_rps_1_replica": rep["stall"]["rps_1_replica"],
        "stall_rps_2_replicas": rep["stall"]["rps_2_replicas"],
        "stall_ratio": ratio,
        "plain_ratio": rep["plain"]["ratio"],
        "slices": rep["stall"]["slices"],
    }
    key = "fleet_quick" if quick else "fleet"
    ok = gate("fleet_rps_scaling", ratio >= 1.5, f"{ratio}x",
              ">= 1.5x rps at 2 replicas (stall scenario)")
    _merge_bench(out_path, {key: entry})
    row("fleet_rps_scaling", 0.0,
        f"stall={ratio}x plain={entry['plain_ratio']}x "
        f"devices={entry['devices']} slices={entry['slices']} "
        f"-> {out_path}")
    return ok


def bench_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 4, 256, 64)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(1, 2, 256, 64)), jnp.float32)
    f_ref = jax.jit(lambda q, k, v: ref.attention_ref(q, k, v))
    f_ref(q, k, v).block_until_ready()
    t_ref = _time(lambda: f_ref(q, k, v).block_until_ready())
    with ops.use_backend("interpret"):
        out = ops.flash_attention(q, k, v)
    ok = bool(jnp.allclose(out, ref.attention_ref(q, k, v), atol=2e-5))
    row("kernel_flash_attention_oracle", t_ref, f"interpret_allclose={ok}")

    a = jnp.asarray(rng.uniform(0.5, 0.99, (1, 256, 512)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(1, 256, 512)), jnp.float32)
    f_rg = jax.jit(ref.rglru_ref)
    f_rg(a, b).block_until_ready()
    t_rg = _time(lambda: f_rg(a, b).block_until_ready())
    with ops.use_backend("interpret"):
        h, _ = ops.rglru_scan(a, b)
    ok = bool(jnp.allclose(h, ref.rglru_ref(a, b), atol=1e-5))
    row("kernel_rglru_oracle", t_rg, f"interpret_allclose={ok}")

    x = jnp.asarray(rng.normal(size=(4, 128, 256)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(4, 256, 512)), jnp.float32)
    f_gmm = jax.jit(ref.gmm_ref)
    f_gmm(x, w).block_until_ready()
    t_g = _time(lambda: f_gmm(x, w).block_until_ready())
    with ops.use_backend("interpret"):
        out = ops.gmm(x, w)
    ok = bool(jnp.allclose(out, ref.gmm_ref(x, w), atol=2e-4))
    row("kernel_gmm_oracle", t_g, f"interpret_allclose={ok}")


def bench_roofline_terms():
    """Surface the dry-run roofline headlines (full table: EXPERIMENTS.md)."""
    for records in ("experiments/dryrun_opt", "experiments/dryrun_baseline",
                    "experiments/dryrun"):
        if os.path.isdir(records):
            break
    else:
        row("roofline_records", 0, "missing (run launch/dryrun --sweep)")
        return
    try:
        from repro.launch.roofline import load_rows
        rows = [r for r in load_rows(records, "single") if r.status == "ok"]
        for r in rows:
            if (r.arch, r.shape) in (("llama3-405b", "train_4k"),
                                     ("llama3-405b", "decode_32k"),
                                     ("rwkv6-7b", "train_4k")):
                row(f"roofline_{r.arch}_{r.shape}", r.step_s * 1e6,
                    f"dominant={r.dominant} useful={r.useful_ratio:.2f} "
                    f"fits={r.fits}")
        row("roofline_pairs_ok", len(rows), f"records={records}")
    except Exception as e:  # records may be mid-sweep
        row("roofline_records", 0, f"unreadable: {e}")


def main(argv=None) -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="run only the gated smokes (QoS overload, fused "
                         "decode, streaming TTFT, paged KV, prefix cache, "
                         "tracing overhead, fault-injection robustness, "
                         "fleet rps scaling — <60s each); exit nonzero "
                         "if any gate fails, "
                         "printing EVERY failing gate with measured vs "
                         "bound")
    ap.add_argument("--chaos-quick", action="store_true",
                    help="run ONLY the fault-injection robustness smoke "
                         "(chaos vs fault-free twin); exit nonzero if "
                         "completion, token identity, or goodput regresses")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print("name,us_per_call,derived")
    if args.quick or args.chaos_quick:
        smokes = [("robustness", bench_robustness)] if args.chaos_quick \
            else [("qos", bench_qos_overload),
                  ("decode", bench_decode_fastpath),
                  ("streaming", bench_streaming),
                  ("paged-kv", bench_paged_kv),
                  ("prefix-cache", bench_prefix_cache),
                  ("observability", bench_observability),
                  ("robustness", bench_robustness),
                  ("fleet", bench_fleet)]
        for name, fn in smokes:
            ok = fn(quick=True)
            print(f"# quick {name} smoke: {'ok' if ok else 'REGRESSION'}",
                  flush=True)
        print_gate_report()
        raise SystemExit(1 if failing_gates() else 0)
    # decode_fastpath first: it measures dispatch overhead, which later
    # benches inflate (heavy compiles + heap pressure skew its timings)
    bench_decode_fastpath()
    bench_wrapper_overhead()
    bench_registry()
    bench_deploy_latency()
    bench_api_roundtrip()
    bench_serving_throughput()
    bench_serving_http()
    bench_qos_overload()
    bench_streaming()
    bench_paged_kv()
    bench_prefix_cache()
    bench_observability()
    bench_robustness()
    bench_fleet()
    bench_kernels()
    bench_roofline_terms()
    print_gate_report()     # informational in the full run (exit stays 0)
    print(f"# {len(ROWS)} benchmarks complete", flush=True)


if __name__ == "__main__":
    main()
