"""On-chip smoke test: qwen3-4b at full width through the MAX REST path.

    python chip_smoke.py              # one TPU chip
    python chip_smoke.py --chips 4    # four one-chip replicas vs one replica

The default run starts ``MAXServer`` in this process and drives it over
HTTP on an ephemeral port: v2 predicts, an SSE stream and a v1 predict
against a batched contiguous-cache deployment, then greedy cold/warm
requests against a paged deployment with the prefix cache on. It checks
that the served decode program contains a Pallas kernel, and that the
served logits after prefill and after decode steps agree with the jnp
oracle on the same bf16 weights.

``--chips 4`` runs only the multi-chip path: four replicas behind the
front door (``replicas: 4, mesh_slice: auto``), each with its weights and
KV cache on its own chip, and compares their greedy output with one
replica's.

Weights are random, made from a seed; nothing is downloaded. Any failed
check raises and exits nonzero. Only a run in which every phase passed
prints the last line ``{"ok": true, "device": {...}}``. With no TPU the
script exits nonzero before building anything. The server, its HTTP
client and the engine share one process, because a process that has
initialised JAX holds the chip.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MODEL = "qwen3-4b"
# Sized by AOT-compiling the served programs for one v5e chip: bf16
# weights 7.49 GiB + a 2.25 GiB KV cache; the fused decode chunk peaks at
# 13.05 GiB (it writes a new cache beside its input) of the 15.75 GiB.
# max_seq stays below the config's 4096 sliding window, so the cache is
# linear (pageable, prefix-cacheable), not a ring.
MAX_SEQ = 2048
MAX_BATCH = 8
# Served path (Pallas kernels) vs the jnp oracle on the same bf16 weights.
# The two round activations to bf16 at different points, and the error
# grows with depth: a 36-layer d=512 reduction differed by 0.04 with
# logits of std 0.44 (CPU, interpret mode). Full-width logits have std ~1
# (tied embeddings, init std 0.02, d 2560) and reach about 12. A wrong
# mask, block index or softmax moves logits by their whole scale.
LOGITS_TOL = 0.5
REQUEST_TIMEOUT_S = 900


class SmokeError(RuntimeError):
    """A phase of the smoke test did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeError(msg)


# -- HTTP client ------------------------------------------------------------

def _request(url: str, method: str, body=None, headers=None):
    data = json.dumps(body).encode() if body is not None else None
    hdrs = {"Content-Type": "application/json", **(headers or {})}
    req = urllib.request.Request(url, data, hdrs, method=method)
    try:
        with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def post(base: str, path: str, body, headers=None):
    return _request(base + path, "POST", body, headers)


def get(base: str, path: str):
    return _request(base + path, "GET")


def delete(base: str, path: str):
    return _request(base + path, "DELETE")


def stream(base: str, path: str, body, headers=None):
    """POST an SSE request; returns (token ids, done event data)."""
    hdrs = {"Content-Type": "application/json", **(headers or {})}
    req = urllib.request.Request(base + path, json.dumps(body).encode(),
                                 hdrs, method="POST")
    events, ev = [], {}
    with urllib.request.urlopen(req, timeout=REQUEST_TIMEOUT_S) as r:
        for raw in r:
            line = raw.decode().rstrip("\n")
            if not line:
                if ev:
                    events.append(ev)
                    ev = {}
                continue
            key, _, val = line.partition(": ")
            ev[key] = json.loads(val) if key == "data" else val
    check(bool(events) and events[-1].get("event") == "done",
          f"stream {path} did not end with a done event: {events[-1:]}")
    ids = [t for e in events[:-1] if e.get("event") == "token"
           for t in e["data"]["token_ids"]]
    return ids, events[-1]["data"]


def concurrently(fns):
    """Run the callables on threads; return their results in order, or
    raise the first exception one of them raised."""
    results, errors = [None] * len(fns), []

    def run(i, fn):
        try:
            results[i] = fn()
        except BaseException as e:   # re-raised on the main thread below
            errors.append(e)

    threads = [threading.Thread(target=run, args=(i, fn))
               for i, fn in enumerate(fns)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(REQUEST_TIMEOUT_S)
        check(not t.is_alive(), "a client thread did not finish")
    if errors:
        raise errors[0]
    return results


def deploy(base: str, body) -> dict:
    code, env = post(base, f"/v2/model/{MODEL}/deploy", body)
    check(code == 200 and env.get("status") == "ok",
          f"deploy {body} failed: {code} {env}")
    return env


def undeploy(base: str) -> None:
    code, env = delete(base, f"/v2/model/{MODEL}")
    check(code == 200, f"undeploy failed: {code} {env}")
    gc.collect()       # engines hold reference cycles through their jits


# -- phases -----------------------------------------------------------------

def phase_batched(base: str) -> None:
    """Batched deployment, default (contiguous) KV layout: concurrent v2
    predicts, one SSE stream, one v1 predict."""
    env = deploy(base, {"service": "batched"})
    check(env["service"] == "batched" and not env["kv_cache"]["paged"],
          f"expected a batched contiguous deployment: {env}")
    # compile the prefill bucket and decode chunks before the burst, so
    # the burst is admitted together
    code, env = post(base, f"/v2/model/{MODEL}/predict",
                     {"input": {"text": "request w: tell me about chips",
                                "max_new_tokens": 16}})
    check(code == 200 and env.get("status") == "ok", f"warm-up: {env}")

    budgets = [16, 24, 32, 40, 48, 64]

    def v2(i, n):
        return post(base, f"/v2/model/{MODEL}/predict",
                    {"input": {"text": f"request {i}: tell me about chips",
                               "max_new_tokens": n}})

    results = concurrently([lambda i=i, n=n: v2(i, n)
                            for i, n in enumerate(budgets)])
    for n, (code, env) in zip(budgets, results):
        check(code == 200 and env.get("status") == "ok",
              f"v2 predict failed: {code} {env}")
        got = env["predictions"][0]["generated_tokens"]
        check(got == n, f"v2 predict generated {got} tokens, asked {n}")

    ids, done = stream(base, f"/v2/model/{MODEL}/stream",
                       {"input": {"text": "stream this", "max_new_tokens": 32}})
    check(done["envelope"]["status"] == "ok", f"stream envelope: {done}")
    check(len(ids) == 32 and done["usage"]["completion_tokens"] == 32,
          f"stream gave {len(ids)} ids, usage {done['usage']}")

    code, env = post(base, f"/model/{MODEL}/predict",
                     {"input": {"text": "a v1 request", "max_new_tokens": 16}})
    check(code == 200 and env.get("status") == "ok", f"v1 predict: {env}")
    check(env["predictions"][0]["generated_tokens"] == 16,
          f"v1 predict: {env['predictions']}")

    code, stats = get(base, f"/v2/model/{MODEL}/stats")
    svc = stats["service"]
    check(code == 200 and svc["max_batch_seen"] >= 2,
          f"concurrent predicts were not batched: {svc}")
    print(f"[batched] {len(budgets)} concurrent v2 + 1 SSE + 1 v1 ok; "
          f"max_batch_seen={svc['max_batch_seen']} "
          f"mean_batch_size={svc['mean_batch_size']}", flush=True)


def phase_prefix(base: str) -> None:
    """Paged deployment with the prefix cache: a warm replay of a prompt
    is token-identical to its cold run, and the cache registers a hit."""
    env = deploy(base, {"service": "batched", "paged": True,
                        "prefix_cache": True})
    check(env["kv_cache"]["paged"] and "prefix_cache" in env["kv_cache"],
          f"expected a paged prefix-cached deployment: {env['kv_cache']}")
    shared = ("You are a helpful assistant serving answers about TPUs. "
              "Answer briefly and precisely. ")
    first = {"input": {"text": shared + "What is HBM?",
                       "max_new_tokens": 24}}
    second = {"input": {"text": shared + "What is a systolic array?",
                        "max_new_tokens": 24}}
    path = f"/v2/model/{MODEL}/stream"
    cold, _ = stream(base, path, first)
    other, _ = stream(base, path, second)
    warm, _ = stream(base, path, first)
    check(len(cold) == 24 and len(other) == 24,
          f"prefix phase lengths {len(cold)}, {len(other)}")
    check(warm == cold, f"warm output differs from cold: {warm} vs {cold}")
    code, stats = get(base, f"/v2/model/{MODEL}/stats")
    pc = stats["service"]["kv_cache"]["prefix_cache"]
    check(pc["hits"] >= 1 and pc["hit_tokens"] > 0,
          f"prefix cache registered no hit: {pc}")
    print(f"[prefix] warm == cold ({len(cold)} tokens); prefix cache "
          f"hits={pc['hits']} hit_tokens={pc['hit_tokens']}", flush=True)


def served_engine(manager):
    return manager.get(MODEL).wrapper.engine


def check_kernels(engine, marker: str = "tpu_custom_call") -> None:
    """Every decode chunk program the engine compiled carries a Pallas
    kernel (``marker`` in the compiled HLO)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    check(bool(engine._chunk_jit), "the engine compiled no decode chunk")
    B = engine.max_batch
    for k, fn in sorted(engine._chunk_jit.items()):
        text = fn.lower(
            engine.params, engine._cache, engine._next_tok,
            jax.random.PRNGKey(0), jnp.zeros((B,), jnp.float32),
            jnp.asarray(np.zeros((B,), np.int32)),
            jnp.asarray(np.zeros((B,), bool))).compile().as_text()
        check(marker in text,
              f"decode chunk k={k} has no Pallas kernel ({marker})")
    print(f"[kernels] decode chunks {sorted(engine._chunk_jit)} each "
          f"contain {marker}", flush=True)


def oracle_logits_diff(engine, steps: int = 4) -> float:
    """Max |served - oracle| over the logits after prefill and after each
    of ``steps`` decode steps, on the engine's own weights. The served
    side traces with the platform's kernels; the oracle side with the
    jnp reference (``use_backend("ref")``), in fresh jits so neither
    reuses the other's trace."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.data.tokenizer import TOKENIZER
    from repro.kernels import ops

    tokens = TOKENIZER.encode("Compare the served logits with the oracle.")
    prompt, feed = tokens[:-steps], tokens[-steps:]
    bucket = 16
    while bucket < len(prompt):
        bucket *= 2
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :len(prompt)] = prompt
    batch = {"tokens": jnp.asarray(padded),
             "prompt_lengths": jnp.asarray([len(prompt)], np.int32)}
    model, params = engine.model, engine.params

    def run():
        prefill = jax.jit(lambda p, b: model.prefill(
            p, b, cache_len=engine.max_seq))
        decode = jax.jit(lambda p, c, t: model.decode_step(p, c, t))
        logits, cache = prefill(params, batch)
        out = [logits]
        for t in feed:
            logits, cache = decode(params, cache,
                                   jnp.asarray([t], jnp.int32))
            out.append(logits)
        V = engine.cfg.vocab_size
        return np.stack([np.asarray(x, np.float32)[0, :V] for x in out])

    served = run()
    with ops.use_backend("ref"):
        oracle = run()
    check(bool(np.isfinite(served).all()), "served logits are not finite")
    diff = float(np.abs(served - oracle).max())
    print(f"[oracle] backend {ops.get_backend()} vs ref: max |logit diff| "
          f"{diff:.6f} over prefill + {steps} decode steps "
          f"(max |logit| {float(np.abs(oracle).max()):.4f}, "
          f"tolerance {LOGITS_TOL})", flush=True)
    return diff


def replica_devices(manager):
    """Per replica: the set of devices its params and KV cache live on."""
    import jax
    out = []
    for rep in manager.get(MODEL).service._replicas:
        eng = rep.service.wrapper.engine
        devs = {d for leaf in jax.tree.leaves((eng.params, eng._cache))
                for d in leaf.devices()}
        out.append(devs)
    return out


def home_clients(n: int):
    """One client id per replica r0..r{n-1}: the front door routes a
    client to the replica with the highest rendezvous score, so four
    clients chosen this way put work on all four chips."""
    from repro.core.fleet import _rendezvous_score
    names = [f"r{i}" for i in range(n)]
    picked, i = {}, 0
    while len(picked) < n:
        client = f"client-{i}"
        home = max(names, key=lambda r: _rendezvous_score(client, r))
        picked.setdefault(home, client)
        i += 1
    return [picked[r] for r in names]


def fleet_streams(base: str, prompts, clients):
    """Greedy SSE requests, all at once, client ``i % len(clients)``."""
    return concurrently([
        lambda i=i, p=p: stream(
            base, f"/v2/model/{MODEL}/stream",
            {"input": {"text": p, "max_new_tokens": 24}},
            headers={"X-MAX-Client": clients[i % len(clients)]})[0]
        for i, p in enumerate(prompts)])


def phase_fleet(base: str, manager, n_chips: int) -> None:
    env = deploy(base, {"service": "batched", "replicas": n_chips,
                        "mesh_slice": "auto"})
    check(env["replicas"] == n_chips, f"fleet deploy: {env}")
    devs = replica_devices(manager)
    check(all(len(d) == 1 for d in devs),
          f"a replica spans several devices: {devs}")
    flat = [next(iter(d)) for d in devs]
    check(len(set(flat)) == n_chips,
          f"replicas share devices: {[str(d) for d in flat]}")
    prompts = [f"fleet request {i}: describe replica {i % n_chips}"
               for i in range(2 * n_chips)]
    clients = home_clients(n_chips)
    fleet_ids = fleet_streams(base, prompts, clients)
    # state still on one device each after serving
    check(replica_devices(manager) == devs, "replica state moved device")
    code, stats = get(base, f"/v2/model/{MODEL}/stats")
    served = {n: r["completed"] for n, r in
              stats["service"]["per_replica"].items()}
    check(all(v >= 1 for v in served.values()),
          f"a replica served nothing: {served}")
    print(f"[fleet] {n_chips} replicas on {[str(d) for d in flat]}; "
          f"completed per replica {served}", flush=True)
    undeploy(base)

    deploy(base, {"service": "batched", "replicas": 1})
    single_ids = fleet_streams(base, prompts, clients)
    check(all(len(x) == 24 for x in fleet_ids + single_ids),
          "a fleet request came back short")
    check(fleet_ids == single_ids,
          "fleet output differs from one replica's output")
    print(f"[fleet] {len(prompts)} greedy requests token-identical to one "
          "replica", flush=True)
    undeploy(base)


# -- entry point ------------------------------------------------------------

class CompileCounter:
    """Counts backend compiles (persistent-cache hits included) and their
    seconds through JAX's monitoring events. Replica workers compile on
    their own threads, hence the lock."""

    def __init__(self):
        import jax
        self.count, self.seconds, self.cache_hits = 0, 0.0, 0
        self._lock = threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.count += 1
                self.seconds += secs

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


def run(chips: int, build_kw: dict,
        kernel_marker: str = "tpu_custom_call") -> dict:
    """Serve ``MODEL`` built with ``build_kw`` and run the phases; returns
    each phase's wall seconds. Raises SmokeError on a failed check."""
    import repro.core.assets  # noqa: F401 — populate the exchange
    from repro.core import MAXServer
    from repro.kernels import ops

    times = {}
    with MAXServer(build_kw=build_kw, auto_deploy=False) as server:
        base, manager = server.url, server.manager
        print(f"MAXServer at {base}; kernel backend {ops.get_backend()}",
              flush=True)

        def timed(name, fn, *a):
            t0 = time.perf_counter()
            out = fn(*a)
            times[name] = round(time.perf_counter() - t0, 3)
            return out

        if chips > 1:
            timed("fleet", phase_fleet, base, manager, chips)
            return times
        timed("batched", phase_batched, base)
        eng = served_engine(manager)
        cfg = eng.cfg
        print(f"[batched] served {cfg.name}: {cfg.num_layers} layers, "
              f"d_model {cfg.d_model}, vocab {cfg.vocab_size}, weights "
              f"{jax_dtype(eng.params)}", flush=True)
        if not build_kw.get("smoke", True):
            check(cfg.name == MODEL and cfg.num_layers == 36
                  and cfg.d_model == 2560,
                  f"not full width: {cfg.num_layers}L d={cfg.d_model}")
        if kernel_marker:
            timed("kernels", check_kernels, eng, kernel_marker)
        diff = timed("oracle", oracle_logits_diff, eng)
        check(diff <= LOGITS_TOL,
              f"served logits differ from the oracle by {diff} "
              f"(tolerance {LOGITS_TOL})")
        del eng
        timed("undeploy", undeploy, base)
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        print(f"bytes_in_use after undeploy: {stats.get('bytes_in_use')}",
              flush=True)
        timed("prefix", phase_prefix, base)
        if kernel_marker:
            timed("kernels_paged", check_kernels, served_engine(manager),
                  kernel_marker)
        undeploy(base)
    return times


def jax_dtype(tree) -> str:
    import jax
    return ",".join(sorted({str(x.dtype) for x in jax.tree.leaves(tree)}))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the four-replica fleet path")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"device_kind={dev.device_kind} count={len(devices)} "
          f"jax={jax.__version__}", flush=True)

    from repro.launch.compile_cache import use_compile_cache
    print(f"compile cache: {use_compile_cache()}", flush=True)
    compiles = CompileCounter()

    build_kw = {"smoke": False, "max_seq": MAX_SEQ, "max_batch": MAX_BATCH}
    times = run(args.chips, build_kw)
    print(f"phase seconds: {json.dumps(times)}", flush=True)
    print(f"compiles={compiles.count} compile_seconds="
          f"{compiles.seconds:.3f} persistent_cache_hits="
          f"{compiles.cache_hits}", flush=True)
    for d in devices[:args.chips]:
        print(f"{d}: peak_bytes_in_use="
              f"{d.memory_stats()['peak_bytes_in_use']}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
