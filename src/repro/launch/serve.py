"""Serving launcher: start the MAX REST stack (v1 + v2 surfaces).

    PYTHONPATH=src python -m repro.launch.serve --port 8080 \
        --deploy max-sentiment --deploy qwen3-4b --service auto

``--service`` picks the execution strategy behind each deployment:
``sync`` (per-request, v1 semantics), ``batched`` (continuous batching —
concurrent HTTP predicts coalesce into engine decode batches), or ``auto``
(batched for generative wrappers, sync otherwise).

Deployed assets use reduced (CPU-runnable) configs. Full width is served
by passing ``build_kw={"smoke": False, ...}`` to ``MAXServer``, as
``chip_smoke.py`` does for qwen3-4b on one TPU v5e. Run this launcher with
``JAX_PLATFORMS=cpu`` on a machine without an accelerator.
"""

from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--deploy", action="append", default=[],
                    help="asset id to deploy at startup (repeatable)")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--service", default="auto",
                    choices=["sync", "batched", "auto"],
                    help="inference service mode for deployments")
    ap.add_argument("--batch-window-ms", type=float, default=10.0,
                    help="coalescing window for the batched service")
    ap.add_argument("--duration", type=float, default=None,
                    help="serve for N seconds then exit (default: forever)")
    args = ap.parse_args()

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    import repro.core.assets  # noqa: F401 — populate the exchange
    from repro.core import EXCHANGE, MAXServer

    server = MAXServer(
        host=args.host, port=args.port,
        build_kw={"max_seq": args.max_seq, "max_batch": args.max_batch},
        service_mode=args.service,
        service_kw={"batch_window_s": args.batch_window_ms / 1e3})
    server.start()
    print(f"[serve] Model Asset eXchange at {server.url}")
    print(f"[serve] {len(EXCHANGE)} assets registered; "
          f"GET /models, /v2/models, /v2/routes, /swagger.json")
    print(f"[serve] service mode: {args.service} "
          f"(window {args.batch_window_ms:.0f}ms)")
    for asset_id in args.deploy:
        t0 = time.perf_counter()
        dep = server.manager.deploy(asset_id, **server.build_kw)
        print(f"[serve] deployed {asset_id} [{dep.service.kind}] "
              f"({time.perf_counter() - t0:.1f}s)")
    try:
        if args.duration:
            time.sleep(args.duration)
        else:
            while True:
                time.sleep(3600)
    except KeyboardInterrupt:
        pass
    finally:
        server.stop()
        print("[serve] stopped")


if __name__ == "__main__":
    main()
