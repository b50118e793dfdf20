"""One run of one cell: set up, measure a window, check the outputs.

Order of a run:

1. set-up: make the weights from the seed on the device, deploy the
   configuration, check that every request of the mix is admissible, warm
   every prefill bucket and decode-chunk length the mix reaches;
2. the window: ``seconds`` of the mix over HTTP (a closed loop's callers
   ramp up before it opens), profiled when traced; requests still running
   when it closes are awaited and counted by their own outcome;
3. read the peak device memory, probe the served weights, undeploy and
   free the program's state;
4. the outputs check against the reference (and, when calibrating, the
   fp8 control judged by the same checks), then the metrics.
"""

from __future__ import annotations

import shutil
import sys
import time
from dataclasses import dataclass
from pathlib import Path
from typing import Any, Callable, Dict, List, Optional

from pbench import check, client, flops, spec, stats, traffic
from pbench import trace as trace_mod
from pbench.serve import (CompileCounter, Deployment, SetupError, annotate,
                          check_admits)

LEAD_S = 0.25              # from the end of set-up to the first request
AWAIT_S = 60.0             # how long past the close late answers are awaited


@dataclass
class RunData:
    """What the metric readers read."""
    cell: spec.Cell
    seconds: float
    t0: float                                   # window opens (host clock)
    t1: float                                   # window closes
    setup_s: float
    outcomes: List[client.Outcome]
    sched: Dict[str, Dict[str, int]]            # scheduler counters
    max_batch: int
    device_kind: str
    trace: Optional[Dict[str, Any]] = None

    @property
    def config(self) -> Dict[str, Any]:
        return self.cell.config

    @property
    def due_in_window(self) -> List[client.Outcome]:
        return [o for o in self.outcomes if self.t0 <= o.due < self.t1]

    def peak(self) -> Dict[str, float]:
        return flops.peak(self.device_kind)


def _sched_counters(dep: Deployment) -> Dict[str, int]:
    s = dep.service.scheduler.stats
    return {"decode_steps": s.decode_steps, "occupancy_sum": s.occupancy_sum}


def _warm(dep: Deployment, t: Dict[str, Any], gen: traffic.Generator):
    """One request per prompt length that reaches a new padding bucket,
    sent together, each long enough to run every decode-chunk length."""
    n_new = 2 * dep.c["serve"]["decode_chunk"]
    load = client.Load(dep.base, dep.cfg.name)
    load.open_loop([gen.make(n, n_new, due=0.0)
                    for n in traffic.warmup_lengths(t)], time.perf_counter())
    load.wait(time.perf_counter() + client.REQUEST_TIMEOUT_S)
    for out in load.outcomes:
        if not out.ok:
            raise SetupError(f"warm-up request of {len(out.req.prompt)} "
                             f"tokens failed: {out.status} {out.detail}")


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def run_cell(cell: spec.Cell, seed: int, seconds: float, traced: bool, *,
             t_start: float, calibrate: bool = False,
             trace_dir: Optional[Path] = None,
             break_path: Optional[Callable[[Deployment], None]] = None
             ) -> Dict[str, Any]:
    """Run ``cell`` once and return the result line's object.
    ``break_path`` is the tests' hook to break the timed path after set-up."""
    import jax
    c, t = cell.config, cell.traffic
    s = c["serve"]
    gen = traffic.Generator(t, seed, bos=s["bos_id"])
    if t["loop"] == "open":
        window_reqs = gen.open(seconds)
        outstanding = len(window_reqs)
    else:
        n_clients = traffic.clients(t, s["max_batch"])
        outstanding = n_clients
    compiles = CompileCounter()
    dep = Deployment(c, seed, max_queue=max(64, 2 * outstanding))
    dev = jax.devices()[0]
    tr = None
    try:
        dep.start()
        t_deployed = time.perf_counter()
        check_admits(dep.engine, t, outstanding, dep.max_queue)
        _warm(dep, t, traffic.Generator(t, seed + 1, bos=s["bos_id"]))
        log(f"set-up: deployed at {t_deployed - t_start:.3f} s, warmed "
            f"{time.perf_counter() - t_deployed:.3f} s later; "
            f"{compiles.count} compiles, {compiles.seconds:.3f} s")
        if break_path is not None:
            break_path(dep)
        if traced:
            annotate(dep.service.scheduler, "tick", "scheduler.tick")
            annotate(dep.engine, "insert_request", "engine.prefill")
            annotate(dep.engine, "step_chunk", "engine.decode_chunk")
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir),
                                     profiler_options=trace_mod.options())
        load = client.Load(dep.base, dep.cfg.name)
        compiled_before = compiles.count
        t_first = time.perf_counter() + LEAD_S
        setup_s = t_first - t_start
        if t["loop"] == "open":
            t0 = t_first
        else:
            ramp = float(t.get("ramp_s", 0.0))
            t0 = t_first + ramp
            load.closed_loop(gen.closed(), n_clients, t_first, ramp,
                             t0 + seconds)
        t1 = t0 + seconds
        while time.perf_counter() < t0:
            time.sleep(0.001)
        sched_before = _sched_counters(dep)
        span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        if traced:
            span.__enter__()
        if t["loop"] == "open":
            load.open_loop(window_reqs, t0)
        time.sleep(max(0.0, t1 - time.perf_counter()))
        if traced:
            span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        sched_after = _sched_counters(dep)
        compiled_in_window = compiles.count - compiled_before
        answered = load.wait(t1 + AWAIT_S)
        mem = dev.memory_stats() or {}
        served_probe = check.probe(dep.engine.params)
        vocab_rows = dep.cfg.padded_vocab_size
        max_batch = dep.engine.max_batch
    finally:
        dep.stop()
    if traced:
        tr = trace_mod.extract(str(trace_dir))
    outcomes = list(load.outcomes)
    run = RunData(cell=cell, seconds=seconds, t0=t0, t1=t1, setup_s=setup_s,
                  outcomes=outcomes,
                  sched={"before": sched_before, "after": sched_after},
                  max_batch=max_batch, device_kind=dev.device_kind, trace=tr)
    in_window = run.due_in_window
    failed = [o for o in in_window if not o.ok]
    lag = stats.present(o.sent - o.due for o in in_window if o.sent)
    log(f"requests: {len(in_window)} attempted, {len(failed)} failed, "
        f"{sum(not o.ok for o in outcomes) - len(failed)} failed after the "
        f"window; all answered: {answered}")
    for o in failed:
        log(f"failed request {o.req.idx}: {o.status} "
            f"(prompt {len(o.req.prompt)}, max_new {o.req.max_new_tokens}) "
            f"{o.detail}")
    short = [o for o in in_window
             if o.ok and len(o.tokens) < o.req.max_new_tokens]
    log(f"finished early (EOS served): {len(short)} of "
        f"{sum(o.ok for o in in_window)}")
    log(f"setup_s: {setup_s}")
    log(f"compiles in window: {compiled_in_window}")
    log(f"generator lag: max {max(lag, default=0.0):.6f} s over "
        f"{len(lag)} requests")
    slow = max((o for o in in_window if o.first is not None),
               key=stats.ttft_s, default=None)
    if slow is not None:
        log(f"slowest first token: {stats.ttft_s(slow):.3f} s, due "
            f"{slow.due - t0:.3f} s into the window")

    silence, at = stats.longest_silence(outcomes, t0, t1)
    log(f"longest silence (no token to any request): {silence:.3f} s, "
        f"{at:.3f} s into the window")
    log(f"tokens per 5 s: {stats.tokens_per_slice(outcomes, t0, t1, 5.0)}")

    # -- the outputs check ---------------------------------------------------
    t_ref = time.perf_counter()
    done = [o for o in outcomes if o.ok]
    seqs = check.sample([check.Served(o.req.prompt, o.tokens) for o in done],
                        int(t["check_requests"]), seed)
    weights_bad = check.probe_mismatch(c, seed, vocab_rows, served_probe)
    length_bad = sum(
        o.usage.get("prompt_tokens") != len(o.req.prompt)
        or o.usage.get("completion_tokens") != len(o.tokens)
        or len(o.tokens) > o.req.max_new_tokens for o in done)
    g = check.gaps(c, seed, vocab_rows, seqs, s["max_seq"],
                   control=calibrate) if seqs else {
        "served": None, "control": None, "positions": 0}
    log(f"reference: {len(seqs)} requests, {g['positions']} served tokens "
        f"compared in {time.perf_counter() - t_ref:.3f} s")

    def checks_for(gap: Optional[float]) -> Dict[str, tuple]:
        return {
            "logit_gap": (gap, cell.limits["logit_gap"]),
            "compared_tokens_short": (
                max(0, int(cell.limits["min_compared_tokens"])
                    - g["positions"]), 0),
            "weights_mismatch": (weights_bad, 0),
            "length_mismatch": (length_bad, 0),
            "unanswered": (sum(o.status == "NO_ANSWER" for o in outcomes),
                           0),
        }

    def passes(checks: Dict[str, tuple]) -> bool:
        return all(v is not None and v <= lim for v, lim in checks.values())

    checks = checks_for(g["served"])
    correct = passes(checks)
    if calibrate:
        # the control in the program's place, judged by the same checks
        control_correct = passes(checks_for(g["control"]))
        log(f"calibration: served_gap {g['served']} control_gap "
            f"{g['control']} positions {g['positions']} "
            f"control_correct {control_correct}")

    # -- metrics ------------------------------------------------------------
    metrics = {}
    for m in (cell.per_layer if traced else cell.end_to_end):
        value = cell.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(mem.get("peak_bytes_in_use", 0))}
    result: Dict[str, Any] = {
        "correct": correct, "attempted": len(in_window),
        "failed": len(failed), "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace_mod.busy_s(tr)
        device["window_s"] = trace_mod.window_s(tr)
        result["breakdown"] = {"device_ops": trace_mod.top_ops(tr),
                               "idle_gaps": trace_mod.idle_gaps(tr)}
    if calibrate:
        result["calibration"] = {"served_gap": g["served"],
                                 "control_gap": g["control"],
                                 "positions": g["positions"],
                                 "control_correct": control_correct}
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} limit {lim}")
    return result
