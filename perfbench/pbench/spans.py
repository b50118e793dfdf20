"""The program's own spans in a profiler trace.

The serving loop opens a profiler span, named ``max.*``, at each boundary
inside the worker thread: the scheduler's tick (``max.sched.tick``) and
its four parts, the engine's admission, the worker between ticks and
while it waits, and every garbage collection (``max.gc``). The tick span
carries that tick's share of the scheduler's counters as attributes
(``host_s``, ``sync_s``, ``cpu_s``, ``steps``, ``kv_tokens``).
``trace.extract`` keeps only the benchmark's own spans, so this module
reads the program's from the same ``.xplane.pb``, into

    {"window": [start_ns, end_ns],
     "spans": [[name, start_ns, dur_ns, {attr: value}], ...]}

A run's trace is read from where ``run.py`` writes it, and only if its
``bench.window`` span is the run's own; otherwise, and for a program that
opens no such span, the readers read nothing.
"""

from __future__ import annotations

import glob
import heapq
import os
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

from pbench import trace as trace_mod
from pbench.spec import ROOT

PREFIX = "max."
TICK = "max.sched.tick"
GC = "max.gc"
PREP = "max.engine.prefill_prep"
DISPATCH = "max.engine.prefill_dispatch"
# where run.py has the profiler write a traced run
TRACE_DIR = ROOT / ".perfbench" / "trace"

_cache: Dict[Tuple[str, float], Dict[str, Any]] = {}


def _latest(trace_dir) -> Optional[str]:
    paths = glob.glob(os.path.join(str(trace_dir), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return max(paths, key=os.path.getmtime) if paths else None


def extract(trace_dir) -> Dict[str, Any]:
    """The ``max.*`` spans of the newest trace under ``trace_dir``."""
    from jax.profiler import ProfileData
    path = _latest(trace_dir)
    if path is None:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    key = (path, os.path.getmtime(path))
    if key in _cache:
        return _cache[key]
    out: Dict[str, Any] = {"window": None, "spans": []}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == trace_mod.WINDOW_SPAN:
                    s = int(e.start_ns)
                    out["window"] = [s, s + int(e.duration_ns)]
                elif e.name.startswith(PREFIX):
                    out["spans"].append([e.name, int(e.start_ns),
                                         int(e.duration_ns),
                                         dict(e.stats)])
    out["spans"].sort(key=lambda sp: sp[1])
    _cache.clear()
    _cache[key] = out
    return out


def of_run(run) -> Optional[Dict[str, Any]]:
    """The program's spans of ``run``'s traced window, or None when the
    run has no trace, its trace is not the one on disk, or the program
    opened no span."""
    if run.trace is None or _latest(TRACE_DIR) is None:
        return None
    got = extract(TRACE_DIR)
    if got["window"] != list(run.trace["window"]) or not got["spans"]:
        return None
    return got


def in_window(spans: Dict[str, Any], name: str) -> List[List[Any]]:
    """Spans called ``name`` that start inside the window."""
    w0, w1 = spans["window"]
    return [sp for sp in spans["spans"] if sp[0] == name and w0 <= sp[1] < w1]


def attr_sum(spans: List[List[Any]], key: str) -> float:
    return sum(float(sp[3].get(key, 0.0)) for sp in spans)


def clipped_s(spans: Dict[str, Any], name: str) -> float:
    """Seconds of the window inside spans called ``name``."""
    w0, w1 = spans["window"]
    return sum(max(0, min(s + d, w1) - max(s, w0))
               for n, s, d, _ in spans["spans"] if n == name) / 1e9


def _innermost(spans: List[List[Any]]) -> List[Tuple[int, int, str]]:
    """The timeline cut at every span edge, each piece named by the
    shortest span covering it: ``[(start, end, name), ...]``."""
    edges = sorted({x for _, s, d, *_ in spans for x in (s, s + d)})
    starts = sorted(((s, d, i, n) for i, (n, s, d, *_) in enumerate(spans)),
                    key=lambda t: t[0])
    out: List[Tuple[int, int, str]] = []
    heap: List[Tuple[int, int, int, str]] = []   # (dur, end, idx, name)
    j = 0
    for a, b in zip(edges, edges[1:]):
        while j < len(starts) and starts[j][0] <= a:
            s, d, i, n = starts[j]
            heapq.heappush(heap, (d, s + d, i, n))
            j += 1
        while heap and heap[0][1] <= a:     # ended: dropped once on top
            heapq.heappop(heap)
        if heap:
            out.append((a, b, heap[0][3]))
    return out


def _device_gaps(trace: Dict[str, Any]) -> List[Tuple[int, int]]:
    devs = [d for d in trace["devices"].values() if d["ops"]]
    if not devs:
        return []
    w0, w1 = trace["window"]
    busy = trace_mod.busy_intervals(devs[0]["ops"], trace["window"])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]


def idle_by_span(trace: Dict[str, Any],
                 spans: Dict[str, Any]) -> Dict[str, float]:
    """Seconds in which no device operation ran, split by the program
    span the host was innermost in (``idle`` where none covered it)."""
    pieces = _innermost(spans["spans"])
    out: Dict[str, float] = defaultdict(float)
    i = 0
    for g0, g1 in _device_gaps(trace):
        covered = 0
        while i < len(pieces) and pieces[i][1] <= g0:
            i += 1
        k = i
        while k < len(pieces) and pieces[k][0] < g1:
            a, b, name = pieces[k]
            part = min(b, g1) - max(a, g0)
            if part > 0:
                out[name] += part / 1e9
                covered += part
            k += 1
        out["idle"] += (g1 - g0 - covered) / 1e9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_gaps(trace: Dict[str, Any], spans: Dict[str, Any],
              n: int = 10) -> List[List[Any]]:
    """``trace.idle_gaps`` named by the program's spans in place of the
    benchmark's wrappers: each of the longest device gaps named by the
    innermost program span over its middle."""
    host = [sp[:3] for sp in spans["spans"]]
    return trace_mod.idle_gaps(dict(trace, host=host), n)


def main() -> None:
    """Print the device's idle time split by program span, and the
    longest gaps named with the program's spans, for the newest traced
    run (``PYTHONPATH=perfbench python3 -m pbench.spans [trace_dir]``)."""
    import json
    import sys
    trace_dir = sys.argv[1] if len(sys.argv) > 1 else TRACE_DIR
    tr = trace_mod.extract(str(trace_dir))
    got = extract(trace_dir)
    print(json.dumps({"idle_by_span_s": idle_by_span(tr, got),
                      "idle_gaps": idle_gaps(tr, got)}))


if __name__ == "__main__":
    main()
