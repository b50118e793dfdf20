"""From a profiler trace to device numbers.

``extract`` reads the ``.xplane.pb`` the JAX profiler wrote into a small
plain structure (kept as a fixture by the tests):

    {"window": [start_ns, end_ns],
     "devices": {"/device:TPU:0": {"ops": [[name, start_ns, dur_ns], ...],
                                   "modules": [[name, start_ns, dur_ns], ...]}},
     "host": [[span, start_ns, dur_ns], ...]}

``window`` is the benchmark's own ``WINDOW_SPAN`` around the traced
window; ``host`` holds the benchmark's spans around the calls into the
scheduler and the engine. The reductions below work on that structure.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("scheduler.tick", "engine.prefill", "engine.decode_chunk",
              WINDOW_SPAN)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def options():
    """Profiler options for a traced window: device activity and the
    benchmark's spans, without the Python call tracer (which would time
    every call of every client thread)."""
    import jax
    o = jax.profiler.ProfileOptions()
    o.python_tracer_level = 0
    return o


def op_name(text: str) -> str:
    """``%fusion.193 = f32[...] fusion(...)`` -> ``fusion.193``."""
    return text.split(" = ", 1)[0].lstrip("%")


def extract(trace_dir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    out: Dict[str, Any] = {"window": None, "devices": {}, "host": []}
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [[op_name(e.name), int(e.start_ns),
                                 int(e.duration_ns)] for e in line.events]
            out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        rec = [e.name, int(e.start_ns), int(e.duration_ns)]
                        if e.name == WINDOW_SPAN:
                            out["window"] = rec[1:]
                        else:
                            out["host"].append(rec)
    if out["window"] is None:
        raise ValueError(f"trace has no {WINDOW_SPAN!r} span")
    out["window"] = [out["window"][0], out["window"][0] + out["window"][1]]
    return out


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    merged: List[Tuple[int, int]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


def busy_intervals(ev: List[List[Any]], window) -> List[Tuple[int, int]]:
    """Union of the events' intervals, clipped to the window."""
    w0, w1 = window
    return _union([(max(s, w0), min(s + d, w1)) for _, s, d in ev
                   if s + d > w0 and s < w1])


def busy_s(trace: Dict[str, Any]) -> Optional[float]:
    """Seconds in which an operation ran, averaged over the devices that
    ran any; None when no device op was traced."""
    per = [sum(e - s for s, e in busy_intervals(d["ops"], trace["window"]))
           for d in trace["devices"].values() if d["ops"]]
    return sum(per) / len(per) / 1e9 if per else None


def window_s(trace: Dict[str, Any]) -> float:
    w0, w1 = trace["window"]
    return (w1 - w0) / 1e9


def module_s(trace: Dict[str, Any], part: str) -> float:
    """Device seconds of the programs whose module name contains ``part``,
    summed over devices, within the window."""
    w0, w1 = trace["window"]
    return sum(min(s + d, w1) - max(s, w0)
               for dev in trace["devices"].values()
               for name, s, d in dev["modules"]
               if part in name and s + d > w0 and s < w1) / 1e9


def _kind(name: str) -> str:
    """``fusion.193`` -> ``fusion``; ``jit__prefill_impl(123)`` ->
    ``jit__prefill_impl``."""
    return re.sub(r"(\.\d+)+$|\(\d+\)$", "", name)


def _outermost(ev: List[List[Any]]) -> List[List[Any]]:
    """Events not inside another: the XLA Ops line also holds the ops a
    ``while`` or a call runs, nested in its interval."""
    out, end = [], None
    for e in sorted(ev, key=lambda e: (e[1], -e[2])):
        if end is None or e[1] >= end:
            out.append(e)
            end = e[1] + e[2]
    return out


def top_ops(trace: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """Outermost device operations that took most time, named
    ``<program>/<op kind>`` by the module they ran in, averaged over
    devices: ``[[name, seconds], ...]``."""
    w0, w1 = trace["window"]
    tot: Dict[str, float] = defaultdict(float)
    devs = [d for d in trace["devices"].values() if d["ops"]]
    for d in devs:
        mods = sorted(d["modules"], key=lambda e: e[1])
        starts = [m[1] for m in mods]
        for name, s, dur in _outermost(d["ops"]):
            if s + dur <= w0 or s >= w1:
                continue
            i = bisect.bisect_right(starts, s) - 1
            mod = _kind(mods[i][0]) if i >= 0 and s < mods[i][1] + mods[i][2] \
                else "?"
            tot[f"{mod}/{_kind(name)}"] += (min(s + dur, w1) - max(s, w0)) / 1e9
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v / max(1, len(devs))] for k, v in ranked]


def idle_gaps(trace: Dict[str, Any], n: int = 10) -> List[List[Any]]:
    """Longest gaps with no device op, on the busiest device, each named
    by the innermost benchmark span that covers its middle (``idle`` when
    none does): ``[[span, seconds], ...]``."""
    devs = [d for d in trace["devices"].values() if d["ops"]]
    if not devs:
        return []
    w0, w1 = trace["window"]
    busy = busy_intervals(devs[0]["ops"], trace["window"])
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:n]:
        mid = (s + e) / 2
        covering = [(d, name) for name, hs, d in trace["host"]
                    if hs <= mid <= hs + d]
        out.append([min(covering)[1] if covering else "idle", (e - s) / 1e9])
    return out
