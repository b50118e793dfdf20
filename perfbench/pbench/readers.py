"""Helpers the metric readers in ``metrics/`` share."""

from __future__ import annotations

from typing import Optional

from pbench import flops, stats
from pbench import trace as trace_mod


def idle_share_pct(run) -> Optional[float]:
    """Share of the traced window in which no operation ran on the device."""
    if run.trace is None:
        return None
    busy = trace_mod.busy_s(run.trace)
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / trace_mod.window_s(run.trace))


def usage_p90(run, key: str) -> Optional[float]:
    """p90 of a server-side phase span (``usage[key]``, ms) over the
    finished requests due in the window."""
    return stats.percentile(
        [o.usage[key] for o in run.due_in_window
         if o.ok and o.usage.get(key) is not None], 90)


def prefill_flops_in_window(run) -> int:
    """Model operations of the prompts whose first token came in the window."""
    c = run.config
    return sum(flops.prefill_flops(c, len(o.req.prompt))
               for o in run.outcomes
               if o.first is not None and run.t0 <= o.first <= run.t1)


def decode_flops_in_window(run) -> int:
    """Model operations of the generated tokens (after each request's
    first) that arrived in the window, each at its own context length."""
    c = run.config
    total = 0
    for o in run.outcomes:
        j = 0
        for t, n in o.events:
            for k in range(j, j + n):
                if k > 0 and run.t0 <= t <= run.t1:
                    total += flops.decode_flops(c, len(o.req.prompt) + k)
            j += n
    return total
