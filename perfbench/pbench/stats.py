"""Arithmetic over the requests of a window, shared by the metric readers."""

from __future__ import annotations

from typing import Iterable, List, Optional

import numpy as np


def percentile(values: Iterable[float], q: float) -> Optional[float]:
    """Linear-interpolated ``q``-th percentile; None for no values."""
    v = list(values)
    return float(np.percentile(np.asarray(v, np.float64), q)) if v else None


def ttft_s(out) -> Optional[float]:
    """Due time to first token: a request that waited to be sent waited."""
    return None if out.first is None else out.first - out.due


def tpot_s(out) -> Optional[float]:
    """(last token - first token) / (tokens - 1): per request, so that the
    chunked arrival of tokens does not show as gaps."""
    n = len(out.tokens)
    if n < 2 or out.first is None:
        return None
    return (out.last - out.first) / (n - 1)


def tokens_between(outcomes, t0: float, t1: float) -> int:
    return sum(n for o in outcomes for t, n in o.events if t0 <= t <= t1)


def present(xs: Iterable[Optional[float]]) -> List[float]:
    return [x for x in xs if x is not None]


def longest_silence(outcomes, t0: float, t1: float) -> tuple:
    """The longest stretch of ``[t0, t1]`` in which no token arrived at
    any request: (seconds, its start after ``t0``)."""
    ts = sorted([t0, t1] + [t for o in outcomes for t, _ in o.events
                            if t0 <= t <= t1])
    gaps = np.diff(np.asarray(ts, np.float64))
    i = int(np.argmax(gaps))
    return float(gaps[i]), ts[i] - t0


def tokens_per_slice(outcomes, t0: float, t1: float,
                     slice_s: float) -> List[int]:
    """Tokens that arrived in each ``slice_s`` of ``[t0, t1)``."""
    n = max(1, int(np.ceil((t1 - t0) / slice_s)))
    out = [0] * n
    for o in outcomes:
        for t, k in o.events:
            if t0 <= t < t1:
                out[min(n - 1, int((t - t0) // slice_s))] += k
    return out
