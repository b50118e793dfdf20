"""The one traffic generator: every mix is a data file it reads.

A mix (``traffic/<name>.json``) gives the loop, the load, and the
distributions of prompt and output lengths:

    {"loop": "open", "rate_per_s": 1.5,
     "prompt_tokens": {"dist": "loguniform", "min": 1024, "max": 1920},
     "max_new_tokens": {"dist": "uniform", "min": 8, "max": 32},
     "deck": 16, "check_requests": 12}

``loop: closed`` takes ``clients`` (a number, or ``"max_batch"``) in place
of a rate, and ``ramp_s``: the clients start one after another, evenly
over that many seconds before the window opens, so the window sees
callers in every phase of a request and not one wave that started
together. Lengths and gaps are drawn as *decks*: ``deck`` stratified
quantiles of each distribution, dealt in an order drawn from the mix's
own ``order_seed``. Every run therefore sends the same sizes at the same
times: the run's seed draws the prompt contents (random printable bytes,
so no two prompts share a prefix) and, elsewhere, the weights.
Reordering the same arrivals by seed widened the spread of p90 TTFT in
51-second windows of a mix at 0.8 x its knee to 12-13% on a TPU v5e.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

PRINTABLE = np.arange(32, 127, dtype=np.uint8)


@dataclass
class Request:
    idx: int
    prompt: List[int]               # token ids the server sees
    text: str                       # what is sent
    max_new_tokens: int
    due: Optional[float] = None     # seconds after the window opens (open loop)


def quantiles(dist: Dict[str, Any], n: int) -> List[int]:
    """``n`` stratified draws: the distribution's (i + 0.5) / n quantiles."""
    qs = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "fixed":
        return [int(dist["value"])] * n
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "uniform":
        return [int(lo + math.floor(q * (hi - lo + 1))) for q in qs]
    if kind == "loguniform":
        a, b = math.log(lo), math.log(hi)
        return [int(round(math.exp(a + q * (b - a)))) for q in qs]
    raise ValueError(f"unknown length distribution {kind!r}")


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified exponential gaps, scaled to a mean of exactly 1/rate."""
    g = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    return g / g.mean() / rate


def length_range(dist: Dict[str, Any]) -> tuple:
    if dist["dist"] == "fixed":
        return int(dist["value"]), int(dist["value"])
    return int(dist["min"]), int(dist["max"])


class Generator:
    """Requests of one mix for one seed. ``bos`` is the id the server's
    tokenizer puts before the prompt's bytes."""

    def __init__(self, traffic: Dict[str, Any], seed: int, bos: int):
        self.t = traffic
        self.bos = bos
        self.deck = int(traffic.get("deck", 16))
        self.rng = np.random.default_rng(seed)
        self.order = np.random.default_rng(int(traffic.get("order_seed", 0)))
        self._prompt_q = quantiles(traffic["prompt_tokens"], self.deck)
        self._new_q = quantiles(traffic["max_new_tokens"], self.deck)
        self._n = 0

    def _deal(self) -> Iterator[tuple]:
        while True:
            p = self.order.permutation(self._prompt_q)
            m = self.order.permutation(self._new_q)
            yield from zip(p.tolist(), m.tolist())

    def make(self, n_prompt: int, max_new: int,
             due: Optional[float] = None) -> Request:
        body = self.rng.choice(PRINTABLE, size=n_prompt - 1)
        req = Request(idx=self._n, prompt=[self.bos] + body.tolist(),
                      text=body.tobytes().decode("ascii"),
                      max_new_tokens=max_new, due=due)
        self._n += 1
        return req

    def closed(self) -> Iterator[Request]:
        """Endless requests for a closed loop, in deal order."""
        for n_prompt, max_new in self._deal():
            yield self.make(n_prompt, max_new)

    def open(self, seconds: float) -> List[Request]:
        """Every request due in ``[0, seconds)`` of an open loop."""
        rate = float(self.t["rate_per_s"])
        out, t = [], 0.0
        deal = self._deal()
        while True:
            for gap in self.order.permutation(exp_gaps(rate, self.deck)):
                t += float(gap)
                if t >= seconds:
                    return out
                n_prompt, max_new = next(deal)
                out.append(self.make(n_prompt, max_new, due=t))


def clients(traffic: Dict[str, Any], max_batch: int) -> int:
    c = traffic["clients"]
    return max_batch if c == "max_batch" else int(c)


def warmup_lengths(traffic: Dict[str, Any]) -> List[int]:
    """Prompt lengths that reach every padding bucket the mix can reach:
    its shortest and longest prompt, and both sides of each power of two
    in between."""
    lo, hi = length_range(traffic["prompt_tokens"])
    out = {lo, hi}
    p = 1
    while p <= hi:
        for n in (p, p + 1):
            if lo <= n <= hi:
                out.add(n)
        p *= 2
    return sorted(out)
