"""The client side: SSE requests timed on the host clock, open and closed
loops.

Each request records when it was due, when it was sent, when each token
event arrived and how many tokens it carried, the served token ids, and
its outcome: ``ok``, or the error code the server answered with, or
``NO_ANSWER`` when it never finished.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.error
import urllib.request
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from pbench.traffic import Request

REQUEST_TIMEOUT_S = 300.0


@dataclass
class Outcome:
    req: Request
    due: float                                  # host clock
    sent: float = 0.0
    events: List[Tuple[float, int]] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)
    status: str = "NO_ANSWER"
    usage: Dict[str, Any] = field(default_factory=dict)
    detail: str = ""

    @property
    def ok(self) -> bool:
        return self.status == "ok"

    @property
    def first(self) -> Optional[float]:
        return self.events[0][0] if self.events else None

    @property
    def last(self) -> Optional[float]:
        return self.events[-1][0] if self.events else None


def _read_sse(resp) -> Iterator[Dict[str, Any]]:
    ev: Dict[str, Any] = {}
    for raw in resp:
        line = raw.decode().rstrip("\n")
        if not line:
            if ev:
                yield ev
                ev = {}
            continue
        key, _, val = line.partition(": ")
        ev[key] = json.loads(val) if key == "data" else val
    if ev:
        yield ev


def stream(base: str, model: str, out: Outcome) -> Outcome:
    """One greedy SSE request; fills ``out`` as events arrive."""
    body = {"input": {"text": out.req.text,
                      "max_new_tokens": out.req.max_new_tokens,
                      "temperature": 0.0}}
    http_req = urllib.request.Request(
        f"{base}/v2/model/{model}/stream", json.dumps(body).encode(),
        {"Content-Type": "application/json"}, method="POST")
    out.sent = time.perf_counter()
    try:
        with urllib.request.urlopen(http_req,
                                    timeout=REQUEST_TIMEOUT_S) as resp:
            for ev in _read_sse(resp):
                kind, data = ev.get("event"), ev.get("data", {})
                if kind == "token":
                    ids = data["token_ids"]
                    out.events.append((time.perf_counter(), len(ids)))
                    out.tokens.extend(ids)
                elif kind == "done":
                    env = data.get("envelope", {})
                    out.usage = data.get("usage", {})
                    out.status = "ok" if env.get("status") == "ok" \
                        else env.get("code", "ERROR")
                    out.detail = "" if out.ok else json.dumps(env)[:300]
                    return out
                elif kind == "error":
                    out.status = data.get("code", "ERROR")
                    out.detail = data.get("message", "")[:300]
                    return out
        out.detail = "stream closed without a done event"
    except urllib.error.HTTPError as e:
        out.status = f"HTTP_{e.code}"
        out.detail = (e.read() or b"")[:300].decode(errors="replace")
    except OSError as e:
        out.status = "CONNECTION"
        out.detail = str(e)[:300]
    return out


class Load:
    """Drives one window of traffic; ``wait`` joins every request."""

    def __init__(self, base: str, model: str):
        self.base, self.model = base, model
        self.outcomes: List[Outcome] = []
        self._threads: List[threading.Thread] = []
        self._lock = threading.Lock()

    def _spawn(self, fn, *args):
        t = threading.Thread(target=fn, args=args, daemon=True)
        with self._lock:
            self._threads.append(t)
        t.start()

    def open_loop(self, requests: List[Request], t0: float):
        """Send each request at ``t0 + due``, whatever is still running."""
        for r in requests:
            due = t0 + r.due
            delay = due - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            out = Outcome(req=r, due=due)
            with self._lock:
                self.outcomes.append(out)
            self._spawn(stream, self.base, self.model, out)

    def closed_loop(self, requests: Iterator[Request], clients: int,
                    t_first: float, ramp_s: float, t_end: float):
        """``clients`` callers, each sending its next request when its
        last one ends, until ``t_end``; caller i starts at
        ``t_first + i * ramp_s / clients``."""
        it_lock = threading.Lock()

        def client(start: float):
            time.sleep(max(0.0, start - time.perf_counter()))
            while True:
                now = time.perf_counter()
                if now >= t_end:
                    return
                with it_lock:
                    r = next(requests)
                out = Outcome(req=r, due=now)
                with self._lock:
                    self.outcomes.append(out)
                stream(self.base, self.model, out)

        for i in range(clients):
            self._spawn(client, t_first + i * ramp_s / clients)

    def wait(self, deadline: float) -> bool:
        """Join every request thread by ``deadline``; False if any is left."""
        while True:
            with self._lock:
                threads = list(self._threads)
            for t in threads:
                t.join(max(0.0, deadline - time.perf_counter()))
            with self._lock:
                if len(self._threads) == len(threads):
                    return not any(t.is_alive() for t in threads)
