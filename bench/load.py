"""Load generation: one general generator for every traffic mix.

A mix is a JSON file under ``bench/traffic/``: ``{"kind": "closed",
"clients": c, "sizes": {"fixed": n}}`` runs ``c`` client threads, each
sending its next request of ``n`` samples as soon as the last one returns
(a trainer's data-loader workers).
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import threading
import time
from typing import List, Optional


@dataclasses.dataclass
class Request:
    due: float           # seconds after the window opened
    asked: int
    send: float = float("nan")
    done: float = float("nan")
    result: object = None
    error: Optional[str] = None


def load_mix(path: str) -> dict:
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") != "closed":
        raise ValueError(f"{path}: kind must be 'closed'")
    return mix


def _timed(req: Request, svc, t0: float, annotate) -> None:
    req.send = time.perf_counter() - t0
    try:
        with annotate():
            req.result = svc.request(int(req.asked))
    except Exception as e:             # a request that never comes back
        req.error = repr(e)
    req.done = time.perf_counter() - t0


def run_closed(svc, mix: dict, seconds: float, annotate=contextlib.nullcontext
               ) -> List[Request]:
    size = int(mix["sizes"]["fixed"])
    reqs: List[Request] = []
    lock = threading.Lock()
    t0 = time.perf_counter()

    def client():
        while time.perf_counter() - t0 < seconds:
            r = Request(due=time.perf_counter() - t0, asked=size)
            _timed(r, svc, t0, annotate)
            with lock:
                reqs.append(r)
            if r.error is not None:
                return

    threads = [threading.Thread(target=client, name=f"bench-client-{i}")
               for i in range(int(mix["clients"]))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return sorted(reqs, key=lambda r: r.due)


def window_s(reqs: List[Request]) -> float:
    """From the window's opening to the last answer."""
    return max(r.done for r in reqs if r.error is None)


def samples_per_s(reqs: List[Request]) -> float:
    """Every sample delivered, over all of the window's time."""
    return sum(r.asked for r in reqs if r.error is None) / window_s(reqs)
