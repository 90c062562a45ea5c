"""Closed loop: ``clients`` requesters, each on its own stream, each with one
request in flight; the next goes out when the last has ended. Concurrency
is fixed, so the rate is whatever the system gives. A closed loop has no
``rate`` and no ``burst``: a mix that sets one names another loop."""

import threading
import time
from typing import List

from benchmarks.client import RESPONSE_WAIT_S, Client
from benchmarks.traffic import RequestSource, TrafficError


def validate(mix: dict) -> None:
    for key in ("rate", "burst"):
        if mix.get(key) not in (None, 0):
            raise TrafficError(f"{key}={mix[key]!r}: a closed loop has none")
    if int(mix["clients"]) < 1:
        raise TrafficError("clients must be at least 1")


def streams(mix: dict) -> int:
    """How many client streams the loop opens."""
    return int(mix["clients"])


def run(clients: List[Client], source: RequestSource, seconds: float,
        mix: dict) -> dict:
    """Drive every client for ``seconds``; returns the window's own bounds.
    Blocks until the last request sent inside the window has ended."""
    stop = threading.Event()
    start_ns = time.perf_counter_ns()
    end_ns = start_ns + int(seconds * 1e9)
    threads = [
        threading.Thread(target=c.run_until, args=(source, end_ns, stop),
                         name=f"bench-client-{c.index}", daemon=True)
        for c in clients
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 2 * RESPONSE_WAIT_S)
        if t.is_alive():
            stop.set()
            raise RuntimeError(f"{t.name} did not end")
    return {"start_ns": start_ns, "end_ns": end_ns,
            "drained_ns": time.perf_counter_ns()}
