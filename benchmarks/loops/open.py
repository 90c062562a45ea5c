"""Open loop: requests come due on a schedule fixed by the mix and the seed,
whether or not earlier ones have ended. ``rate`` is requests a second;
``burst`` (null or 1: none) is how many come due together. The gaps between
arrivals are the middles of ``set_size`` equal slices of an exponential
distribution (a Poisson process, coarsened), ordered by the seed lap after
lap: every seed offers the same gaps in another order.

``clients`` is the pool of streams, so the most requests in flight; a request
that finds every stream busy waits for one. A request is timed from when it
was DUE (``RequestLog.sent_ns``), so that wait is part of its latency, and how
late the generator itself ran is reported (``generator_lag_ms``).
"""

import math
import queue
import threading
import time
from typing import List

import numpy as np

from benchmarks.client import RESPONSE_WAIT_S, Client
from benchmarks.traffic import RequestSource, TrafficError


def validate(mix: dict) -> None:
    rate = mix.get("rate")
    if not isinstance(rate, (int, float)) or not rate > 0:
        raise TrafficError(f"rate={rate!r}: an open loop needs requests/s > 0")
    burst = mix.get("burst")
    if burst is not None and (not isinstance(burst, int) or burst < 1):
        raise TrafficError(f"burst={burst!r}: a whole number of requests")
    if int(mix["clients"]) < 1:
        raise TrafficError("clients must be at least 1")


def streams(mix: dict) -> int:
    return int(mix["clients"])


def arrival_gaps(mix: dict, seed: int, lap: int) -> np.ndarray:
    """Seconds between one arrival (or burst) and the next, for one lap."""
    n = int(mix["set_size"])
    mean = int(mix.get("burst") or 1) / float(mix["rate"])
    gaps = np.array([-mean * math.log(1 - (i + 0.5) / n) for i in range(n)])
    # The middles of equal slices under-weigh the tail: keep the mean exact.
    gaps *= mean / gaps.mean()
    return np.random.default_rng([int(seed), 3, lap]).permutation(gaps)


def run(clients: List[Client], source: RequestSource, seconds: float,
        mix: dict) -> dict:
    burst = int(mix.get("burst") or 1)
    due: "queue.Queue" = queue.Queue()
    lags_ms: List[float] = []
    start_ns = time.perf_counter_ns()
    end_ns = start_ns + int(seconds * 1e9)

    def schedule():
        at, lap, gaps = float(start_ns), 0, iter(())
        while at < end_ns:
            wait = (at - time.perf_counter_ns()) / 1e9
            if wait > 0:
                time.sleep(wait)
            lags_ms.append((time.perf_counter_ns() - at) / 1e6)
            for _ in range(burst):
                due.put((int(at), source.take()))
            gap = next(gaps, None)
            if gap is None:
                gaps = iter(arrival_gaps(mix, source.seed, lap))
                lap += 1
                gap = next(gaps)
            at += gap * 1e9
        for _ in clients:
            due.put(None)

    def serve(client: Client):
        while True:
            item = due.get()
            if item is None:
                return
            client.send(item[1], due_ns=item[0])

    threads = [threading.Thread(target=schedule, name="bench-schedule",
                                daemon=True)]
    threads += [threading.Thread(target=serve, args=(c,), daemon=True,
                                 name=f"bench-client-{c.index}")
                for c in clients]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + 2 * RESPONSE_WAIT_S)
        if t.is_alive():
            raise RuntimeError(f"{t.name} did not end")
    return {"start_ns": start_ns, "end_ns": end_ns,
            "drained_ns": time.perf_counter_ns(),
            "generator_lag_ms": max(lags_ms, default=0.0)}
