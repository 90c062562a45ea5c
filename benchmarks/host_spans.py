"""The host's share of a token, read from what stepscope stamps where the
time is spent: a token's egress split at the stream handler's two
hand-overs, the engine loop's time off the CPU, and the collector's pauses.

``request_spans.joined`` moves the stamps it knows onto the client's clock;
the handler's two stamps a token (``taken_ns[i]``: its ``req.out.get`` has
returned token i; ``resumed_ns[i]``: the response generator is resumed
behind the token's ``yield``) are moved here, by the same offset expression.
A program whose records carry no such stamps (a commit from before it had
them) makes every reader built on this None, never a guess; so does a loop
record without ``cpu_us``, and a dump without a ``gc`` ring. The records'
``runq_us`` (the thread's run-queue delay, where its ``schedstat`` can be
read) has no reader here: the chip's host does not keep that file (PERF.md
section 7), and a metric a cell's traced run leaves out is refused.
"""

import time
from typing import Callable, List, Optional, Tuple

from benchmarks import request_spans
from benchmarks.stats import percentile

# The engine loop's stretches in which it neither waits on purpose nor is
# idle: housekeeping that did work, a join, and the two dispatch brackets
# (``ticket_wait`` and ``idle_wait`` are waits the loop means to make).
WORKING_PHASES = ("admit", "join", "prefill_chunk", "decode")


def joined(obs) -> Optional[List[Tuple[object, dict]]]:
    """``request_spans.joined``'s pairs with ``taken_ns`` / ``resumed_ns``
    on the client's clock too. None where that join is None, or a record
    has not one stamp of each kind a token."""
    pairs = request_spans.joined(obs)
    if pairs is None:
        return None
    to_client = time.perf_counter_ns() - time.monotonic_ns()
    out = []
    for log, record in pairs:
        taken, resumed = record.get("taken_ns"), record.get("resumed_ns")
        if (taken is None or resumed is None
                or not len(taken) == len(resumed) == len(record["out_ns"])):
            return None
        out.append((log, dict(
            record, taken_ns=[t + to_client for t in taken],
            resumed_ns=[t + to_client for t in resumed])))
    return out


def span_percentile_ms(obs, q: float, spans: Callable) -> Optional[float]:
    """``request_spans.span_percentile_ms`` over this module's join."""
    pairs = joined(obs)
    if pairs is None:
        return None
    values = [ns / 1e6 for log, record in pairs for ns in spans(log, record)]
    return percentile(values, q) if values else None


def loop_blocked_share(obs) -> Optional[float]:
    """Over the engine loop's working stretches, cut to the window: the
    share of the window, in %, that the loop's thread spent off the CPU (the
    stretches' wall time less the thread's own CPU time). A stretch that
    straddles an end of the window counts by the part inside. The
    difference is taken of the SUMS, not stretch by stretch: where the
    kernel advances a thread's CPU clock a tick at a time (10 ms on the
    chip's host, PERF.md section 7) a 3 ms stretch reads 0 or 10 ms of CPU,
    and only the sums are right. None where a stretch carries no such
    clock."""
    if not obs.window_s:
        return None
    lo, hi = obs.window["start_ns"], obs.window["end_ns"]
    total, seen = 0.0, False
    for r in obs.steps:
        if r["phase"] not in WORKING_PHASES:
            continue
        if "cpu_us" not in r:
            return None
        seen = True
        wall_ns = r["dispatch_us"] * 1e3
        inside = min(r["start_ns"] + wall_ns, hi) - max(r["start_ns"], lo)
        if wall_ns <= 0 or inside <= 0:
            continue
        total += (r["dispatch_us"] - r["cpu_us"]) * 1e3 * inside / wall_ns
    return 100.0 * max(total, 0.0) / (hi - lo) if seen else None


def collector_pauses() -> Optional[List[Tuple[int, int]]]:
    """``(start, end)`` of every collection the program's hook recorded, on
    the client's clock; None where the program keeps no such ring."""
    from tritonclient_tpu import _stepscope

    pauses = _stepscope.dump().get("gc")
    if pauses is None:
        return None
    to_client = time.perf_counter_ns() - time.monotonic_ns()
    return [(p["start_ns"] + to_client,
             p["start_ns"] + p["duration_ns"] + to_client) for p in pauses]
