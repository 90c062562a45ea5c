"""The program's own request timelines (stepscope's ``requests`` ring),
joined to the client's logs and put on the client's clock.

The ring outlives the window: the harness restores stepscope's mode after a
traced run and resets nothing, so the readers find every request that ended
since the set-up's ``reset()``. The wire carries no request id in the
benchmark's traffic, so a record is joined to a log by what both sides can
compute: ``(crc32 of the prompt's int32 bytes, prompt length, tokens
asked)``. A key that does not match one record to one log makes the whole
join None: every reader built on it then reports nothing, never a guess.

Client and server share a process here, and both clocks count
CLOCK_MONOTONIC; the offset between ``perf_counter_ns`` (the client's and
the harness's) and ``monotonic_ns`` (the program's) is taken the way the
harness takes it for stepscope's dispatch records.
"""

import time
import zlib
from collections import Counter
from typing import List, Optional, Tuple

import numpy as np

from benchmarks.stats import percentile

_STAMPS = ("recv_ns", "core_ns", "submit_ns", "admitted_ns",
           "first_chunk_ns", "last_chunk_ns", "first_ready_ns", "end_ns")


def key_of(request) -> Tuple[int, int, int]:
    prompt = np.ascontiguousarray(request.prompt, dtype=np.int32)
    return (zlib.crc32(prompt.tobytes()), int(prompt.shape[-1]),
            int(request.max_tokens))


def ring() -> Optional[List[dict]]:
    """The finished requests' records, or None where the program keeps no
    such ring (a commit from before it had one)."""
    from tritonclient_tpu import _stepscope

    return _stepscope.dump().get("requests")


def joined(obs) -> Optional[List[Tuple[object, dict]]]:
    """``(log, record)`` for every request sent in the window that finished,
    the record's stamps moved onto the client's clock. None where there is
    no ring, a finished request has no record of its own, or a record
    disagrees with its log (arrival before the send, another token count)."""
    records = ring()
    finished = obs.finished()
    if records is None or not finished:
        return None
    to_client = time.perf_counter_ns() - time.monotonic_ns()
    by_key = {}
    for record in records:
        by_key.setdefault(tuple(record["key"]), []).append(record)
    sent = Counter(key_of(log.request) for log in obs.logs)
    pairs = []
    for log in finished:
        key = key_of(log.request)
        candidates = by_key.get(key, [])
        if len(candidates) != 1 or sent[key] != 1:
            return None
        record = dict(candidates[0])
        for name in _STAMPS:
            if record[name] is not None:
                record[name] += to_client
        record["out_ns"] = [t + to_client for t in record["out_ns"]]
        if (record["outcome"] != "finished"
                or len(record["out_ns"]) != len(log.token_ns)
                or record["admitted_ns"] is None
                or record["submit_ns"] < log.sent_ns):
            return None
        pairs.append((log, record))
    return pairs


def span_percentile_ms(obs, q: float, spans) -> Optional[float]:
    """The ``q``-th percentile, in ms, of the nanosecond spans that
    ``spans(log, record)`` yields for each joined request; None where the
    join fails, a stamp is missing (``spans`` raised TypeError on a None) or
    there is nothing to take a percentile of."""
    pairs = joined(obs)
    if pairs is None:
        return None
    try:
        values = [ns / 1e6 for log, record in pairs
                  for ns in spans(log, record)]
    except TypeError:
        return None
    return percentile(values, q) if values else None
