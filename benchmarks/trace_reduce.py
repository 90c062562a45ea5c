"""From a profiler trace to numbers: device busy time, the operations that
took most of it, and the idle gaps by what the engine's host thread was doing.

The reduction works on plain tuples so that it can be checked on a small
recorded trace (tests/benchmark/data) without a profiler:

    device event:  (device, name, start_ns, duration_ns)

``read_xplane`` turns a ``.xplane.pb`` into those with ``jax.profiler``'s own
reader. On a TPU the device planes are ``/device:TPU:<n>`` and their
operations sit on the line ``XLA Ops``; a ``while`` (the layer scan) spans
its body's operations there, so busy time is the UNION of intervals and an
operation's time is its SELF time (its span less its children's).
"""

import bisect
import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

DeviceEvent = Tuple[str, str, float, float]

CLOCK_SYNC = "bench_clock_sync"    # a TraceAnnotation the harness emits
OPS_LINE = "XLA Ops"


def read_xplane(trace_dir: str) -> dict:
    """Device events, the traced span and the clock-sync pair of one trace."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    events: List[DeviceEvent] = []
    host_ops: List[DeviceEvent] = []
    sync: Optional[Tuple[float, int]] = None
    layout = []
    planes = list(data.planes)
    on_chip = any(p.name.startswith("/device:TPU") for p in planes)
    for plane in planes:
        is_device = plane.name.startswith("/device:TPU")
        for line in plane.lines:
            count = 0
            for event in line.events:
                count += 1
                if is_device:
                    if line.name == OPS_LINE:
                        events.append((plane.name, short_name(event.name),
                                       event.start_ns, event.duration_ns))
                    continue
                if event.name == CLOCK_SYNC:
                    mono = dict(event.stats).get("mono_ns")
                    if mono is not None:
                        sync = (event.start_ns, int(mono))
                elif (not on_chip and event.duration_ns > 0
                      and "hlo_op" in dict(event.stats)):
                    # The CPU backend runs its operations on host threads:
                    # the rehearsal's stand-in for a device plane.
                    host_ops.append(("/host:CPU-backend", event.name,
                                     event.start_ns, event.duration_ns))
            layout.append((plane.name, line.name, count))
    return {"events": events or host_ops, "sync": sync, "layout": layout,
            "file_bytes": os.path.getsize(paths[-1])}


def short_name(name: str) -> str:
    """``convert.58 f32[512,16,25,64]`` from the HLO text the TPU's trace
    gives an operation as its name; other names pass unchanged."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    if shape.startswith("("):           # a tuple: the name alone says enough
        return head.lstrip("%")
    return f"{head.lstrip('%')} {shape}"


def _merged(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def busy_intervals(events: Sequence[DeviceEvent]) -> Dict[str, List[List[float]]]:
    """Per device, the merged intervals in which some operation ran."""
    by_device: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for device, _, start, duration in events:
        if duration > 0:
            by_device[device].append((start, start + duration))
    return {d: _merged(iv) for d, iv in by_device.items()}


def self_times(events: Sequence[DeviceEvent]) -> Dict[str, float]:
    """Nanoseconds by operation name, children's time taken out of their
    parent's (a ``while`` keeps only what its body does not cover)."""
    totals: Dict[str, float] = defaultdict(float)
    by_device: Dict[str, List[DeviceEvent]] = defaultdict(list)
    for event in events:
        if event[3] > 0:
            by_device[event[0]].append(event)
    for device_events in by_device.values():
        # Parents first: earlier start, and on a tie the longer span.
        device_events.sort(key=lambda e: (e[2], -e[3]))
        stack: List[List] = []   # [name, end, self_ns]
        for _, name, start, duration in device_events:
            while stack and start >= stack[-1][1]:
                done = stack.pop()
                totals[done[0]] += max(done[2], 0.0)
            if stack:
                stack[-1][2] -= duration
            stack.append([name, start + duration, duration])
        while stack:
            done = stack.pop()
            totals[done[0]] += max(done[2], 0.0)
    return dict(totals)


def reduce_trace(events: Sequence[DeviceEvent], span_ns: Tuple[float, float],
                 chips: int, host_spans: Sequence[Tuple[float, float, str]] = (),
                 top: int = 10) -> dict:
    """The numbers the result line carries.

    ``span_ns`` is the traced window on the trace's clock; busy time is cut to
    it and averaged over ``chips``. ``host_spans`` are (start, end, label) on
    the same clock: what the engine's host thread was doing. An idle gap's
    seconds go to the label that covers its start, ``engine between
    dispatches`` where none does.
    """
    lo, hi = span_ns
    busy = busy_intervals(events)
    busy_ns = 0.0
    gaps: Dict[str, float] = defaultdict(float)
    spans = sorted(host_spans)
    span_starts = [span[0] for span in spans]
    for intervals in busy.values():
        cursor = lo
        for start, end in intervals:
            start, end = max(start, lo), min(end, hi)
            if end <= start:
                continue
            busy_ns += end - start
            if start > cursor:
                gaps[_label_at(spans, span_starts, cursor)] += start - cursor
            cursor = max(cursor, end)
        if hi > cursor:
            gaps[_label_at(spans, span_starts, cursor)] += hi - cursor
    for _ in range(chips - len(busy)):      # a chip with no event at all
        gaps["device never used"] += hi - lo
    ops = sorted(self_times(events).items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": busy_ns / max(chips, 1) / 1e9,
        "window_s": (hi - lo) / 1e9,
        "device_ops": [[name, ns / 1e9] for name, ns in ops],
        "idle_gaps": [[label, ns / max(chips, 1) / 1e9] for label, ns in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:top]],
    }


def _label_at(spans: Sequence[Tuple[float, float, str]],
              starts: Sequence[float], at: float) -> str:
    i = bisect.bisect_right(starts, at) - 1
    if i >= 0 and spans[i][0] <= at < spans[i][1]:
        return spans[i][2]
    return "engine between dispatches"
