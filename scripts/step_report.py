#!/usr/bin/env python
"""Engine steps, loop states and request timelines from stepscope, and —
where the dump has a device clock — the dominant-stage verdict: dispatch-,
device-, or collective-bound.

``tail_report.py`` attributes *request* tails across serving stages;
this report goes one level down, into what stepscope
(``TPU_STEPSCOPE=1``) collects: per dispatch, host-dispatch time and, in
``sync`` mode only, device time and the clamped remainder, plus collectives
charged per step, positions computed and context held and, for a family
with a routed expert layer, what its router did (tokens routed, the share
of the experts held that got one, the most loaded expert against the mean,
how often the experts' product streamed an expert for each one hit: the
``routing <phase>`` rows); per delivery item,
how long it queued for the delivery thread, its readback and its hand-over;
what the engine thread did between dispatches (``ticket_wait`` /
``idle_wait`` / ``admit`` / ``join``) and, inside ``admit`` and ``join``,
its slot-state updates (how many, the slots each joined and freed, what
each cost the thread: the ``slot updates`` row); for every stretch the two
threads bracket (a loop state, a dispatch bracket: the ``dispatching
<phase>`` rows, a slot update, a delivery's hand-over) the time the thread
was OFF THE CPU (wall less the thread's own CPU time: in line for the
interpreter lock, or descheduled) and the part of it spent runnable on a
RUN QUEUE (its core taken), as totals and shares; the interpreter's
collector (the ``collector`` row: collections by generation, total and worst
pause, the seconds of the recorded span they fell in); and one timeline per
request (receipt to core to submit, wait for a slot, the prefill span split
at its first chunk, last chunk and first token's readback, the worst
hand-over of a token to its stream handler (``wake``) and the handler's worst
time on one message (``handler``), tokens, worst gap between two tokens). A counters-mode dump has no device clock — the engine thread's
post-dispatch remainder is bookkeeping, and the delivery thread's
``ready_ns`` is when *it* saw the result — so it gets the tables and no
dispatch-/device-bound verdict. It consumes

* a stepscope dump (``tritonclient_tpu._stepscope.dump()`` saved to a
  file) — the primary input: the recent-step ring with full breakdowns,
  the loop states, the delivery thread's ring, the slot-state updates'
  ring, the finished requests' ring and the collector's pauses;
* a flight-recorder dump (``GET v2/debug/flight_recorder``) — retained
  records carry the slowest step's breakdown as ``step.slowest.*``
  attributes;
* a Perfetto trace file whose thread-scoped stepscope tracks carry the
  per-step args (``--trace-out`` / flight Perfetto export);
* a MULTICHIP bench record (``MULTICHIP_rNN.json``) whose tail carries
  the ``[tp-engine-stepscope]`` breakdown line.

and reports, per model: per-phase step p50/p99, the mean per-step stage
split, collectives per step, the deliveries' table, the loop states' share
of the recorded span, the per-request table, and (``sync`` dumps) the verdict —

* **dispatch-bound** — host time (dispatch + other) dominates: the
  device waits on python/trace/dispatch; batch more or trim host work;
* **device-bound** — device time dominates and steps issue no
  collectives: compute is the wall; scale or shrink the model;
* **collective-bound** — device time dominates and steps carry
  collectives: the tp all-reduces are inside that device time; how much
  of it no compute hides is read from a device trace, not from here.

Usage::

    python scripts/step_report.py DUMP_FILE [--json]
    python scripts/step_report.py DUMP_A --compare DUMP_B   # tp=1 vs tp=2
    python scripts/step_report.py --self-check
"""

import argparse
import json
import os
import sys
from typing import Dict, List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

from tritonclient_tpu import _otel, _stepscope  # noqa: E402

STAGES = _stepscope.STEP_STAGES

VERDICT_DISPATCH = "dispatch-bound"
VERDICT_DEVICE = "device-bound"
VERDICT_COLLECTIVE = "collective-bound"
#: Records without a device stage (counters mode): nothing on the host's
#: clock says when the device finished, so no bound is named.
VERDICT_NO_DEVICE_CLOCK = "no-device-clock"
_NO_DEVICE_CLOCK_WHY = (
    "counters mode records no device time (TPU_STEPSCOPE=sync brackets "
    "block_until_ready; per-executable device time is on the profile's "
    "XLA Modules line)")
REQUEST_ROWS = 32       # the per-request table shows the slowest waits

_BENCH_TAG = "dryrun_multichip[tp-engine-stepscope]:"


def _percentile(sorted_values: List[int], q: float) -> int:
    if not sorted_values:
        return 0
    idx = min(int(q * len(sorted_values)), len(sorted_values) - 1)
    return sorted_values[idx]


def _coll_count(collectives) -> int:
    """Total op count from a record's collectives field (dict of
    op -> {count, bytes}, or already an int)."""
    if isinstance(collectives, dict):
        total = 0
        for v in collectives.values():
            total += int(v.get("count", 0)) if isinstance(v, dict) else int(v)
        return total
    try:
        return int(collectives or 0)
    except (TypeError, ValueError):
        return 0


def _records_from_flight(doc: dict) -> List[dict]:
    """One pseudo-record per retained flight record that carries the
    slowest-step stamp (deduped: the same slowest step is stamped onto
    many records)."""
    seen = set()
    out = []
    for rec in doc.get("records", []):
        attrs = rec.get("attributes") or {}
        if "step.slowest.total_us" not in attrs:
            continue
        key = (rec.get("model_name", ""), attrs.get("step.slowest.phase"),
               attrs.get("step.slowest.index"))
        if key in seen:
            continue
        seen.add(key)
        out.append({
            "model": rec.get("model_name", ""),
            "phase": attrs.get("step.slowest.phase", "decode"),
            "step_index": int(attrs.get("step.slowest.index", 0)),
            "batch_size": int(attrs.get("step.slowest.batch_size", 0)),
            "dispatch_us": int(attrs.get("step.slowest.dispatch_us", 0)),
            "total_us": int(attrs.get("step.slowest.total_us", 0)),
            "collectives": int(attrs.get("step.slowest.collectives", 0)),
            **{f"{stage}_us": int(attrs[f"step.slowest.{stage}_us"])
               for stage in ("device", "other")
               if f"step.slowest.{stage}_us" in attrs},
        })
    return out


def _records_from_spans(spans: List[dict]) -> List[dict]:
    """Step records from a trace file's stepscope thread tracks (events
    whose args carry the per-step breakdown)."""
    out = []
    for s in spans:
        attrs = s.get("attributes") or {}
        if "dispatch_us" not in attrs or "phase" not in attrs:
            continue
        stages = {f"{stage}_us": int(attrs[f"{stage}_us"])
                  for stage in STAGES if f"{stage}_us" in attrs}
        out.append({
            "model": attrs.get("model", ""),
            "phase": attrs.get("phase", "decode"),
            "step_index": int(attrs.get("step_index", 0)),
            "batch_size": int(attrs.get("batch_size", 0)),
            "start_ns": int(s.get("start_ns", 0)),
            **stages,
            "total_us": int(s.get("duration_ns", 0)) // 1000
            or sum(stages.values()),
            "collectives": int(attrs.get("collectives", 0)),
        })
    return out


def load_records(doc) -> List[dict]:
    """Normalize any supported input document to flat step-record dicts:
    {model, phase, step_index, batch_size, dispatch_us, total_us,
    collectives:int} plus device_us/other_us where the source had a
    device clock (``sync`` mode). Loop states ride along under their own
    phase names; ``analyze`` keeps them apart."""
    if isinstance(doc, dict) and doc.get("kind") == "stepscope":
        out = []
        for r in doc.get("records", []):
            r = dict(r)
            r["collectives"] = _coll_count(r.get("collectives"))
            out.append(r)
        return out
    if isinstance(doc, dict) and doc.get("kind") == "flight_recorder":
        return _records_from_flight(doc)
    return _records_from_spans(_otel.load_spans(doc))


def load_compiles(doc) -> Dict[str, Dict[str, dict]]:
    """Compile-plane totals from a stepscope dump: model -> callable ->
    {entries, retraces}. Only stepscope dumps carry the plane (flight
    dumps and traces have no compile stream); pre-compile-plane dumps
    simply have no key and report an empty map."""
    if not (isinstance(doc, dict) and doc.get("kind") == "stepscope"):
        return {}
    out: Dict[str, Dict[str, dict]] = {}
    for key, cell in (doc.get("compiles") or {}).items():
        model, _, fn = key.partition("|")
        out.setdefault(model, {})[fn] = {
            "entries": int(cell.get("entries", 0)),
            "retraces": int(cell.get("retraces", 0)),
        }
    return out


def _load_ring(doc, name: str) -> List[dict]:
    """One of a stepscope dump's rings beside the records (empty for every
    other input, and for dumps from before the ring existed)."""
    if not (isinstance(doc, dict) and doc.get("kind") == "stepscope"):
        return []
    return list(doc.get(name) or [])


def load_requests(doc) -> List[dict]:
    """The finished requests' ring."""
    return _load_ring(doc, "requests")


def load_deliveries(doc) -> List[dict]:
    """The delivery thread's ring."""
    return _load_ring(doc, "deliveries")


def load_slot_updates(doc) -> List[dict]:
    """The slot-state updates' ring."""
    return _load_ring(doc, "slot_updates")


def load_pauses(doc) -> List[dict]:
    """The collector's pauses (process-wide: no model)."""
    return _load_ring(doc, "gc")


def load_file(path: str) -> List[dict]:
    with open(path) as f:
        return load_records(json.load(f))


def _verdict(dispatch_us: float, device_us: Optional[float],
             other_us: float, coll_per_step: float) -> str:
    """The decision rule: host time (dispatch + the clamped remainder)
    vs device time; device-dominant steps that issue collectives are
    collective-bound (the all-reduce wait is inside device time — there
    is no separate collective clock). Without a device stage there is
    nothing to weigh the host against."""
    if device_us is None:
        return VERDICT_NO_DEVICE_CLOCK
    if dispatch_us + other_us >= device_us:
        return VERDICT_DISPATCH
    if coll_per_step > 0:
        return VERDICT_COLLECTIVE
    return VERDICT_DEVICE


def _stage_means(recs: List[dict]) -> Dict[str, float]:
    """Mean µs per stage over the records that carry it; a stage no
    record carries (device/other in counters mode) is left out."""
    means = {}
    for stage in STAGES:
        values = [int(r[f"{stage}_us"]) for r in recs
                  if r.get(f"{stage}_us") is not None]
        if values:
            means[stage] = sum(values) / len(values)
    return means


def _off_cpu(recs: List[dict], wall_us) -> Optional[dict]:
    """Over the stretches that carry stepscope's thread clocks: the time the
    thread was off the CPU (Σ ``wall_us(r)`` - ``cpu_us``: it slept in line
    for the interpreter lock or a condition, or stood descheduled) and the
    part of it spent runnable on a run queue (Σ ``runq_us``: its core was
    taken), in ms, each with its share of the stretches' wall time. None
    where no record carries ``cpu_us`` (an older dump); no run-queue fields
    where none carries ``runq_us`` (the thread's schedstat was unreadable)."""
    clocked = [r for r in recs if r.get("cpu_us") is not None]
    if not clocked:
        return None
    wall = sum(wall_us(r) for r in clocked)
    # Of the sums: a CPU clock that advances a tick at a time (10 ms on
    # some hosts) reads 0 or a whole tick on a short stretch.
    off = max(wall - sum(int(r["cpu_us"]) for r in clocked), 0)
    cell = {"off_cpu_ms": round(off / 1000, 3),
            "off_cpu_share": round(off / wall, 4) if wall else 0.0}
    queued = [int(r["runq_us"]) for r in clocked
              if r.get("runq_us") is not None]
    if queued:
        cell["runq_ms"] = round(sum(queued) / 1000, 3)
        cell["runq_share"] = round(sum(queued) / wall, 4) if wall else 0.0
    return cell


def _off_cpu_text(cell: Optional[dict], lead: str = ", off-cpu") -> str:
    if not cell or "off_cpu_ms" not in cell:
        return ""
    text = (f"{lead} {cell['off_cpu_ms']} ms "
            f"({100 * cell['off_cpu_share']:.1f}%)")
    if "runq_ms" in cell:
        text += (f", run-queue {cell['runq_ms']} ms "
                 f"({100 * cell['runq_share']:.1f}%)")
    return text


def _dispatch_us(r: dict) -> int:
    return int(r.get("dispatch_us", 0))


def _loop_states(recs: List[dict]) -> Dict[str, dict]:
    """Per loop state: stretches, their total, its share of the span the
    model's records cover (first start to last end), and the stretches'
    time off the CPU and on a run queue (``_off_cpu``)."""
    spans = [(int(r["start_ns"]), int(r["start_ns"])
              + 1000 * int(r.get("total_us") or r.get("dispatch_us", 0)))
             for r in recs if r.get("start_ns")]
    covered_us = ((max(e for _, e in spans) - min(s for s, _ in spans))
                  / 1000 if spans else 0)
    out = {}
    for state in _stepscope.LOOP_STATES:
        stretches = [r for r in recs if r.get("phase") == state]
        durations = [_dispatch_us(r) for r in stretches]
        if durations:
            out[state] = {
                "n": len(durations),
                "total_ms": round(sum(durations) / 1000, 3),
                "share": round(sum(durations) / covered_us, 4)
                if covered_us else 0.0,
                **(_off_cpu(stretches, _dispatch_us) or {}),
            }
    return out


def _span_ms(start: Optional[int], end: Optional[int]) -> Optional[float]:
    if start is None or end is None:
        return None
    return round((end - start) / 1e6, 3)


def _deliveries(deliveries: List[dict]) -> Dict[str, dict]:
    """Per phase of the dispatch that made the item: how long items queued
    for the delivery thread, the readback as that thread saw it, and the
    hand-over to the requests (p50 / p95, ms), with the hand-overs' time off
    the CPU and on a run queue. Never a device time."""
    out = {}
    for phase in sorted({d.get("phase", "") for d in deliveries}):
        cell = {"n": 0}
        for name, start, end in (("queue_wait", "queued_ns", "taken_ns"),
                                 ("readback", "taken_ns", "ready_ns"),
                                 ("handover", "ready_ns", "delivered_ns")):
            spans = sorted(
                ms for ms in (_span_ms(d.get(start), d.get(end))
                              for d in deliveries if d.get("phase") == phase)
                if ms is not None)
            cell["n"] = max(cell["n"], len(spans))
            cell[f"{name}_ms"] = {"p50": _percentile(spans, 0.50),
                                  "p95": _percentile(spans, 0.95)}
        # The hand-over's time off the CPU (ready_ns -> delivered_ns is the
        # stretch the delivery thread clocks).
        cell.update(_off_cpu(
            [d for d in deliveries if d.get("phase") == phase
             and d.get("ready_ns") and d.get("delivered_ns")],
            lambda d: (d["delivered_ns"] - d["ready_ns"]) // 1000) or {})
        out[phase] = cell
    return out


def _slot_updates(updates: List[dict]) -> Optional[dict]:
    """The engine loop's slot-state updates: how many dispatches, the slots
    they joined and freed in all, and what one cost the loop's thread
    (host time from building its arrays to the call's return; p50 / p95,
    ms), with the updates' time off the CPU and on a run queue. None where
    the dump has none."""
    if not updates:
        return None
    host_ms = sorted(round(int(u.get("host_ns", 0)) / 1e6, 3)
                     for u in updates)
    return {"n": len(updates),
            "joined": sum(int(u.get("joined", 0)) for u in updates),
            "freed": sum(int(u.get("freed", 0)) for u in updates),
            "host_ms": {"p50": _percentile(host_ms, 0.50),
                        "p95": _percentile(host_ms, 0.95)},
            "host_total_ms": round(sum(host_ms), 3),
            **(_off_cpu(updates,
                        lambda u: int(u.get("host_ns", 0)) // 1000) or {})}


#: The ms columns of the per-request table, in the order of the timeline.
REQUEST_SPANS = ("recv_ms", "core_ms", "wait_ms", "to_chunk_ms",
                 "chunking_ms", "readback_ms", "handover_ms", "wake_ms",
                 "handler_ms", "prefill_span_ms", "worst_gap_ms")


def _worst_ms(starts, ends) -> Optional[float]:
    """The longest of the spans ``starts[i]`` -> ``ends[i]``, in ms."""
    spans = [b - a for a, b in zip(starts or [], ends or [])]
    return round(max(spans) / 1e6, 3) if spans else None


def _request_rows(requests: List[dict]) -> List[dict]:
    """One row per finished request, all spans in ms: receipt to the core
    (``recv_ms``) and the core to the engine's submit (``core_ms``); the
    wait for a slot and pages; the prefill span (admission to the first
    token's hand-over) and its split at the first chunk's dispatch return,
    the last chunk's, and the first token's readback; over its tokens the
    worst hand-over from the delivery thread's put to the stream handler
    holding the token (``wake_ms``: ``out_ns[i]`` -> ``taken_ns[i]``) and the
    handler's worst time on one message (``handler_ms``: ``taken_ns[i]`` ->
    ``resumed_ns[i]``); tokens; and the worst gap between two consecutive
    tokens."""
    rows = []
    for r in requests:
        out_ns = r.get("out_ns") or []
        admitted = r.get("admitted_ns")
        first_out = out_ns[0] if out_ns else None
        gaps = [b - a for a, b in zip(out_ns, out_ns[1:])]
        key = r.get("key") or [0, 0, 0]
        rows.append({
            "prompt_len": key[1], "max_new": key[2],
            "outcome": r.get("outcome"),
            "recv_ms": _span_ms(r.get("recv_ns"), r.get("core_ns")),
            "core_ms": _span_ms(r.get("core_ns"), r.get("submit_ns")),
            "wait_ms": _span_ms(r.get("submit_ns"), admitted),
            "waited_for_pages": bool(r.get("waited_for_pages")),
            "to_chunk_ms": _span_ms(admitted, r.get("first_chunk_ns")),
            "chunking_ms": _span_ms(r.get("first_chunk_ns"),
                                    r.get("last_chunk_ns")),
            "readback_ms": _span_ms(r.get("last_chunk_ns"),
                                    r.get("first_ready_ns")),
            "handover_ms": _span_ms(r.get("first_ready_ns"), first_out),
            "wake_ms": _worst_ms(out_ns, r.get("taken_ns")),
            "handler_ms": _worst_ms(r.get("taken_ns"), r.get("resumed_ns")),
            "prefill_span_ms": _span_ms(admitted, first_out),
            "chunks": r.get("chunks", 0),
            "tokens": len(out_ns),
            "worst_gap_ms": round(max(gaps) / 1e6, 3) if gaps else None,
        })
    return rows


def _collector(pauses: List[dict], records: List[dict]) -> Optional[dict]:
    """The interpreter's collector over the dump: collections by generation,
    their total and the worst pause (and the thread it ran on), and the
    seconds of the recorded span (from the first record's start) in which a
    collection began. Every thread of the process stalls for a collection.
    None where the dump has none."""
    if not pauses:
        return None
    starts = [int(r["start_ns"]) for r in records if r.get("start_ns")]
    origin = min(starts) if starts else min(p["start_ns"] for p in pauses)
    by_generation: Dict[str, int] = {}
    for p in pauses:
        gen = str(p.get("generation"))
        by_generation[gen] = by_generation.get(gen, 0) + 1
    worst = max(pauses, key=lambda p: p.get("duration_ns", 0))
    return {
        "n": len(pauses),
        "by_generation": dict(sorted(by_generation.items())),
        "total_ms": round(sum(p.get("duration_ns", 0)
                              for p in pauses) / 1e6, 3),
        "worst_ms": round(worst.get("duration_ns", 0) / 1e6, 3),
        "worst_thread": worst.get("thread_name", ""),
        "seconds": sorted({int((p["start_ns"] - origin) // 1e9)
                           for p in pauses}),
    }


def _pages_read_share(recs: List[dict]) -> Optional[float]:
    """Σ pages read / Σ table pages (``lanes`` x ``ctx_blocks`` a
    micro-step) of a phase's dispatches: the share of the tables' width
    that the attention read. A paged kernel reads what lies under the
    lanes' lengths (``ctx_pages``); a family that gathers its table, the
    width it took of every lane's (``pages_gathered``: 1.0 where it takes
    the whole table). None where no record carries either (a phase with no
    block table, an older dump)."""
    pages = sum(int(r.get("pages_gathered", r.get("ctx_pages", 0)))
                for r in recs)
    table = sum(int(r.get("lanes", 0)) * int(r.get("ctx_blocks", 0))
                * max(int(r.get("micro_steps", 1) or 1), 1) for r in recs)
    if not pages or not table:
        return None
    return round(pages / table, 3)


def _straight_share(recs: List[dict]) -> Optional[dict]:
    """Of a phase's dispatches whose attention is the paged kernel
    (``attn_straight`` on the record: which of the kernel's two bodies the
    executable holds, a fact of its shapes), the share that ran the
    straight-line body, by dispatches and by ``ctx_pages`` read. None where
    no record carries the field (another kind of attention, an older dump,
    a phase with no block table)."""
    told = [r for r in recs if "attn_straight" in r]
    if not told:
        return None
    straight = [r for r in told if r["attn_straight"]]
    pages = sum(int(r.get("ctx_pages", 0)) for r in told)
    return {
        "n": len(told),
        "dispatches": round(len(straight) / len(told), 3),
        "ctx_pages": (round(sum(int(r.get("ctx_pages", 0))
                                for r in straight) / pages, 3)
                      if pages else None),
    }


def _dash(value) -> str:
    return "-" if value is None else str(value)


def _routing(recs: List[dict]) -> Optional[dict]:
    """What the router of a routed family did in a phase's dispatches
    (stepscope ``ROUTING_FIELDS``, read back by the delivery thread): the
    tokens routed a dispatch, the share of the experts held that got a
    token, the most loaded expert against the mean, and how often the
    experts' product streamed an expert's matrices for each expert hit (1 =
    once each; more where the product takes ``f`` in tiles and an expert's
    rows lie in two row tiles; absent from a dump older than the counter).
    None where no record carries the counters (another family, or an older
    dump)."""
    routed = [r for r in recs
              if r.get("experts_held") and r.get("routed_tokens")]
    if not routed:
        return None
    n = len(routed)
    hit = sum(r["experts_hit"] for r in routed)
    row = {
        "n": n,
        "routed_tokens_per_step": round(
            sum(r["routed_tokens"] for r in routed) / n, 1),
        "experts_hit_share": round(
            hit / sum(r["experts_held"] for r in routed), 4),
        # sum over sum: a dispatch counts by the tokens it routed
        "load_max_over_mean": round(
            sum(r["expert_load_max"] for r in routed)
            / sum(r["expert_load_mean"] for r in routed), 2),
    }
    if hit and all("expert_passes" in r for r in routed):
        row["passes_per_hit"] = round(
            sum(r["expert_passes"] for r in routed) / hit, 3)
    return row


def analyze(records: List[dict],
            compiles: Optional[Dict[str, Dict[str, dict]]] = None,
            requests: Optional[List[dict]] = None,
            deliveries: Optional[List[dict]] = None,
            slot_updates: Optional[List[dict]] = None,
            pauses: Optional[List[dict]] = None) -> dict:
    """Per-model verdict + per-phase quantiles and stage means; when the
    dump carries the compile plane, each model also gets its per-callable
    cache-entry/retrace totals, and from a stepscope dump its loop states,
    its deliveries' table, its slot-state updates' row and its requests'
    table; the collector's row is the process's (``collector``)."""
    by_model: Dict[str, List[dict]] = {}
    for r in records:
        by_model.setdefault(r.get("model", ""), []).append(r)
    models = {}
    for model, every in sorted(by_model.items()):
        recs = [r for r in every
                if r.get("phase") not in _stepscope.LOOP_STATES]
        if not recs:
            continue
        phases = {}
        for phase in sorted({r.get("phase", "") for r in recs}):
            ph = [r for r in recs if r.get("phase", "") == phase]
            totals = sorted(int(r.get("total_us", 0)) for r in ph)
            n = len(ph)
            phases[phase] = {
                "n": n,
                "p50_us": _percentile(totals, 0.50),
                "p99_us": _percentile(totals, 0.99),
                "mean_us": {stage: int(v)
                            for stage, v in _stage_means(ph).items()},
                "collectives_per_step": round(
                    sum(_coll_count(r.get("collectives")) for r in ph) / n, 2
                ),
                "mean_batch": round(
                    sum(int(r.get("batch_size", 0)) for r in ph) / n, 2
                ),
                # Paged-KV traffic (PR 16): bytes the gathered view
                # touched per step; absent on pre-kv dumps.
                "kv_bytes_per_step": round(
                    sum(int(r.get("kv_bytes", 0)) for r in ph) / n
                ),
                # What the work was: positions computed and context held
                # by the real lanes per dispatch; absent on older dumps.
                "tokens_per_step": round(
                    sum(int(r.get("tokens", 0)) for r in ph) / n, 1
                ),
                "ctx_tokens_per_step": round(
                    sum(int(r.get("ctx_tokens", 0)) for r in ph) / n, 1
                ),
                # Table entries under the lanes' lengths (what a paged
                # kernel visits) over the tables' whole width (what a
                # gather reads: lanes x ctx_blocks a micro-step); absent
                # on dumps from before the record had ``ctx_pages``.
                "pages_read_share": _pages_read_share(ph),
            }
            # The dispatch brackets' time off the CPU (begin -> dispatched).
            off_cpu = _off_cpu(ph, _dispatch_us)
            if off_cpu is not None:
                phases[phase]["off_cpu"] = {
                    "total_ms": round(
                        sum(_dispatch_us(r) for r in ph) / 1000, 3),
                    **off_cpu}
            routing = _routing(ph)
            if routing is not None:
                phases[phase]["routing"] = routing
            straight = _straight_share(ph)
            if straight is not None:
                phases[phase]["attention"] = straight
        n = len(recs)
        means = _stage_means(recs)
        coll = sum(_coll_count(r.get("collectives")) for r in recs) / n
        micro = sum(int(r.get("micro_steps", 1) or 1) for r in recs) / n
        models[model] = {
            "n": n,
            "mean_us": {k: round(v, 1) for k, v in means.items()},
            "collectives_per_step": round(coll, 2),
            "micro_steps": round(micro, 2),
            "verdict": _verdict(means.get("dispatch", 0.0),
                                means.get("device"),
                                means.get("other", 0.0), coll),
            "phases": phases,
            "loop_states": _loop_states(every),
            "deliveries": _deliveries(
                [d for d in deliveries or [] if d.get("model") == model]),
            "slot_updates": _slot_updates(
                [u for u in slot_updates or [] if u.get("model") == model]),
            "requests": _request_rows(
                [q for q in requests or [] if q.get("model") == model]),
            "compiles": dict(sorted(((compiles or {}).get(model)
                                     or {}).items())),
        }
    out = {"models": models}
    collector = _collector(pauses or [], records)
    if collector is not None:
        out["collector"] = collector
    return out


def render(analysis: dict) -> str:
    lines = []
    for model, m in analysis["models"].items():
        mu = m["mean_us"]
        total = max(sum(mu.values()), 1)
        shares = " ".join(
            f"{stage}={mu[stage]}us({100 * mu[stage] / total:.0f}%)"
            for stage in STAGES if stage in mu
        )
        verdict = m["verdict"]
        if verdict == VERDICT_NO_DEVICE_CLOCK:
            verdict += f" ({_NO_DEVICE_CLOCK_WHY})"
        lines.append(
            f"{model}: {m['n']} steps, {shares}, "
            f"coll/step={m['collectives_per_step']} -> "
            f"verdict: {verdict}"
        )
        # Compile plane: distinct cache entries and retraces per jitted
        # callable. Retraces growing with step count (rather than
        # plateauing at the bucket-family size) is the TPU017 signal.
        if m.get("compiles"):
            cells = ", ".join(
                f"{fn}={cell['entries']}({cell['retraces']} retraces)"
                for fn, cell in m["compiles"].items()
            )
            lines.append(f"  compiles: {cells}")
        lines.append(
            f"  {'phase':<10} {'n':>6} {'p50_us':>8} {'p99_us':>8} "
            f"{'dispatch':>9} {'device':>8} {'other':>7} {'coll':>6} "
            f"{'batch':>6} {'kv_MB':>8} {'tokens':>8} {'ctx_tok':>9} "
            f"{'pages/table':>11}"
        )
        for phase, ph in m["phases"].items():
            pm = ph["mean_us"]
            kv_mb = ph.get("kv_bytes_per_step", 0) / 1e6
            lines.append(
                f"  {phase:<10} {ph['n']:>6} {ph['p50_us']:>8} "
                f"{ph['p99_us']:>8} {pm.get('dispatch', '-'):>9} "
                f"{pm.get('device', '-'):>8} {pm.get('other', '-'):>7} "
                f"{ph['collectives_per_step']:>6} "
                f"{ph['mean_batch']:>6} {kv_mb:>8.2f} "
                f"{ph.get('tokens_per_step', 0):>8} "
                f"{ph.get('ctx_tokens_per_step', 0):>9} "
                f"{_dash(ph.get('pages_read_share')):>11}"
            )
        # A routed family's router, per phase: how many of the experts a
        # dispatch holds its tokens reached (what it had to read), and how
        # unevenly (the grouped product's longest group).
        for phase, ph in m["phases"].items():
            routing = ph.get("routing")
            if routing:
                lines.append(
                    f"  routing {phase:<14} {routing['n']:>6} dispatches, "
                    f"{routing['routed_tokens_per_step']} tokens routed "
                    f"each, {100 * routing['experts_hit_share']:.1f}% of "
                    f"the experts held hit, load max/mean "
                    f"{routing['load_max_over_mean']}"
                    + (f", {routing['passes_per_hit']} passes an expert hit"
                       if "passes_per_hit" in routing else "")
                )
        # Which body of the paged-attention kernel a phase's executables
        # hold: few-row tables (decode) take the straight-line one.
        for phase, ph in m["phases"].items():
            cell = ph.get("attention")
            if cell:
                of_pages = ("-" if cell["ctx_pages"] is None
                            else f"{100 * cell['ctx_pages']:.1f}%")
                lines.append(
                    f"  attention {phase:<12} {cell['n']:>6} dispatches, "
                    f"{100 * cell['dispatches']:.1f}% on the kernel's "
                    f"straight-line body, {of_pages} of the pages read"
                )
        # What of a phase's dispatch brackets (begin -> dispatched) the engine
        # thread spent off the CPU: in line for the interpreter lock (every
        # transfer and the call give it up), or descheduled.
        for phase, ph in m["phases"].items():
            cell = ph.get("off_cpu")
            if cell:
                lines.append(
                    f"  dispatching {phase:<10} {ph['n']:>6} brackets "
                    f"{cell['total_ms']:>10.3f} ms{_off_cpu_text(cell)}"
                )
        # The delivery thread's view of each dispatch's result (ms): a long
        # queue wait says items stand behind one another, a long readback
        # that the thread waited on the device. Not a device time.
        for phase, cell in (m.get("deliveries") or {}).items():
            spans = " ".join(
                f"{name}={cell[f'{name}_ms']['p50']}/"
                f"{cell[f'{name}_ms']['p95']}"
                for name in ("queue_wait", "readback", "handover"))
            lines.append(
                f"  delivery {phase:<14} {cell['n']:>6} items, "
                f"p50/p95 ms: {spans}"
                + _off_cpu_text(cell, "; hand-overs off-cpu")
            )
        # What the engine thread did between dispatches: a large
        # ticket_wait share says the host runs ahead of the chip.
        for state, cell in (m.get("loop_states") or {}).items():
            lines.append(
                f"  loop {state:<12} {cell['n']:>6} stretches "
                f"{cell['total_ms']:>10.3f} ms "
                f"({100 * cell['share']:.1f}% of the recorded span)"
                + _off_cpu_text(cell)
            )
        # The writes joins, frees and cancels made to the slot state, inside
        # the ``admit`` and ``join`` stretches above: one dispatch a burst.
        updates = m.get("slot_updates")
        if updates:
            lines.append(
                f"  slot updates      {updates['n']:>6} dispatches, "
                f"{updates['joined']} slots joined, {updates['freed']} "
                f"freed, host p50/p95 ms: {updates['host_ms']['p50']}/"
                f"{updates['host_ms']['p95']} "
                f"({updates['host_total_ms']} ms in all)"
                + _off_cpu_text(updates)
            )
        rows = m.get("requests") or []
        if rows:
            lines.append(
                f"  requests: {len(rows)} "
                f"({sum(r['outcome'] == 'finished' for r in rows)} "
                f"finished); slowest waits first"
            )
            # prefill_span = to_chunk + chunking + readback + handover;
            # wake and handler are the worst over the request's tokens;
            # a wait marked * stood behind the page pool, not a slot.
            head = " ".join(
                f"{name[:-3].replace('prefill_span', 'prefill'):>9}"
                for name in REQUEST_SPANS)
            lines.append(
                f"  {'prompt':>7} {'asked':>6} {'outcome':<10} {head} "
                f"{'chunks':>6} {'tokens':>6}   (ms)"
            )

            def cells(row):
                return " ".join(
                    f"{'-' if row[name] is None else row[name]:>9}"
                    for name in REQUEST_SPANS)

            shown = sorted(rows, key=lambda r: -(r["wait_ms"] or 0))
            for r in shown[:REQUEST_ROWS]:
                lines.append(
                    f"  {r['prompt_len']:>7} {r['max_new']:>6} "
                    f"{str(r['outcome']):<10} {cells(r)} "
                    f"{r['chunks']:>6} {r['tokens']:>6}"
                    f"{' *' if r['waited_for_pages'] else ''}"
                )
            if len(rows) > REQUEST_ROWS:
                lines.append(f"  ... {len(rows) - REQUEST_ROWS} more")
            medians = {}
            for name in REQUEST_SPANS:
                values = sorted(r[name] for r in rows
                                if r[name] is not None)
                medians[name] = _percentile(values, 0.50) if values else None
            lines.append(f"  {'median':>7} {'':>6} {'':<10} {cells(medians)}")
    # The interpreter's collector, process-wide: a collection holds the
    # interpreter lock, so every thread above stalled for each.
    collector = analysis.get("collector")
    if collector:
        by_generation = ", ".join(
            f"gen {gen}: {n}" for gen, n in collector["by_generation"].items())
        lines.append(
            f"collector: {collector['n']} collections ({by_generation}), "
            f"{collector['total_ms']} ms in all, worst "
            f"{collector['worst_ms']} ms on {collector['worst_thread']}, "
            f"begun in seconds {collector['seconds']} of the recorded span"
        )
    return "\n".join(lines)


def compare(a: dict, b: dict, label_a: str = "A",
            label_b: str = "B") -> str:
    """tp=1 vs tp=2 mode: line up the two runs' per-phase quantiles and
    verdicts, with B/A slowdown ratios per shared phase."""
    lines = [f"-- {label_a} --", render(a), f"-- {label_b} --", render(b),
             "-- comparison --"]
    models_a, models_b = a["models"], b["models"]
    for model_b, mb in models_b.items():
        # Pair by exact model name first, else by position (tp runs may
        # serve the same config under a different scope name).
        ma = models_a.get(model_b)
        model_a = model_b
        if ma is None and len(models_a) == 1:
            model_a, ma = next(iter(models_a.items()))
        if ma is None:
            continue
        lines.append(
            f"{label_a}[{model_a}]: {ma['verdict']} vs "
            f"{label_b}[{model_b}]: {mb['verdict']}"
        )
        for phase, phb in mb["phases"].items():
            pha = ma["phases"].get(phase)
            if pha is None or not pha["p50_us"]:
                continue
            r50 = phb["p50_us"] / max(pha["p50_us"], 1)
            r99 = phb["p99_us"] / max(pha["p99_us"], 1)
            line = (
                f"  {phase}: p50 {pha['p50_us']} -> {phb['p50_us']} us "
                f"({r50:.2f}x), p99 {pha['p99_us']} -> {phb['p99_us']} us "
                f"({r99:.2f}x), coll/step "
                f"{pha['collectives_per_step']} -> "
                f"{phb['collectives_per_step']}"
            )
            lines.append(line)
    return "\n".join(lines)


# -- MULTICHIP bench tail --------------------------------------------------- #


def bench_tail_summary(doc: dict) -> Optional[dict]:
    """Extract the ``[tp-engine-stepscope]`` breakdown a MULTICHIP bench
    record carries in its tail (written by __graft_entry__)."""
    tail = doc.get("tail")
    if not isinstance(tail, str):
        return None
    for line in tail.splitlines():
        line = line.strip()
        if line.startswith(_BENCH_TAG):
            try:
                return json.loads(line[len(_BENCH_TAG):].strip())
            except json.JSONDecodeError:
                return None
    return None


def render_bench(summary: dict) -> str:
    tp = summary.get("tp", "?")
    lines = [f"MULTICHIP stepscope breakdown (tp={tp} vs tp=1):"]
    for key, label in (("tp", f"tp={tp}"), ("tp1", "tp=1")):
        row = summary.get(f"{key}_decode") or {}
        verdict = summary.get(f"{key}_verdict", "?")
        if row:
            lines.append(
                f"  {label}: decode p50={row.get('p50_us')}us "
                f"p99={row.get('p99_us')}us "
                f"dispatch={row.get('dispatch_us')}us "
                + "".join(f"{stage}={row[f'{stage}_us']}us "
                          for stage in ("device", "other")
                          if row.get(f"{stage}_us") is not None)
                + f"coll/step={row.get('collectives_per_step')} -> "
                f"verdict: {verdict}"
            )
        else:
            lines.append(f"  {label}: verdict: {verdict}")
    return "\n".join(lines)


# -- self-check ------------------------------------------------------------- #


def _synthetic_dump(dispatch_us: int, device_us: int, other_us: int,
                    coll_per_step: int, model: str = "gpt_engine",
                    n: int = 24, micro_steps: int = 1) -> dict:
    """Deterministic stepscope-kind dump (no RNG: a fixed per-step jitter
    pattern keeps quantiles meaningful and reproducible)."""
    records = []
    for i in range(n):
        jitter = (i * 7) % 5  # 0..4 us, fixed pattern
        d, dev, o = dispatch_us + jitter, device_us + jitter, other_us
        # Phase pattern mirrors the paged engine's real mix: mostly
        # decode, with chunked-prefill records interleaved (plus one
        # legacy whole-prompt prefill so both spellings stay covered).
        phase = ("prefill" if i == 0
                 else "prefill_chunk" if i % 4 == 0
                 else "decode")
        records.append({
            "model": model,
            "phase": phase,
            "step_index": i,
            "batch_size": 4,
            "start_ns": 1_000_000 + i * 1_000_000,
            "dispatch_us": d,
            "device_us": dev,
            "other_us": o,
            "total_us": d + dev + o,
            "collectives": (
                {"psum": {"count": coll_per_step, "bytes": 0}}
                if coll_per_step else {}
            ),
            # Prefills are never fused.
            "micro_steps": micro_steps if phase == "decode" else 1,
            "thread_ident": 42,
            "thread_name": "gpt-engine",
            # KV traffic scales with fused depth on decode, is a single
            # chunk's worth on prefill — mirrors the engine's charging.
            "kv_bytes": (4_000_000 * micro_steps if phase == "decode"
                         else 1_000_000),
        })
    return {
        # device_us/other_us on every record: what a ``sync`` run dumps.
        "kind": "stepscope", "mode": "sync", "records": records,
        # Compile plane: the well-bucketed shape — a handful of entries,
        # retraces = entries - 1 (each new bucket paid one compile).
        "compiles": {
            f"{model}|decode_step": {"entries": 2, "retraces": 1},
            f"{model}|prefill_chunk": {"entries": 3, "retraces": 2},
        },
    }


def self_check() -> int:
    """Three synthetic dumps with known dominant stages must recover
    their verdicts through load/analyze/render, via the stepscope loader
    AND the Perfetto track round-trip; the flight-dump loader must
    recover the slowest-step stamp."""
    failures = 0
    cases = [
        ("dispatch-heavy", _synthetic_dump(900, 80, 40, 0),
         VERDICT_DISPATCH),
        ("device-heavy", _synthetic_dump(60, 900, 20, 0), VERDICT_DEVICE),
        ("collective-heavy", _synthetic_dump(60, 900, 20, 16),
         VERDICT_COLLECTIVE),
    ]
    for label, dump, want in cases:
        analysis = analyze(load_records(dump))
        got = analysis["models"]["gpt_engine"]["verdict"]
        if got != want:
            print(f"self-check [{label}]: verdict {got} != {want}",
                  file=sys.stderr)
            failures += 1
            continue
        rendered = render(analysis)
        if (want not in rendered or "decode" not in rendered
                or "prefill_chunk" not in rendered):
            print(f"self-check [{label}]: render missing verdict/phase",
                  file=sys.stderr)
            failures += 1
            continue
        print(f"self-check [{label}]: ok ({got})")
    # Perfetto round-trip: stepscope events -> loader -> same verdict.
    dump = cases[2][1]
    events = []
    for r in dump["records"]:
        events.append({
            "name": f"{r['model']}/{r['phase']}[{r['step_index']}]",
            "cat": "stepscope", "ph": "X",
            "ts": r["start_ns"] / 1000.0, "dur": r["total_us"],
            "pid": 7, "tid": r["thread_ident"],
            "args": {
                "model": r["model"], "phase": r["phase"],
                "step_index": str(r["step_index"]),
                "batch_size": str(r["batch_size"]),
                "dispatch_us": str(r["dispatch_us"]),
                "device_us": str(r["device_us"]),
                "other_us": str(r["other_us"]),
                "collectives": str(_coll_count(r["collectives"])),
            },
        })
    perfetto_doc = {"displayTimeUnit": "ns", "traceEvents": events}
    analysis = analyze(load_records(perfetto_doc))
    got = analysis["models"]["gpt_engine"]["verdict"]
    if got != VERDICT_COLLECTIVE:
        print(f"self-check [perfetto]: verdict {got} != "
              f"{VERDICT_COLLECTIVE}", file=sys.stderr)
        failures += 1
    else:
        print("self-check [perfetto]: ok")
    # Flight-dump loader: the slowest-step stamp round-trips.
    flight = {
        "kind": "flight_recorder",
        "records": [{
            "model_name": "gpt_engine",
            "attributes": {
                "step.slowest.phase": "decode",
                "step.slowest.index": 9,
                "step.slowest.batch_size": 4,
                "step.slowest.total_us": 1500,
                "step.slowest.dispatch_us": 1200,
                "step.slowest.device_us": 250,
                "step.slowest.other_us": 50,
                "step.slowest.collectives": 0,
            },
        }],
    }
    analysis = analyze(load_records(flight))
    got = analysis["models"]["gpt_engine"]["verdict"]
    if got != VERDICT_DISPATCH:
        print(f"self-check [flight]: verdict {got} != {VERDICT_DISPATCH}",
              file=sys.stderr)
        failures += 1
    else:
        print("self-check [flight]: ok")
    dump = _synthetic_dump(60, 700, 20, 16, micro_steps=4)
    analysis = analyze(load_records(dump))
    m = analysis["models"]["gpt_engine"]
    decode = m["phases"]["decode"]
    # KV traffic column: per-phase bytes-touched means must survive the
    # loader and surface in the rendered table (decode fused 4x deep
    # charges 16 MB/step vs 1 MB/step on prefill chunks).
    if (decode.get("kv_bytes_per_step") != 16_000_000
            or m["phases"]["prefill_chunk"]["kv_bytes_per_step"]
            != 1_000_000
            or "kv_MB" not in render(analysis)
            or "16.00" not in render(analysis)):
        print("self-check [kv-bytes]: kv_bytes column lost",
              file=sys.stderr)
        failures += 1
    else:
        print("self-check [kv-bytes]: ok")
    # Compile plane: the dump's per-callable entry/retrace totals must
    # survive load_compiles/analyze and surface in the rendered report.
    dump = _synthetic_dump(60, 700, 20, 0)
    analysis = analyze(load_records(dump), load_compiles(dump))
    m = analysis["models"]["gpt_engine"]
    rendered = render(analysis)
    if (m["compiles"].get("decode_step") != {"entries": 2, "retraces": 1}
            or "compiles:" not in rendered
            or "prefill_chunk=3(2 retraces)" not in rendered):
        print("self-check [compiles]: compile plane lost",
              file=sys.stderr)
        failures += 1
    else:
        print("self-check [compiles]: ok")
    # A counters-mode dump: no device stage on any record, loop states in
    # the ring, finished requests in their own. No bound may be named; the
    # loop states stay out of the step tables; the request table renders.
    dump = _synthetic_dump(60, 700, 20, 0)
    dump["mode"] = "counters"
    for r in dump["records"]:
        del r["device_us"], r["other_us"]
    dump["records"] += [
        {"model": "gpt_engine", "phase": "ticket_wait", "step_index": 0,
         "batch_size": 0, "slots": 8, "start_ns": 30_000_000,
         "dispatch_us": 4000, "total_us": 4000, "micro_steps": 0,
         "collectives": {}, "thread_ident": 42, "thread_name": "gpt-engine",
         "cpu_us": 40, "runq_us": 10},
        {"model": "gpt_engine", "phase": "admit", "step_index": 0,
         "batch_size": 0, "slots": 8, "start_ns": 40_000_000,
         "dispatch_us": 500, "total_us": 500, "micro_steps": 0,
         "collectives": {}, "thread_ident": 42, "thread_name": "gpt-engine",
         "cpu_us": 100, "runq_us": 50},
    ]
    dump["requests"] = [{
        "model": "gpt_engine", "key": [7, 40, 3], "recv_ns": 900_000,
        "core_ns": 950_000, "submit_ns": 1_000_000,
        "admitted_ns": 3_000_000, "waited_for_pages": False,
        "first_chunk_ns": 4_000_000, "last_chunk_ns": 5_000_000,
        "chunks": 2, "first_ready_ns": 8_000_000,
        "out_ns": [9_000_000, 10_000_000, 14_000_000],
        "taken_ns": [9_100_000, 10_700_000, 14_200_000],
        "resumed_ns": [9_150_000, 10_750_000, 14_500_000],
        "end_ns": 14_100_000, "outcome": "finished",
    }]
    for r in dump["records"]:
        r["tokens"], r["ctx_tokens"] = 4, 400
        if r["phase"] == "decode":      # 8 lanes x 64 entries, 128 live
            r.update(lanes=8, ctx_blocks=64, ctx_pages=128,
                     attn_straight=True,
                     cpu_us=r["dispatch_us"] - 20, runq_us=5)
        if r["phase"] == "prefill_chunk":   # the kernel's looped body
            r.update(ctx_pages=32, attn_straight=False)
        if r["phase"] == "decode":      # a routed family's counters
            r.update(routed_tokens=4, experts_hit=24, experts_held=64,
                     expert_load_max=2, expert_load_mean=0.5,
                     expert_passes=30)
    dump["deliveries"] = [
        {"model": "gpt_engine", "phase": "decode", "step_index": i,
         "queued_ns": 1_000_000 * i, "taken_ns": 1_000_000 * i + 250_000,
         "ready_ns": 1_000_000 * i + 750_000,
         "delivered_ns": 1_000_000 * i + 800_000,
         "cpu_us": 30, "runq_us": 10} for i in (1, 2, 3)]
    dump["slot_updates"] = [
        {"model": "gpt_engine", "joined": 3, "freed": 0,
         "start_ns": 40_100_000, "host_ns": 300_000, "cpu_us": 100,
         "runq_us": 40},
        {"model": "gpt_engine", "joined": 0, "freed": 2,
         "start_ns": 41_000_000, "host_ns": 100_000, "cpu_us": 100,
         "runq_us": 0}]
    dump["gc"] = [
        {"start_ns": 2_000_000, "duration_ns": 3_000_000, "generation": 0,
         "thread_ident": 42, "thread_name": "gpt-engine"},
        {"start_ns": 1_002_000_000, "duration_ns": 7_500_000,
         "generation": 2, "thread_ident": 7, "thread_name": "handler"}]
    analysis = analyze(load_records(dump), load_compiles(dump),
                       load_requests(dump), load_deliveries(dump),
                       load_slot_updates(dump), load_pauses(dump))
    m = analysis["models"]["gpt_engine"]
    rendered = render(analysis)
    if (m["verdict"] != VERDICT_NO_DEVICE_CLOCK
            or "device" in m["mean_us"] or m["n"] != 24
            or "ticket_wait" in m["phases"]
            or m["loop_states"]["ticket_wait"]["total_ms"] != 4.0
            or {k: m["loop_states"]["admit"].get(k) for k in (
                "off_cpu_ms", "off_cpu_share", "runq_ms", "runq_share")} != {
                "off_cpu_ms": 0.4, "off_cpu_share": 0.8, "runq_ms": 0.05,
                "runq_share": 0.1}
            or "off-cpu 0.4 ms (80.0%), run-queue 0.05 ms (10.0%)"
            not in rendered
            or m["phases"]["decode"]["off_cpu"]["off_cpu_ms"]
            != 0.02 * m["phases"]["decode"]["n"]
            or "dispatching decode" not in rendered
            or "off_cpu" in m["phases"]["prefill"]
            or analysis.get("collector") != {
                "n": 2, "by_generation": {"0": 1, "2": 1},
                "total_ms": 10.5, "worst_ms": 7.5,
                "worst_thread": "handler", "seconds": [0, 1]}
            or "collector: 2 collections (gen 0: 1, gen 2: 1)"
            not in rendered
            or m["requests"] != [{
                "prompt_len": 40, "max_new": 3, "outcome": "finished",
                "recv_ms": 0.05, "core_ms": 0.05, "wait_ms": 2.0,
                "waited_for_pages": False, "to_chunk_ms": 1.0,
                "chunking_ms": 1.0, "readback_ms": 3.0, "handover_ms": 1.0,
                "wake_ms": 0.7, "handler_ms": 0.3,
                "prefill_span_ms": 6.0, "chunks": 2, "tokens": 3,
                "worst_gap_ms": 4.0}]
            or m["phases"]["decode"]["ctx_tokens_per_step"] != 400
            or m["phases"]["decode"]["pages_read_share"] != 0.25
            or "pages/table" not in rendered
            or m["phases"]["decode"].get("attention") != {
                "n": m["phases"]["decode"]["n"], "dispatches": 1.0,
                "ctx_pages": 1.0}
            or m["phases"]["prefill_chunk"].get("attention") != {
                "n": m["phases"]["prefill_chunk"]["n"], "dispatches": 0.0,
                "ctx_pages": 0.0}
            or "attention" in m["phases"]["prefill"]
            or "attention decode" not in rendered
            or "100.0% on the kernel's straight-line body" not in rendered
            or m["phases"]["decode"].get("routing") != {
                "n": m["phases"]["decode"]["n"],
                "routed_tokens_per_step": 4.0, "experts_hit_share": 0.375,
                "load_max_over_mean": 4.0, "passes_per_hit": 1.25}
            or "1.25 passes an expert hit" not in rendered
            or m["deliveries"] != {"decode": {
                "n": 3, "queue_wait_ms": {"p50": 0.25, "p95": 0.25},
                "readback_ms": {"p50": 0.5, "p95": 0.5},
                "handover_ms": {"p50": 0.05, "p95": 0.05},
                "off_cpu_ms": 0.06, "off_cpu_share": 0.4,
                "runq_ms": 0.03, "runq_share": 0.2}}
            or "hand-overs off-cpu 0.06 ms (40.0%)" not in rendered
            or m["slot_updates"] != {
                "n": 2, "joined": 3, "freed": 2,
                "host_ms": {"p50": 0.3, "p95": 0.3}, "host_total_ms": 0.4,
                "off_cpu_ms": 0.2, "off_cpu_share": 0.5,
                "runq_ms": 0.04, "runq_share": 0.1}
            or "slot updates" not in rendered
            or "records no device time" not in rendered
            or "loop ticket_wait" not in rendered
            or "delivery decode" not in rendered
            or "worst_gap" not in rendered or "median" not in rendered):
        print("self-check [counters]: device clock invented, or loop "
              "states / deliveries / slot updates / requests / routing / "
              "the attention body / the time off the CPU / the collector "
              "lost",
              file=sys.stderr)
        failures += 1
    else:
        print("self-check [counters]: ok")
    # Compare mode renders ratios for shared phases.
    a = analyze(load_records(_synthetic_dump(60, 200, 20, 0)))
    b = analyze(load_records(_synthetic_dump(60, 700, 20, 16,
                                             micro_steps=4)))
    text = compare(a, b, "tp=1", "tp=2")
    if ("decode: p50" not in text or VERDICT_COLLECTIVE not in text
            or "coll/step 0.0 -> 16.0" not in text):
        print("self-check [compare]: comparison incomplete",
              file=sys.stderr)
        failures += 1
    else:
        print("self-check [compare]: ok")
    # Bench-tail extraction.
    tail_doc = {"tail": (
        "dryrun_multichip[tp-engine-genai]: ...\n"
        + _BENCH_TAG + ' {"tp": 2, "tp_verdict": "collective-bound", '
        '"tp1_verdict": "dispatch-bound", "tp_decode": {"p50_us": 90, '
        '"p99_us": 120, "dispatch_us": 20, "device_us": 60, '
        '"other_us": 10, "collectives_per_step": 4.0, '
        '"micro_steps": 4}, "tp1_decode": '
        '{"p50_us": 30, "p99_us": 40, "dispatch_us": 20, '
        '"device_us": 8, "other_us": 2, "collectives_per_step": 0.0}}\n'
    )}
    summary = bench_tail_summary(tail_doc)
    if (not summary or "collective-bound" not in render_bench(summary)
            or "coll/step=4.0" not in render_bench(summary)):
        print("self-check [bench-tail]: extraction failed",
              file=sys.stderr)
        failures += 1
    else:
        print("self-check [bench-tail]: ok")
    if failures:
        print(f"self-check: {failures} failure(s)", file=sys.stderr)
        return 1
    print("self-check: verdicts recovered through every loader")
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        prog="step_report",
        description="Dominant-stage verdict for engine step records",
    )
    parser.add_argument("dump_file", nargs="?",
                        help="stepscope dump, flight dump, trace file, "
                        "or MULTICHIP bench record")
    parser.add_argument("--compare", metavar="DUMP_B",
                        help="second dump (e.g. tp=2) to line up against "
                        "dump_file (e.g. tp=1)")
    parser.add_argument("--json", dest="as_json", action="store_true",
                        help="emit the analysis as JSON")
    parser.add_argument("--self-check", action="store_true",
                        help="run the synthetic verdict checks and exit")
    args = parser.parse_args(argv)
    if args.self_check:
        return self_check()
    if not args.dump_file:
        parser.error("a dump file is required (or --self-check)")
    try:
        with open(args.dump_file) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        print(f"unable to load {args.dump_file}: {e}", file=sys.stderr)
        return 1
    bench = bench_tail_summary(doc) if isinstance(doc, dict) else None
    if bench is not None:
        print(json.dumps(bench, indent=2) if args.as_json
              else render_bench(bench))
        return 0
    try:
        records = load_records(doc)
    except ValueError as e:
        print(f"unable to parse {args.dump_file}: {e}", file=sys.stderr)
        return 1
    if not records:
        print(f"{args.dump_file}: no step records (is TPU_STEPSCOPE on?)",
              file=sys.stderr)
        return 1
    analysis = analyze(records, load_compiles(doc), load_requests(doc),
                       load_deliveries(doc), load_slot_updates(doc),
                       load_pauses(doc))
    if args.compare:
        try:
            with open(args.compare) as f:
                other_doc = json.load(f)
            other = load_records(other_doc)
        except (OSError, ValueError) as e:
            print(f"unable to load {args.compare}: {e}", file=sys.stderr)
            return 1
        if not other:
            print(f"{args.compare}: no step records", file=sys.stderr)
            return 1
        print(compare(analysis, analyze(other, load_compiles(other_doc)),
                      os.path.basename(args.dump_file),
                      os.path.basename(args.compare)))
        return 0
    try:
        print(json.dumps(analysis, indent=2) if args.as_json
              else render(analysis))
    except BrokenPipeError:
        return 0
    return 0


if __name__ == "__main__":
    sys.exit(main())
