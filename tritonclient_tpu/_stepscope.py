"""stepscope: per-step engine profiling plane.

The observability stack stops at ``compute``: a request span says how long
the model ran, not where an engine *step* spent its time. This module is
the missing layer — a low-overhead clock the decode/prefill loops in
``models/gpt_engine.py`` and the dynamic batcher's compute phase stamp
where the work happens. All times are ``time.monotonic_ns()``. It keeps
five rings (``dump()["records"]``, ``dump()["deliveries"]``,
``dump()["slot_updates"]``, ``dump()["requests"]`` and ``dump()["gc"]``):

**Dispatch records** — one per device dispatch, opened on the dispatching
thread:

- step index, phase (``prefill`` / ``prefill_chunk`` / ``decode`` /
  ``compute``), batch size and slot occupancy;
- what the work was: ``lanes`` (the padded lane bucket; ``max_slots`` for
  decode), ``ctx_blocks`` (the width of the block table the executable is
  given: what a table-wide gather reads, every lane and micro-step),
  ``ctx_pages`` (the table entries under the real lanes' lengths, summed
  over the micro-steps: what a paged-attention kernel visits, and the GPT
  family's does), ``tokens`` (positions computed) and ``ctx_tokens``
  (context really held by the real lanes), all from host-side state; for a
  family whose layers are of two kinds, ``ctx_pages_global`` and
  ``ctx_pages_window`` (the pages a layer of each kind read: a window
  layer's are its window's, not the context's) and
  ``kv_held_global_bytes`` / ``kv_held_window_bytes`` (what the dispatch's
  requests hold in the layers of each kind: the window layers' stops
  growing at their ring); for a family whose attention is the paged
  kernel, ``attn_straight``: whether this dispatch's executable holds the
  kernel's straight-line body (few-row tables: decode) or its looped one
  (``ops.paged_attention.straight_line``, a fact of the shapes);
- ``dispatch_us``: host time from step begin to dispatch return (trace +
  XLA dispatch of the jitted call); the same bracket is a
  ``jax.profiler.TraceAnnotation`` named ``{model}/{phase}``, so a profile
  opened in Perfetto or TensorBoard shows the host span beside the
  device's module; and what of that bracket the thread spent ON the CPU and
  in line for one (``cpu_us``, ``runq_us``: "Off the CPU", below);
- for a family with a routed expert layer, what the router did
  (``ROUTING_FIELDS``): ``routed_tokens``, ``experts_hit`` (distinct
  experts that got a token, summed over the expert layers and the
  micro-steps) of ``experts_held`` (layers x micro-steps x experts),
  ``expert_load_max`` (the most tokens on one expert of one layer) against
  ``expert_load_mean``, ``pairs_elsewhere`` (the (token, expert) pairs
  whose expert another chip holds: 0 where the program holds them all; the
  experts counted are the held ones) and ``expert_passes`` (how often the
  experts' product streamed an expert's matrices,
  ``ops.grouped_experts.passes``: ``experts_hit`` where every hit expert
  was read once). They come from the histogram the step returns,
  which the delivery thread reads back behind the tokens (``step_routing``):
  a record that is read before that has no such fields yet; for a family
  whose residual path is several streams mixed by per-token maps
  (``models/mhc.py``) the same read-back adds ``RESIDUAL_FIELDS``:
  ``hc_streams`` (the streams a token, n) and ``hc_rows`` (live rows x the
  sublayers whose maps they passed, two a layer, summed over the
  micro-steps), from which a reader reckons the maps' bytes (``hc_rows`` x
  3 passes x n x hidden x the streams' item size);
- ``device_us`` / ``other_us``: **``sync`` mode only** — a bracketed
  ``jax.block_until_ready`` (true device wait) and the clamped remainder.
  Counters mode has no device clock: the engine thread's post-dispatch
  remainder is a few microseconds of bookkeeping, so the record carries
  neither field and ``/metrics`` no ``device``/``other`` stage;
- collective count/bytes, accumulated by ``note_collective`` at the
  ``parallel/`` call sites through a thread-local step context, or charged
  as an expected per-step count for GSPMD-implicit all-reduces
  (``expected_tp_collectives``). A count, never a time: what a
  collective costs, and how much of it no compute hides, is read from a
  device trace.

**Delivery records** — one per delivery item, written by the engine's
delivery thread when it is done with the item, and joined to the dispatch
that made the item by ``(model, phase, step_index)``: ``queued_ns``,
``taken_ns``, ``ready_ns`` (readback returned), ``delivered_ns`` (last
token handed to its request), and ``cpu_us`` / ``runq_us`` of the hand-over
``ready_ns`` -> ``delivered_ns``. ``ready_ns`` is when *that thread saw* the
result, in the order it serves its two queues — NOT the device's
completion time; no device time may be derived from it. A dispatch with no
delivery item (a prefill chunk that finishes no prompt) has no delivery
record, and nothing is added to observe it.

**Engine-loop states** (``LOOP_STATES``: ``ticket_wait``, ``idle_wait``,
``admit``, ``join``) — what the engine thread did between dispatches, as
records in the same ring with ``dispatch_us`` = their duration and the
stretch's ``cpu_us`` / ``runq_us``. They overlap neither a dispatch record
nor each other, and reach the ring only: no sketch, no ``/metrics`` row.

**Off the CPU** — every stretch the engine loop and the delivery thread
bracket (a loop state, a dispatch bracket, a slot update, a delivery's
hand-over) is clocked three ways at both ends (``clock()``): the wall
(``monotonic_ns``), the thread's own CPU time (``time.thread_time_ns``)
and the thread's run-queue delay (second field of
``/proc/self/task/<tid>/schedstat``, one ``os.pread`` on a descriptor the
thread opens once). The record carries ``cpu_us`` and, where that file can
be read, ``runq_us``. ``wall - cpu`` in ``admit``, ``join``, a dispatch
bracket or a slot update is time the thread neither ran nor meant to wait:
it SLEPT in line for the interpreter lock, or it was RUNNABLE and its core
was taken; ``runq_us`` is the second part. In ``ticket_wait`` and
``idle_wait`` the thread means to wait and the difference says nothing.

**Slot-update records** — one per dispatch of the engine's slot-state
update (``gpt_engine._update_slots``: the one program through which joins,
frees and cancels write the per-slot device state): ``joined`` and
``freed`` (the slots the call carried), ``start_ns`` / ``host_ns``, the
host time from building the call's arrays to its return, and that
stretch's ``cpu_us`` / ``runq_us``. They lie INSIDE a
``join`` or ``admit`` stretch, so they have a ring of their own and are no
loop state.

**Request records** — one per generation, written once by the thread that
ends it: receipt and core stamps copied from the request's
``TraceContext``, then submit, admission, first/last prefill chunk, first
token ready, every token's hand-over, end and outcome (``RequestRecord``).
Beside each token's hand-over (``out_ns[i]``, the delivery thread's put) the
stream handler's thread stamps ``taken_ns[i]`` (its ``req.out.get`` has
returned the token: it is awake and holds the interpreter lock) and
``resumed_ns[i]`` (the response generator is resumed after the token's
``yield``: the core's response, the protobuf message and grpcio's send of it
are done on the server's side). The handler may still be taking tokens
when the delivery thread ends the request, so the ring holds the record
itself and ``dump()`` takes the copy.

**Collector pauses** (``dump()["gc"]``) — while stepscope is on a
``gc.callbacks`` hook records every collection of the interpreter's
collector: ``start_ns``, ``duration_ns``, ``generation`` and the thread it
ran on. A collection holds the interpreter lock, so it stalls every thread
of the process, the engine loop included. The hook is registered by
``configure`` and removed when stepscope goes off, as the threads'
``schedstat`` descriptors are closed then.

The module also carries a tiny in-flight plane: ``inflight_update`` tracks
how many decode dispatches each engine currently has in flight (the
pipelined dispatch window), exported as the
``nv_engine_inflight_steps`` gauge.

Dispatch records land in three existing sinks rather than a new one:
``/metrics`` (``nv_engine_step_duration_us_quantiles`` +
``nv_engine_collectives_total``, via ``metrics_snapshot``), the flight
recorder (``flight_attributes`` stamps the slowest step's breakdown onto
retained records), and the Perfetto exporters (``perfetto_events`` emits
one thread-scoped track per engine thread — orphan tracks with no request
parent, which the loaders accept). ``scripts/step_report.py`` turns a
``dump()`` into per-phase tables, the deliveries' queue wait and readback,
the loop-state shares with their time off the CPU, the collector's row, a
per-request table and — from a ``sync`` dump only — a dispatch-bound /
device-bound / collective-bound verdict.

Activation: ``TPU_STEPSCOPE=1`` (cheap counters), ``TPU_STEPSCOPE=sync``
(adds ``block_until_ready`` bracketing). Off by default; the off path is
one module-global read per step and per submitted request. All locks go
through ``sanitize.named_lock`` so the runtime sanitizer sees them.
"""

from __future__ import annotations

import gc
import os
import threading
import time
import zlib
from collections import deque
from typing import Dict, List, Optional, Tuple

from tritonclient_tpu import sanitize
from tritonclient_tpu._sketch import LatencySketch

# -- modes ------------------------------------------------------------------ #

MODE_OFF = "off"
MODE_COUNTERS = "counters"
MODE_SYNC = "sync"
MODES = (MODE_OFF, MODE_COUNTERS, MODE_SYNC)

# -- canonical vocabularies (mirrored by check_metrics_exposition.py) ------- #

STAGE_DISPATCH = "dispatch"
STAGE_DEVICE = "device"
STAGE_OTHER = "other"
STEP_STAGES = (STAGE_DISPATCH, STAGE_DEVICE, STAGE_OTHER)

PHASE_PREFILL = "prefill"
#: One fixed-size chunk of a paged-KV chunked prefill: prompts stream
#: into blocks interleaved with decode steps, so a long prompt is many
#: prefill_chunk records instead of one monolithic prefill record.
PHASE_PREFILL_CHUNK = "prefill_chunk"
PHASE_DECODE = "decode"
PHASE_COMPUTE = "compute"
STEP_PHASES = (PHASE_PREFILL, PHASE_PREFILL_CHUNK, PHASE_DECODE,
               PHASE_COMPUTE)

# What the engine thread does between dispatches (``loop_state``). Ring
# only: these never reach a sketch or /metrics, so they are not STEP_PHASES.
LOOP_TICKET_WAIT = "ticket_wait"   # inside try_ticket, the window was full
LOOP_IDLE_WAIT = "idle_wait"       # parked on the condition, nothing to do
LOOP_ADMIT = "admit"               # frees + cancels + admissions that did work
LOOP_JOIN = "join"                 # finished prefills' slot state -> decode bank
LOOP_STATES = (LOOP_TICKET_WAIT, LOOP_IDLE_WAIT, LOOP_ADMIT, LOOP_JOIN)

OUTCOME_FINISHED = "finished"
OUTCOME_CANCELLED = "cancelled"
OUTCOME_ERROR = "error"

STEP_METRIC = "nv_engine_step_duration_us_quantiles"
COLLECTIVES_METRIC = "nv_engine_collectives_total"
INFLIGHT_METRIC = "nv_engine_inflight_steps"
KV_BYTES_METRIC = "nv_engine_kv_bytes_touched_total"
COMPILE_CACHE_METRIC = "nv_engine_compile_cache_entries"
RETRACE_METRIC = "nv_engine_retrace_total"

# Bounded recent-step ring so dumps and Perfetto tracks stay small no
# matter how long the engine runs.
_DEFAULT_RING = 256


def _env_mode() -> str:
    raw = os.environ.get("TPU_STEPSCOPE", "").strip().lower()
    if raw in ("", "0", "off", "false", "no"):
        return MODE_OFF
    if raw == MODE_SYNC:
        return MODE_SYNC
    return MODE_COUNTERS


_mode = _env_mode()


# -- the calling thread's clocks -------------------------------------------- #

# A thread's scheduler statistics: "<ns on a CPU> <ns runnable, waiting on a
# run queue> <timeslices>". Read with one pread on a descriptor the thread
# opens once; the descriptors are closed when stepscope goes off.
_SCHEDSTAT = "/proc/self/task/{tid}/schedstat"
_sched_lock = sanitize.named_lock("stepscope._sched_lock")
_sched_fds: Dict[int, int] = {}     # native thread id -> descriptor
_sched_epoch = 0                    # bumped when the descriptors are closed


def _open_schedstat() -> Optional[int]:
    """The calling thread's ``schedstat`` descriptor, or None where the file
    cannot be opened (no procfs, no scheduler statistics) or stepscope is
    off. Descriptors of threads that have ended are closed on the way."""
    tid = threading.get_native_id()
    with _sched_lock:
        if _mode == MODE_OFF:
            return None
        alive = {t.native_id for t in threading.enumerate()}
        for gone in [t for t in _sched_fds if t not in alive]:
            os.close(_sched_fds.pop(gone))
        try:
            fd = os.open(_SCHEDSTAT.format(tid=tid), os.O_RDONLY)
        except OSError:
            return None
        _sched_fds[tid] = fd
        return fd


def _close_schedstat():
    """Close every thread's descriptor; a thread that reads its clocks
    again (stepscope is on again) sees the new epoch and opens anew."""
    global _sched_epoch
    with _sched_lock:
        _sched_epoch += 1
        while _sched_fds:
            os.close(_sched_fds.popitem()[1])


def _runq_ns() -> Optional[int]:  # tpulint: disable=TPU009 - lock-free read of an int that only grows: a stale epoch is met by a failed pread (None) and a reopen at the next reading
    """How long the calling thread has stood runnable on a run queue, in
    all; None where its ``schedstat`` cannot be read."""
    cell = getattr(_tls, "sched", None)
    if cell is None or cell[0] != _sched_epoch:
        cell = _tls.sched = (_sched_epoch, _open_schedstat())
    if cell[1] is None:
        return None
    try:
        return int(os.pread(cell[1], 96, 0).split()[1])
    except (OSError, ValueError, IndexError):
        return None


def _now() -> Tuple[int, int, Optional[int]]:
    return time.monotonic_ns(), time.thread_time_ns(), _runq_ns()


def clock() -> Optional[Tuple[int, int, Optional[int]]]:
    """The calling thread's clocks at this point, for one end of a stretch
    that ``loop_state`` or ``slot_update`` records: ``(monotonic_ns, the
    thread's CPU time, its run-queue delay or None)``. None while stepscope
    is off: callers pass it straight through."""
    if _mode == MODE_OFF:
        return None
    return _now()


def _off_cpu(began, ended) -> Dict[str, int]:
    """A record's ``cpu_us`` and, where both ends read it, ``runq_us``: of
    the stretch between two ``clock()`` readings of ONE thread."""
    fields = {"cpu_us": (ended[1] - began[1]) // 1000}
    if began[2] is not None and ended[2] is not None:
        fields["runq_us"] = (ended[2] - began[2]) // 1000
    return fields


class StepRecord:
    """One engine dispatch. Mutated by the stepping thread until
    ``step_end`` hands it to the aggregator."""

    __slots__ = (
        "model", "phase", "step_index", "batch_size", "slots",
        "lanes", "ctx_blocks", "ctx_pages", "tokens", "ctx_tokens",
        "t_begin", "t_dispatch", "t_end",
        "dispatch_us", "device_us", "other_us", "total_us",
        "_began", "_off_cpu",
        "micro_steps",
        "collectives", "kv_bytes", "thread_ident", "thread_name",
        "ctx_pages_global", "ctx_pages_window", "kv_held_global",
        "kv_held_window", "attn_straight", "pages_gathered",
        "_annotation", "_entry",
    )

    def __init__(self, model: str, phase: str, step_index: int,
                 batch_size: int, slots: int, lanes: int = 0,
                 ctx_blocks: int = 0):
        self.model = model
        self.phase = phase
        self.step_index = step_index
        self.batch_size = batch_size
        self.slots = slots
        # What the work was: the padded lane bucket and the width of the
        # block table the executable is given; the table entries under the
        # real lanes' lengths, positions computed and context really held
        # (set by the engine on the thread-owned record).
        self.lanes = lanes
        self.ctx_blocks = ctx_blocks
        self.ctx_pages = 0
        self.tokens = 0
        self.ctx_tokens = 0
        # The thread's three clocks where the dispatch bracket opens; the
        # bracket's CPU time and run-queue delay are taken where it closes.
        self._began = _now()
        self.t_begin = self._began[0]
        self.t_dispatch = 0
        self.t_end = 0
        self.dispatch_us = 0
        self._off_cpu: Dict[str, int] = {}
        # sync mode only (a bracketed block_until_ready and the clamped
        # remainder); counters mode has no device clock and leaves None.
        self.device_us: Optional[int] = None
        self.other_us: Optional[int] = None
        self.total_us = 0
        # Fused pipelined dispatch: how many decode micro-steps this one
        # dispatch covers (1 for the lockstep path).
        self.micro_steps = 1
        # op -> [count, bytes]
        self.collectives: Dict[str, List[int]] = {}
        # Paged-KV bytes this step's attention read: ``ctx_pages`` x block
        # bytes where the family's kernel reads the pages held,
        # ``pages_gathered`` x block bytes where it gathers the table; the
        # engine sets it on the thread-owned record before step_end.
        self.kv_bytes = 0
        # A family with window layers only (set by the engine): the pages
        # the kernel read in a layer of each kind, and the bytes the
        # dispatch's requests hold in the layers of each kind.
        self.ctx_pages_window: Optional[int] = None
        self.ctx_pages_global = self.kv_held_global = self.kv_held_window = 0
        # A family whose attention is the paged kernel only (set by the
        # engine): which of the kernel's two bodies this executable holds.
        self.attn_straight: Optional[bool] = None
        # A family that gathers its table only (set by the engine): the
        # entries gathered, every lane's and micro-step's width taken.
        self.pages_gathered: Optional[int] = None
        thread = threading.current_thread()
        self.thread_ident = thread.ident or 0
        self.thread_name = thread.name
        self._annotation = None
        self._entry: Optional[dict] = None    # its dict in the ring

    def collective_count(self) -> int:
        return sum(c for c, _ in self.collectives.values())

    def as_dict(self) -> dict:
        out = {
            "model": self.model,
            "phase": self.phase,
            "step_index": self.step_index,
            "batch_size": self.batch_size,
            "slots": self.slots,
            "lanes": self.lanes,
            "ctx_blocks": self.ctx_blocks,
            "ctx_pages": self.ctx_pages,
            "tokens": self.tokens,
            "ctx_tokens": self.ctx_tokens,
            "start_ns": self.t_begin,
            "dispatch_us": self.dispatch_us,
            "total_us": self.total_us,
            "micro_steps": self.micro_steps,
            "collectives": {
                op: {"count": c, "bytes": b}
                for op, (c, b) in sorted(self.collectives.items())
            },
            "kv_bytes": self.kv_bytes,
            "thread_ident": self.thread_ident,
            "thread_name": self.thread_name,
        }
        out.update(self._off_cpu)
        if self.device_us is not None:
            out["device_us"] = self.device_us
            out["other_us"] = self.other_us
        if self.attn_straight is not None:
            out["attn_straight"] = self.attn_straight
        if self.pages_gathered is not None:
            out["pages_gathered"] = self.pages_gathered
        if self.ctx_pages_window is not None:
            out.update(ctx_pages_global=self.ctx_pages_global,
                       ctx_pages_window=self.ctx_pages_window,
                       kv_held_global_bytes=self.kv_held_global,
                       kv_held_window_bytes=self.kv_held_window)
        return out


class RequestRecord:
    """One generation's timeline across the three threads that serve a
    token (server worker, engine loop, delivery). Each stamp is written by
    the one thread that owns that moment; ``request_end`` hands the record
    to the ring once. The server worker (the stream's handler) goes on
    stamping ``taken_ns`` / ``resumed_ns`` after that, until it has taken
    the last token: the ring holds the record, ``dump()`` the copy."""

    __slots__ = (
        "model", "key", "recv_ns", "core_ns", "submit_ns", "admitted_ns",
        "waited_for_pages", "first_chunk_ns", "last_chunk_ns", "chunks",
        "first_ready_ns", "out_ns", "taken_ns", "resumed_ns", "end_ns",
        "outcome",
    )

    def __init__(self, model: str, prompt, max_new: int, timestamps=None):
        self.model = model
        # What a reader outside the server joins on: the wire carries no
        # request id it could know.
        self.key = (zlib.crc32(prompt.tobytes()), int(prompt.shape[-1]),
                    int(max_new))
        timestamps = timestamps or {}
        self.recv_ns: Optional[int] = timestamps.get("REQUEST_RECV")
        self.core_ns: Optional[int] = timestamps.get("COMPUTE_INFER")
        self.submit_ns = time.monotonic_ns()
        self.admitted_ns: Optional[int] = None
        self.waited_for_pages = False
        self.first_chunk_ns: Optional[int] = None
        self.last_chunk_ns: Optional[int] = None
        self.chunks = 0
        self.first_ready_ns: Optional[int] = None
        self.out_ns: List[int] = []
        # The stream handler's two stamps a token: it holds the token; the
        # generator is resumed behind the token's ``yield``.
        self.taken_ns: List[int] = []
        self.resumed_ns: List[int] = []
        self.end_ns: Optional[int] = None
        self.outcome: Optional[str] = None

    def as_dict(self) -> dict:
        out = {name: getattr(self, name) for name in self.__slots__}
        out["key"] = list(self.key)
        for name in ("out_ns", "taken_ns", "resumed_ns"):
            out[name] = list(out[name])
        return out


# Thread-local active step: ``note_collective`` at a parallel/ call site
# (which runs at JAX trace time, inside the dispatch bracket of the step
# that triggers compilation) charges the step that is live on this thread.
_tls = threading.local()


class _Aggregator:
    """Process-wide sink for finished step records. One named lock; every
    read (metrics scrape, dump, flight stamp) resolves under it."""

    def __init__(self):
        self._lock = sanitize.named_lock("stepscope._lock")
        self.reset()

    def reset(self):
        with self._lock:
            # (model, phase, stage) -> LatencySketch (microseconds)
            self.sketches: Dict[Tuple[str, str, str], LatencySketch] = {}
            # (model, phase) -> finished-step count
            self.step_counts: Dict[Tuple[str, str], int] = {}
            # (model, op) -> [count, bytes]
            self.collectives: Dict[Tuple[str, str], List[int]] = {}
            # (model, phase) -> cumulative paged-KV bytes touched
            self.kv_bytes: Dict[Tuple[str, str], int] = {}
            # model -> decode dispatches currently in flight
            self.inflight: Dict[str, int] = {}
            # (model, callable) -> distinct dispatch-signature keys; the
            # set size is the compile-cache-entries gauge.
            self.compile_keys: Dict[Tuple[str, str], set] = {}
            # (model, callable) -> new-signature events beyond the first
            # (each one paid a fresh XLA trace+compile).
            self.retraces: Dict[Tuple[str, str], int] = {}
            # model -> slowest finished step (as_dict)
            self.slowest: Dict[str, dict] = {}
            try:
                ring = int(os.environ.get("TPU_STEPSCOPE_RING",
                                          str(_DEFAULT_RING)))
            except ValueError:
                ring = _DEFAULT_RING
            # Dispatch records (entered at step_end) and loop states.
            self.ring: deque = deque(maxlen=max(ring, 1))
            # The delivery thread's records (``delivery_end``).
            self.deliveries: deque = deque(maxlen=max(ring, 1))
            # The engine loop's slot-state updates (``slot_update``).
            self.slot_updates: deque = deque(maxlen=max(ring, 1))
            # Ended requests' records (the RequestRecord itself: its
            # stream handler may still be stamping; ``dump`` copies).
            self.requests: deque = deque(maxlen=max(ring, 1))
            # The collector's pauses (``_gc_hook``; appended WITHOUT the
            # lock: a collection may begin on a thread that holds it).
            self.gc: deque = deque(maxlen=max(ring, 1))

    def absorb(self, rec: StepRecord):
        stages = [(STAGE_DISPATCH, rec.dispatch_us)]
        if rec.device_us is not None:
            stages += [(STAGE_DEVICE, rec.device_us),
                       (STAGE_OTHER, rec.other_us)]
        with self._lock:
            for stage, us in stages:
                key = (rec.model, rec.phase, stage)
                sketch = self.sketches.get(key)
                if sketch is None:
                    sketch = self.sketches[key] = LatencySketch()
                sketch.insert(us)
            ck = (rec.model, rec.phase)
            self.step_counts[ck] = self.step_counts.get(ck, 0) + 1
            for op, (count, nbytes) in rec.collectives.items():
                cell = self.collectives.setdefault((rec.model, op), [0, 0])
                cell[0] += count
                cell[1] += nbytes
            if rec.kv_bytes:
                self.kv_bytes[ck] = (
                    self.kv_bytes.get(ck, 0) + rec.kv_bytes
                )
            worst = self.slowest.get(rec.model)
            if worst is None or rec.total_us > worst["total_us"]:
                self.slowest[rec.model] = rec.as_dict()
            rec._entry = rec.as_dict()
            self.ring.append(rec._entry)


_aggregator = _Aggregator()


# -- mode control ----------------------------------------------------------- #


def mode() -> str:
    return _mode


def enabled() -> bool:
    return _mode != MODE_OFF


# Benign mode publication: a single str rebind (GIL-atomic) set at
# process/test setup; engine threads that race it record under the old
# mode for at most one step.
# tpulint: disable=TPU009 - benign single-rebind mode publication
def configure(new_mode: Optional[str] = None) -> str:
    """Set the mode explicitly (tests / benches), or re-read the
    environment when called with None. Returns the active mode."""
    global _mode
    if new_mode is None:
        _mode = _env_mode()
    elif new_mode in MODES:
        _mode = new_mode
    else:
        raise ValueError(f"unknown stepscope mode: {new_mode!r}")
    _sync_hooks()
    return _mode


_gc_started = 0


def _gc_hook(phase: str, info: dict):
    """``gc.callbacks`` entry: one record a collection. The collector does
    not nest, so one module-level start stamp serves; the ring is appended
    to without the aggregator's lock (``_Aggregator.reset``)."""
    global _gc_started
    if phase == "start":
        _gc_started = time.monotonic_ns()
    elif _gc_started:
        started, _gc_started = _gc_started, 0
        thread = threading.current_thread()
        _aggregator.gc.append({
            "start_ns": started,
            "duration_ns": time.monotonic_ns() - started,
            "generation": info.get("generation"),
            "thread_ident": thread.ident or 0, "thread_name": thread.name})


def _sync_hooks():
    """What stepscope keeps outside its own state while it is on, and
    nothing of when it is off: the collector's hook and the threads'
    ``schedstat`` descriptors."""
    hooked = _gc_hook in gc.callbacks
    if hooked == (_mode != MODE_OFF):
        return
    # Going off closes the descriptors; coming on none is open, and the new
    # epoch makes a thread forget a try that failed while stepscope was off.
    _close_schedstat()
    if hooked:
        gc.callbacks.remove(_gc_hook)
    else:
        gc.callbacks.append(_gc_hook)


_sync_hooks()


def reset():
    """Drop all aggregated state (tests / bench phase boundaries)."""
    _aggregator.reset()
    _tls.active = None


# -- step clock ------------------------------------------------------------- #


def step_begin(model: str, phase: str, step_index: int,
               batch_size: int = 0, slots: int = 0, lanes: int = 0,
               ctx_blocks: int = 0) -> Optional[StepRecord]:
    """Open a step. Returns None when stepscope is off — callers pass the
    handle straight through, so the off path is one global read. The
    bracket up to ``step_dispatched`` is also a profiler annotation (free
    while no profile is being taken), so the host span sits beside the
    device's module on the trace's own clock."""
    if _mode == MODE_OFF:
        return None
    import jax

    rec = StepRecord(model, phase, step_index, batch_size, slots, lanes,
                     ctx_blocks)
    _tls.active = rec
    rec._annotation = jax.profiler.TraceAnnotation(
        f"{model}/{phase}", step=step_index, lanes=lanes,
        ctx_blocks=ctx_blocks)
    rec._annotation.__enter__()
    return rec


def _close_annotation(rec: StepRecord):
    annotation, rec._annotation = rec._annotation, None
    if annotation is not None:
        annotation.__exit__(None, None, None)


def step_abandon():
    """The dispatch of the step open on this thread raised: close its
    annotation and drop the record (callers' failure handlers)."""
    rec = getattr(_tls, "active", None)
    _tls.active = None
    if rec is not None:
        _close_annotation(rec)


def step_dispatched(rec: Optional[StepRecord]):
    """Mark dispatch return: host trace+dispatch of the jitted call is
    everything between ``step_begin`` and here."""
    if rec is not None:
        _stamp_dispatched(rec)
        _close_annotation(rec)


def _stamp_dispatched(rec: StepRecord):
    now = _now()
    rec.t_dispatch = now[0]
    rec._off_cpu = _off_cpu(rec._began, now)


def step_end(rec: Optional[StepRecord], outputs=None):
    """Close the step and hand it to the aggregator.

    In ``sync`` mode, ``outputs`` (any pytree of device arrays) is waited
    on with a timed ``jax.block_until_ready`` — the bracketed wait is the
    device time, and the clamped remainder is ``other``. In counters mode
    outputs are ignored and the record has no device stage: nothing on
    this thread's clock says when the device finished.
    """
    if rec is None:
        return
    _tls.active = None
    _close_annotation(rec)
    if rec.t_dispatch == 0:
        _stamp_dispatched(rec)
    device_ns = -1
    if _mode == MODE_SYNC and outputs is not None:
        t0 = time.monotonic_ns()
        try:
            import jax

            # MODE_SYNC is the opt-in measurement mode: this barrier IS
            # the device-time probe (off by default; see mode()).
            jax.block_until_ready(outputs)  # tpulint: disable=TPU010
            device_ns = time.monotonic_ns() - t0
        except Exception:
            device_ns = -1
    rec.t_end = time.monotonic_ns()
    total_ns = max(rec.t_end - rec.t_begin, 0)
    dispatch_ns = min(max(rec.t_dispatch - rec.t_begin, 0), total_ns)
    rec.total_us = total_ns // 1000
    rec.dispatch_us = dispatch_ns // 1000
    if device_ns >= 0:
        device_ns = min(device_ns, total_ns - dispatch_ns)
        rec.device_us = device_ns // 1000
        rec.other_us = max(total_ns - dispatch_ns - device_ns, 0) // 1000
    _aggregator.absorb(rec)


ROUTING_FIELDS = ("routed_tokens", "experts_hit", "experts_held",
                  "expert_load_max", "expert_load_mean", "pairs_elsewhere",
                  "expert_passes")
# Beside them, from a family with a multi-stream residual path: the streams
# a token and the (live row, sublayer) pairs that passed the maps.
RESIDUAL_FIELDS = ("hc_streams", "hc_rows")


def step_routing(rec: Optional[StepRecord], counters: Optional[dict]):
    """What the router of a routed family did in ``rec``'s dispatch
    (``ROUTING_FIELDS``), from the per-layer histogram the step returned:
    the engine's delivery thread reads that back behind the dispatch's
    tokens and adds the counters to the record already in the ring, so the
    engine loop never waits for them. Call after ``step_end``."""
    if rec is None or not counters:
        return
    with _aggregator._lock:
        if rec._entry is not None:
            rec._entry.update(counters)


def delivery_begin(rec: Optional[StepRecord]) -> Optional[dict]:
    """Open the delivery record of the item that carries ``rec``'s result
    (stamps ``queued_ns``); None when stepscope is off. The item hands it
    to the delivery thread, which stamps ``taken_ns``, ``ready_ns``
    (``delivery_ready``) and ``delivered_ns`` (``delivery_delivered``) and
    closes it with ``delivery_end``."""
    if rec is None:
        return None
    return {"model": rec.model, "phase": rec.phase,
            "step_index": rec.step_index,
            "queued_ns": time.monotonic_ns(), "taken_ns": None,
            "ready_ns": None, "delivered_ns": None}


def delivery_ready(delivery: dict) -> int:
    """The item's readback has returned on the delivery thread: stamps
    ``ready_ns`` (and returns it), and keeps the thread's clocks for the
    hand-over that starts here."""
    now = delivery["_ready"] = _now()
    delivery["ready_ns"] = now[0]
    return now[0]


def delivery_delivered(delivery: dict):
    """The item's last token is handed to its request: stamps
    ``delivered_ns`` and the hand-over's ``cpu_us`` / ``runq_us``."""
    now = _now()
    delivery["delivered_ns"] = now[0]
    delivery.update(_off_cpu(delivery.pop("_ready", now), now))


def delivery_end(delivery: Optional[dict]):
    """The delivery thread is done with the item: its record enters the
    ``deliveries`` ring."""
    if delivery is not None:
        delivery.pop("_ready", None)    # a hand-over that raised
        with _aggregator._lock:
            _aggregator.deliveries.append(delivery)


def loop_state(model: str, state: str, began, ended, slots: int = 0):
    """One stretch of an engine-loop state (``LOOP_STATES``), into the
    ring only, between two ``clock()`` readings of the loop's thread: its
    duration, and what of it the thread spent on the CPU and in line for
    one. The caller keeps stretches from overlapping a dispatch record or
    each other; a stretch whose either end was read with stepscope off (it
    came on or went off mid-stretch) and an empty one are dropped."""
    if _mode == MODE_OFF or not began or not ended or ended[0] <= began[0]:
        return
    thread = threading.current_thread()
    duration_us = (ended[0] - began[0]) // 1000
    record = {
        "model": model, "phase": state, "step_index": 0, "batch_size": 0,
        "slots": slots, "start_ns": began[0], "dispatch_us": duration_us,
        "total_us": duration_us, "micro_steps": 0, "collectives": {},
        "thread_ident": thread.ident or 0, "thread_name": thread.name,
    }
    record.update(_off_cpu(began, ended))
    with _aggregator._lock:
        _aggregator.ring.append(record)


def slot_update(model: str, joined: int, freed: int, began, ended):
    """One dispatch of the engine's slot-state update: how many slots it
    joined and freed, the host time it took the engine loop from building
    the arrays to the call's return (between two ``clock()`` readings),
    and what of that the thread spent on the CPU and in line for one. An
    end read with stepscope off records nothing."""
    if _mode == MODE_OFF or not began or not ended:
        return
    record = {"model": model, "joined": joined, "freed": freed,
              "start_ns": began[0], "host_ns": ended[0] - began[0]}
    record.update(_off_cpu(began, ended))
    with _aggregator._lock:
        _aggregator.slot_updates.append(record)


# -- request timeline ------------------------------------------------------- #


def request_begin(model: str, prompt, max_new: int,
                  timestamps=None) -> Optional[RequestRecord]:
    """Open a request's record as it is submitted to the engine (stamps
    ``submit_ns``). ``timestamps`` is the request's ``TraceContext``
    timeline, where the core handed one down. Returns None when stepscope
    is off: the engine checks once, here, and stamps nothing after."""
    if _mode == MODE_OFF:
        return None
    return RequestRecord(model, prompt, max_new, timestamps)


def request_end(rec: Optional[RequestRecord], outcome: str):
    """The request ended (its terminator or error is about to be put):
    stamp and hand the record to the ring, once. The record itself: its
    stream handler has not yet taken the last tokens."""
    if rec is None or rec.end_ns is not None:
        return
    rec.end_ns = time.monotonic_ns()
    rec.outcome = outcome
    with _aggregator._lock:
        _aggregator.requests.append(rec)


def note_collective(op: str, count: int = 1, nbytes: int = 0):
    """Charge a collective to the step live on this thread (no-op when
    stepscope is off or no step is open). Called from the ``parallel/``
    call sites at JAX trace time."""
    if _mode == MODE_OFF:
        return
    rec = getattr(_tls, "active", None)
    if rec is None:
        return
    cell = rec.collectives.setdefault(op, [0, 0])
    cell[0] += count
    cell[1] += nbytes


def charge_collectives(rec: Optional[StepRecord], ops: Dict[str, int],
                       nbytes: int = 0):
    """Charge an expected per-step collective count (GSPMD-implicit
    all-reduces never hit a python call site — the engine charges the
    count the sharding provably forces)."""
    if rec is None:
        return
    for op, count in ops.items():
        cell = rec.collectives.setdefault(op, [0, 0])
        cell[0] += count
        cell[1] += nbytes


def expected_tp_collectives(n_layers: int, tp: int) -> Dict[str, int]:
    """Per-decode-step collective count the gpt PARTITION_RULES force
    under tensor parallelism: wo and w_out are row-sharded on 'tp', so
    GSPMD inserts one all-reduce after the attention projection and one
    after the FFN output — 2 psums per layer. tp=1 shards nothing.
    tests/test_gpt_engine.py reads the same two off the partitioned
    program, and beside them two collective-permutes a layer (the fused
    QKV's split re-laying its columns across the shards) that this
    count, of all-reduces, leaves out."""
    if tp <= 1:
        return {}
    return {"psum": 2 * n_layers}


def note_compile(model: str, fn: str, key: str):
    """Record one dispatch signature of a jitted callable.

    The engine computes ``key`` from the traced-operand shapes/dtypes of
    the dispatch (the same identity XLA's compile cache uses), so a key
    not seen before means this dispatch paid a fresh trace+compile. The
    distinct-key count is the ``nv_engine_compile_cache_entries`` gauge;
    new keys beyond the first increment ``nv_engine_retrace_total``.
    The tpusan compile-cache watcher (``sanitize/_jax.py``) feeds the
    same plane and additionally enforces declared bucket budgets
    (TPU017). No-op when stepscope is off (one global read)."""
    if _mode == MODE_OFF:
        return
    agg = _aggregator
    with agg._lock:
        keys = agg.compile_keys.setdefault((model, fn), set())
        if key in keys:
            return
        keys.add(key)
        if len(keys) > 1:
            ck = (model, fn)
            agg.retraces[ck] = agg.retraces.get(ck, 0) + 1


def compile_snapshot() -> List[Tuple[str, str, int, int]]:
    """``(model, callable, cache entries, retraces)`` rows for the
    nv_engine_compile_cache_entries / nv_engine_retrace_total families."""
    agg = _aggregator
    with agg._lock:
        return [
            (model, fn, len(keys), agg.retraces.get((model, fn), 0))
            for (model, fn), keys in sorted(agg.compile_keys.items())
        ]


def inflight_update(model: str, delta: int):
    """Track the pipelined-dispatch window: the engine calls ``+1`` when a
    decode dispatch is submitted and ``-1`` when its delivery drains.
    No-op when stepscope is off (one global read)."""
    if _mode == MODE_OFF:
        return
    agg = _aggregator
    with agg._lock:
        depth = agg.inflight.get(model, 0) + delta
        agg.inflight[model] = max(depth, 0)


# -- sinks ------------------------------------------------------------------ #


def inflight_snapshot() -> List[Tuple[str, int]]:
    """``(model, decode dispatches in flight)`` rows for the
    nv_engine_inflight_steps gauge of a /metrics scrape."""
    with _aggregator._lock:
        return sorted(_aggregator.inflight.items())


def metrics_snapshot(quantiles: Tuple[float, ...]):
    """Resolve the step sketches for a /metrics scrape.

    Returns ``(step_rows, collective_rows)`` where step_rows is a list of
    ``(model, phase, stage, [q values], count, sum)`` — quantiles resolved
    under the aggregator lock, mirroring InferenceCore's sketch_rows —
    and collective_rows is ``(model, op, count)``.
    """
    agg = _aggregator
    with agg._lock:
        step_rows = [
            (model, phase, stage,
             sketch.quantiles(quantiles), sketch.count, sketch.sum)
            for (model, phase, stage), sketch in sorted(agg.sketches.items())
        ]
        collective_rows = [
            (model, op, cell[0])
            for (model, op), cell in sorted(agg.collectives.items())
        ]
    return step_rows, collective_rows


def kv_bytes_snapshot() -> List[Tuple[str, str, int]]:
    """``(model, phase, cumulative bytes)`` rows for the
    nv_engine_kv_bytes_touched_total exposition family."""
    agg = _aggregator
    with agg._lock:
        return [
            (model, phase, total)
            for (model, phase), total in sorted(agg.kv_bytes.items())
        ]


def flight_attributes(model: str) -> Dict[str, object]:
    """Slowest-step breakdown for the given model, as span attributes the
    flight recorder stamps onto retained records. Empty when stepscope is
    off or no step finished yet."""
    if _mode == MODE_OFF:
        return {}
    with _aggregator._lock:
        worst = _aggregator.slowest.get(model)
        if worst is None:
            return {}
        attrs = {
            "step.slowest.phase": worst["phase"],
            "step.slowest.index": worst["step_index"],
            "step.slowest.batch_size": worst["batch_size"],
            "step.slowest.total_us": worst["total_us"],
            "step.slowest.dispatch_us": worst["dispatch_us"],
            "step.slowest.collectives": sum(
                c["count"] for c in worst["collectives"].values()
            ),
        }
        if "device_us" in worst:    # sync mode only
            attrs["step.slowest.device_us"] = worst["device_us"]
            attrs["step.slowest.other_us"] = worst["other_us"]
        return attrs


def perfetto_events(epoch_ns: int) -> List[dict]:
    """Chrome trace events for the recent-step ring: one thread-scoped
    track per engine thread (ph='M' thread_name metadata + 'X' complete
    events). The events carry no trace/span ids — they are orphan tracks
    the loaders keep per-track, merging under the request spans in the
    Perfetto UI by time."""
    pid = os.getpid()
    with _aggregator._lock:
        records = list(_aggregator.ring)
    events: List[dict] = []
    named_tids = set()
    for r in records:
        tid = r["thread_ident"] or 1
        if tid not in named_tids:
            named_tids.add(tid)
            events.append({
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": f"stepscope:{r['thread_name']}"},
            })
        events.append({
            "name": f"{r['model']}/{r['phase']}[{r['step_index']}]",
            "cat": "stepscope",
            "ph": "X",
            "ts": (r["start_ns"] + epoch_ns) / 1000.0,
            "dur": r["total_us"],
            "pid": pid,
            "tid": tid,
            "args": {
                "model": r["model"],
                "phase": r["phase"],
                "step_index": str(r["step_index"]),
                "batch_size": str(r["batch_size"]),
                "dispatch_us": str(r["dispatch_us"]),
                # sync mode only; loop states and counters records have none
                **{k: str(r[k]) for k in ("device_us", "other_us")
                   if k in r},
                "collectives": str(sum(
                    c["count"] for c in r["collectives"].values()
                )),
            },
        })
    return events


def dump() -> dict:
    """Self-describing document ``scripts/step_report.py`` loads: the
    recent-step ring (dispatch records and loop states), the delivery
    thread's ring, the slot-state updates' ring, the ended requests' ring
    (copied here), the collector's pauses, plus aggregate totals."""
    agg = _aggregator
    with agg._lock:
        records = list(agg.ring)
        deliveries = list(agg.deliveries)
        slot_updates = list(agg.slot_updates)
        requests = [r.as_dict() for r in agg.requests]
        pauses = list(agg.gc)
        step_counts = {
            f"{model}|{phase}": count
            for (model, phase), count in sorted(agg.step_counts.items())
        }
        collectives = {
            f"{model}|{op}": {"count": cell[0], "bytes": cell[1]}
            for (model, op), cell in sorted(agg.collectives.items())
        }
        kv_bytes = {
            f"{model}|{phase}": total
            for (model, phase), total in sorted(agg.kv_bytes.items())
        }
        inflight = dict(sorted(agg.inflight.items()))
        slowest = dict(agg.slowest)
        compiles = {
            f"{model}|{fn}": {
                "entries": len(keys),
                "retraces": agg.retraces.get((model, fn), 0),
            }
            for (model, fn), keys in sorted(agg.compile_keys.items())
        }
    return {
        "kind": "stepscope",
        "mode": _mode,
        "records": records,
        "deliveries": deliveries,
        "slot_updates": slot_updates,
        "requests": requests,
        "gc": pauses,
        "step_counts": step_counts,
        "collectives": collectives,
        "kv_bytes": kv_bytes,
        "inflight": inflight,
        "slowest": slowest,
        "compiles": compiles,
    }
