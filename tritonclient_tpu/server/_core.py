"""Transport-neutral inference core for the in-process JAX server.

The reference repo is client-only and relies on a live Triton server for
integration tests (SURVEY.md §4); this core is the hermetic, JAX-backed
equivalent of that server's request plane. Both the HTTP and gRPC front-ends
(tritonclient_tpu.server._http / ._grpc) translate wire requests into
``CoreRequest`` and back, so protocol behavior (classification extension,
shared-memory I/O routing, sequence parameters, decoupled responses,
statistics) lives here exactly once.
"""

import json
import logging
import math
import mmap
import os
import threading
import time
from dataclasses import dataclass, field
from typing import Dict, Iterator, List, Optional, Union

import numpy as np

from tritonclient_tpu import _kvcache, _memscope, _stepscope, sanitize
from tritonclient_tpu._sketch import LatencySketch
from tritonclient_tpu._tracing import (
    FlightRecorder,
    TraceCollector,
    TraceContext,
    configure_logging,
)
from tritonclient_tpu.protocol._literals import (
    INVALID_REASON_DATA_MISMATCH,
    INVALID_REASON_MALFORMED,
    INVALID_REASONS,
    PARAM_CANCEL_EVENT,
    PARAM_TRACE_TIMESTAMPS,
    PREFIX_EVENTS,
    SERVER_EXTENSIONS,
    SHED_REASON_ADMISSION,
    SHED_REASON_CANCELLED,
    SHED_REASON_EXPIRED,
    SHED_REASONS,
    STATUS_CANCELLED,
    STATUS_INVALID,
    STATUS_SHED,
)
from tritonclient_tpu.protocol._validate import (
    ValidationError,
    validate_data_length,
    validate_dtype,
    validate_shm_window,
)
from tritonclient_tpu.utils import (
    InferenceServerException,
    deserialize_bytes_tensor,
    num_elements,
    serialize_byte_tensor,
    triton_dtype_size,
    triton_to_np_dtype,
)

SERVER_NAME = "triton-tpu"
SERVER_VERSION = "2.0.0-tpu"


class CoreError(Exception):
    """Server-side error with an HTTP-ish status code hint.

    ``reason`` is set (to one of ``INVALID_REASONS``) when the error came
    out of boundary validation of an untrusted request value: the
    front-ends stamp it on ``nv_inference_invalid_request_total`` and the
    flight record's ``invalid.reason`` attribute. Empty for server-side
    errors that are not the client's fault.
    """

    def __init__(self, msg: str, status: int = STATUS_INVALID,
                 reason: str = ""):
        super().__init__(msg)
        self.status = status
        self.reason = reason


def invalid_to_core_error(e: ValidationError) -> CoreError:
    """Re-raise boundary validation as the core's uniform error type,
    preserving the status and the canonical invalid reason."""
    return CoreError(str(e), e.status, reason=e.reason)


@dataclass
class CoreTensor:
    """One input tensor, either inline data or a shared-memory reference."""

    name: str
    datatype: str
    shape: List[int]
    data: Optional[np.ndarray] = None
    shm_kind: Optional[str] = None  # "system" | "cuda" | "tpu"
    shm_region: Optional[str] = None
    shm_offset: int = 0
    shm_byte_size: int = 0


@dataclass
class CoreRequestedOutput:
    name: str
    binary: bool = True
    class_count: int = 0
    shm_kind: Optional[str] = None
    shm_region: Optional[str] = None
    shm_offset: int = 0
    shm_byte_size: int = 0


@dataclass
class CoreRequest:
    model_name: str
    model_version: str = ""
    id: str = ""
    parameters: dict = field(default_factory=dict)
    inputs: List[CoreTensor] = field(default_factory=list)
    outputs: List[CoreRequestedOutput] = field(default_factory=list)
    # Parsed KServe `timeout` request parameter (microseconds; 0 = none).
    # Held OUT of `parameters` so carrying a deadline does not disqualify
    # the request from dynamic batching. A SCHEDULING input: the dynamic
    # batcher orders deadline traffic earliest-deadline-first, rejects
    # requests whose budget cannot cover the service estimate with a fast
    # 504 at admission, and sweeps expired requests out of the queue.
    deadline_us: int = 0
    # Tenant this request belongs to (the ``tenant-id`` header / gRPC
    # metadata value, empty when the caller sent none). Stamped by the
    # protocol front-ends so per-tenant accounting — flight-recorder
    # attribution, tail_report fairness rows — survives into the core
    # without re-parsing transport metadata. Excluded from equality so
    # the gRPC stream's cached-parse comparison is unaffected.
    tenant: str = field(default="", compare=False)
    # Per-request cancellation signal (a threading.Event), armed by the
    # protocol front-ends on client disconnect / RPC termination. The
    # batcher sheds queued requests whose event is set, and engine-backed
    # models (``accepts_cancel_event``) poll it between decode steps so
    # abandoned work stops consuming slots. Excluded from equality so the
    # gRPC stream's cached-parse comparison is unaffected.
    cancel_event: Optional[object] = field(default=None, compare=False)
    # Per-request TraceContext (tritonclient_tpu._tracing), attached by the
    # protocol front-end when the request is sampled; the execution paths
    # stamp the QUEUE_START/COMPUTE_* spans onto it. Excluded from equality
    # so the gRPC stream's cached-parse comparison is unaffected.
    trace: Optional[object] = field(default=None, compare=False)


@dataclass
class CoreOutput:
    name: str
    datatype: str
    shape: List[int]
    data: Optional[np.ndarray] = None  # None when routed to shared memory
    shm_kind: Optional[str] = None
    shm_region: Optional[str] = None
    shm_offset: int = 0
    shm_byte_size: int = 0


@dataclass
class CoreResponse:
    model_name: str
    model_version: str = "1"
    id: str = ""
    parameters: dict = field(default_factory=dict)
    outputs: List[CoreOutput] = field(default_factory=list)


# --------------------------------------------------------------------------- #
# shared-memory registries (server side)                                      #
# --------------------------------------------------------------------------- #


class SystemShmRegistry:
    """Server-side registry of POSIX shared-memory regions.

    The client creates regions via shm_open (utils/shared_memory); the server
    maps the same key through /dev/shm. Only registration metadata ever crosses
    the wire — tensor bytes move through the mapping (reference architecture:
    SURVEY.md §5.8).
    """

    def __init__(self):
        self._regions: Dict[str, dict] = {}
        # Named for the tpusan lock-order witness (plain threading.Lock
        # when the sanitizer is inactive).
        self._lock = sanitize.named_lock("SystemShmRegistry._lock")
        # Bumped on every (un)register: lets per-stream request-parse caches
        # (server/_grpc.py) invalidate when a region's identity could change.
        self.generation = 0

    def register(self, name: str, key: str, offset: int, byte_size: int):
        path = "/dev/shm/" + key.lstrip("/")
        try:
            fd = os.open(path, os.O_RDWR)
        except OSError as e:
            raise CoreError(
                f"Unable to open shared memory region: '{name}' ({e})", STATUS_INVALID
            )
        try:
            try:
                mm = mmap.mmap(fd, 0)
            finally:
                os.close(fd)
        except (OSError, ValueError) as e:
            # mmap of an empty/truncated object: a protocol error, not a
            # server fault — and never a leaked fd (closed above).
            raise CoreError(
                f"Unable to map shared memory region: '{name}' ({e})", STATUS_INVALID
            )
        try:
            # The registered window is client-supplied wire data: it must
            # be non-negative and fit the mapping, or every later read
            # would do attacker-controlled ``base + offset`` arithmetic.
            offset, byte_size = validate_shm_window(
                offset, byte_size, len(mm), name
            )
        except ValidationError as e:
            mm.close()
            raise invalid_to_core_error(e)
        with self._lock:
            # Insert the new mapping BEFORE closing a replaced one: if the
            # old close raises (BufferError while a reader still holds an
            # exported buffer), the registry must not end up holding
            # neither mapping — that was an error-path leak of `mm` (TPU006
            # register/replace discipline).
            old = self._regions.get(name)
            self._regions[name] = {
                "name": name,
                "key": key,
                "offset": int(offset),
                "byte_size": int(byte_size),
                "mmap": mm,
            }
            self.generation += 1
        # Registered region bytes on the device-memory ledger (server scope,
        # shm pool). "sys:" keys the host-mapped plane apart from "tpu:".
        _memscope.set_static(
            _memscope.SCOPE_SERVER, _memscope.MEM_POOL_SHM, "sys:" + name,
            int(byte_size), {"key": key},
        )
        if old is not None:
            try:
                old["mmap"].close()
            except BufferError:
                pass  # exported buffers keep the old mapping alive; the
                # view is dropped from the registry either way

    def __contains__(self, name: str) -> bool:
        # GIL-atomic dict membership; safe without the lock on the hot path.
        return name in self._regions  # tpulint: disable=TPU002

    def unregister(self, name: Optional[str]):
        removed = []
        with self._lock:
            names = [name] if name else list(self._regions)
            for n in names:
                region = self._regions.pop(n, None)
                if region is not None:
                    removed.append(n)
                    try:
                        region["mmap"].close()
                    except BufferError:
                        # A reader still holds an exported buffer
                        # (np.frombuffer over the mapping). The mapping
                        # closes when the last view dies; aborting the
                        # loop here used to strand every remaining region
                        # registered with the generation un-bumped.
                        pass
            self.generation += 1
        for n in removed:
            _memscope.clear_static(
                _memscope.SCOPE_SERVER, _memscope.MEM_POOL_SHM, "sys:" + n
            )

    def status(self, name: Optional[str] = None) -> List[dict]:
        with self._lock:
            regions = (
                [self._regions[name]] if name and name in self._regions
                else ([] if name else list(self._regions.values()))
            )
            return [
                {k: r[k] for k in ("name", "key", "offset", "byte_size")}
                for r in regions
            ]

    def read(self, name: str, offset: int, nbytes: int) -> bytes:
        with self._lock:
            region = self._regions.get(name)
        if region is None:
            raise CoreError(f"Unable to find shared memory region: '{name}'", STATUS_INVALID)
        try:
            # Request-supplied window: negative offsets walk backwards out
            # of the mapping through the ``base + offset`` arithmetic, and
            # over-sized windows read bytes the client never registered.
            offset, nbytes = validate_shm_window(
                offset, nbytes, self._window_cap(region), name
            )
        except ValidationError as e:
            raise invalid_to_core_error(e)
        base = region["offset"] + offset
        if base + nbytes > len(region["mmap"]):
            raise CoreError(
                f"Invalid offset + byte size for shared memory region: '{name}'", STATUS_INVALID
            )
        return bytes(region["mmap"][base : base + nbytes])

    @staticmethod
    def _window_cap(region) -> int:
        """Largest request window the registered region allows: the
        registered byte_size, or (for a 0-sized registration) whatever of
        the mapping lies past the registered base offset."""
        return region["byte_size"] or (
            len(region["mmap"]) - region["offset"]
        )

    def write(self, name: str, offset: int, data: bytes):
        with self._lock:
            region = self._regions.get(name)
        if region is None:
            raise CoreError(f"Unable to find shared memory region: '{name}'", STATUS_INVALID)
        try:
            offset, _ = validate_shm_window(
                offset, len(data), self._window_cap(region), name
            )
        except ValidationError as e:
            raise invalid_to_core_error(e)
        base = region["offset"] + offset
        if base + len(data) > len(region["mmap"]):
            raise CoreError(
                f"Shared memory region '{name}' is too small for output", STATUS_INVALID
            )
        region["mmap"][base : base + len(data)] = data


class TpuShmRegistry:
    """Server-side registry for the TPU zero-copy plane.

    Regions live in a process-global table owned by
    ``tritonclient_tpu.utils.tpu_shared_memory`` (the PjRt analog of cudaIpc:
    co-location means the same process/PjRt client — SURVEY.md §7 hard part 1).
    Registration resolves the client's raw handle against that table; reads and
    writes then move jax.Array data without host staging when possible.
    """

    def __init__(self):
        self._regions: Dict[str, dict] = {}
        self._lock = sanitize.named_lock("TpuShmRegistry._lock")
        # Same cache-invalidation contract as SystemShmRegistry.generation.
        self.generation = 0

    def register(self, name: str, raw_handle: bytes, device_id: int, byte_size: int):
        try:
            from tritonclient_tpu.utils import tpu_shared_memory as tpushm
        except ImportError as e:  # pragma: no cover
            raise CoreError(f"TPU shared memory support unavailable: {e}", STATUS_INVALID)

        region = tpushm._resolve_raw_handle(raw_handle)
        if region is None:
            raise CoreError(
                f"Unable to resolve TPU shared memory handle for region: '{name}'", STATUS_INVALID
            )
        try:
            _, byte_size = validate_shm_window(0, byte_size, None, name)
        except ValidationError as e:
            raise invalid_to_core_error(e)
        with self._lock:
            self._regions[name] = {
                "name": name,
                "device_id": int(device_id),
                "byte_size": int(byte_size),
                "region": region,
            }
            self.generation += 1
        # Registered DEVICE-buffer bytes on the ledger: this is the pool the
        # memscope shm family actually measures on hardware.
        _memscope.set_static(
            _memscope.SCOPE_SERVER, _memscope.MEM_POOL_SHM, "tpu:" + name,
            int(byte_size), {"device_id": int(device_id)},
        )

    def __contains__(self, name: str) -> bool:
        # GIL-atomic dict membership; safe without the lock on the hot path.
        return name in self._regions  # tpulint: disable=TPU002

    def unregister(self, name: Optional[str]):
        with self._lock:
            if name:
                removed = [name] if self._regions.pop(name, None) else []
            else:
                removed = list(self._regions)
                self._regions.clear()
            self.generation += 1
        for n in removed:
            _memscope.clear_static(
                _memscope.SCOPE_SERVER, _memscope.MEM_POOL_SHM, "tpu:" + n
            )

    def status(self, name: Optional[str] = None) -> List[dict]:
        with self._lock:
            regions = (
                [self._regions[name]] if name and name in self._regions
                else ([] if name else list(self._regions.values()))
            )
            return [
                {k: r[k] for k in ("name", "device_id", "byte_size")} for r in regions
            ]

    def get_region(self, name: str):
        with self._lock:
            entry = self._regions.get(name)
        if entry is None:
            raise CoreError(f"Unable to find shared memory region: '{name}'", STATUS_INVALID)
        return entry["region"]

    def _checked_window(self, name: str, offset: int, nbytes: int):
        with self._lock:
            entry = self._regions.get(name)
        if entry is None:
            raise CoreError(f"Unable to find shared memory region: '{name}'", STATUS_INVALID)
        try:
            return entry["region"], validate_shm_window(
                offset, nbytes, entry["byte_size"] or None, name
            )
        except ValidationError as e:
            raise invalid_to_core_error(e)

    def read(self, name: str, offset: int, nbytes: int) -> bytes:
        region, (offset, nbytes) = self._checked_window(name, offset, nbytes)
        return region.read_bytes(offset, nbytes)

    def write(self, name: str, offset: int, data: bytes):
        region, (offset, _) = self._checked_window(name, offset, len(data))
        region.write_bytes(offset, data)

    def read_array(self, name: str, datatype: str, shape: List[int],
                   offset: int, prefer_host: bool = False):
        """Zero-copy typed read: a jax.Array view over the region.

        ``prefer_host=True`` returns mirror-staged bytes as a host array
        instead of uploading (parked device arrays still return as-is) —
        the dynamic batcher's path, which uploads once per batch.
        """
        return self.get_region(name).as_array(
            datatype, shape, offset, prefer_host=prefer_host
        )

    def write_array(self, name: str, array, offset: int):
        """Zero-copy typed write: park a jax.Array in the region.

        Non-blocking (``block=False``): the parked array may still be
        computing when the response goes out — readers block only when they
        materialize it, so request handling never serializes on the device.
        This is the XLA-async equivalent of the reference's output-donation
        goal (SURVEY.md §7 hard part 2): the region table repoints at the
        result buffer, no copy and no sync on the response path.

        The device->host copy is also *enqueued* here (async, non-blocking):
        output regions exist to be read back, and enqueueing the transfer
        back-to-back with the compute keeps the whole device chain in one
        dispatch window — a reader's later materialization then waits on an
        in-flight transfer instead of issuing a fresh one a network
        round-trip later. Device-side consumers are unaffected (the parked
        buffer stays on device; the async copy only warms the host path).
        """
        from tritonclient_tpu.utils import tpu_shared_memory as tpushm

        region = self.get_region(name)
        region.set_array(array, offset, block=False)
        if isinstance(array, tpushm.BatchRowView):
            return  # base already warmed once by the batch executor
        coalescer = tpushm.transfer_coalescer()
        if (
            coalescer is not None
            and type(region) is tpushm.TpuSharedMemoryRegion
            and hasattr(array, "copy_to_host_async")
        ):
            # Bundle this output's d2h with its contemporaries: one transfer
            # op per bundle instead of per response (readback ops cost
            # fixed ~0.8 ms host CPU on latency-bound links).
            coalescer.submit(region, offset, array)
            return
        try:
            array.copy_to_host_async()
        except AttributeError:  # non-jax array (host data): nothing to warm
            pass


# --------------------------------------------------------------------------- #
# statistics                                                                  #
# --------------------------------------------------------------------------- #


# Histogram bucket upper bounds (microseconds) for per-request duration.
# Spans 100us..5s: the knee-finding range for a serving sweep (BASELINE.md
# p99 targets are single-digit ms; the tail buckets catch saturation).
_DURATION_BUCKETS_US = (
    100, 500, 1000, 5000, 10000, 25000, 50000,
    100000, 250000, 500000, 1000000, 5000000,
)


# Stage-latency sketch keys: "request" is end-to-end (success AND fail,
# matching the duration histogram); the rest mirror the cumulative
# nv_inference_*_duration_us counters with full distributions. One fixed
# tuple so /metrics rendering and tests agree on the family set.
_SKETCH_STAGES = (
    "request", "queue", "compute_input", "compute_infer", "compute_output",
)

# Quantiles exposed per sketch-backed /metrics summary family.
_METRIC_QUANTILES = (0.5, 0.9, 0.99, 0.999)


class _ModelStats:
    def __init__(self):
        self.inference_count = 0
        self.execution_count = 0
        self.last_inference = 0
        self.success_count = 0
        self.success_ns = 0
        self.fail_count = 0
        self.fail_ns = 0
        self.cancel_count = 0
        self.cancel_ns = 0
        self.queue_ns = 0
        self.compute_input_ns = 0
        self.compute_infer_ns = 0
        self.compute_output_ns = 0
        # Requests whose KServe `timeout` budget elapsed before the
        # response went out (observation only — the request still ran).
        self.deadline_exceeded_count = 0
        # Requests the batcher shed instead of serving, by reason:
        # admission (budget provably smaller than the service estimate),
        # expired (deadline elapsed while queued), cancelled (client went
        # away while queued). The nv_inference_shed_total counter family.
        self.shed_counts = {reason: 0 for reason in SHED_REASONS}
        # Requests rejected by boundary validation before any execution,
        # by canonical reason (protocol/_literals.INVALID_REASONS). The
        # nv_inference_invalid_request_total counter family; the same
        # reason rides the flight record as ``invalid.reason``.
        self.invalid_counts = {reason: 0 for reason in INVALID_REASONS}
        # Per-bucket (non-cumulative) request-duration counts; the +Inf
        # bucket is the trailing slot. Every success AND failure observes
        # exactly once, so +Inf cumulative == success_count + fail_count.
        self.duration_buckets = [0] * (len(_DURATION_BUCKETS_US) + 1)
        # Mergeable relative-error quantile sketches (microseconds) per
        # stage: the histogram's fixed buckets smear the tail, these do
        # not (<= 2% relative error at any quantile). Mutated only under
        # the core lock, same as every other counter here.
        self.sketches = {name: LatencySketch() for name in _SKETCH_STAGES}
        # Requests admitted (infer()/infer_submit()) but not yet answered:
        # the queue-depth gauge. Returns to 0 when the server is idle.
        self.pending = 0
        # Requests admitted whose estimated device bytes exceeded the
        # model's memscope headroom at that instant. Observation only —
        # nothing is rejected — the nv_inference_headroom_near_miss_total
        # counter family (see _stamp_headroom).
        self.headroom_near_miss = 0

    def observe_duration(self, duration_ns: int):
        us = duration_ns // 1000
        self.sketches["request"].insert(us)
        for i, edge in enumerate(_DURATION_BUCKETS_US):
            if us <= edge:
                self.duration_buckets[i] += 1
                return
        self.duration_buckets[-1] += 1

    def observe_stages(self, input_ns: int, infer_ns: int, output_ns: int,
                       n: int = 1):
        """Per-request compute-stage samples (success path, microseconds);
        the queue stage is observed by the dynamic batcher at dispatch."""
        self.sketches["compute_input"].insert(input_ns // 1000, n)
        self.sketches["compute_infer"].insert(infer_ns // 1000, n)
        self.sketches["compute_output"].insert(output_ns // 1000, n)

    def as_dict(self, name: str, version: str) -> dict:
        return {
            "name": name,
            "version": version,
            "last_inference": self.last_inference,
            "inference_count": self.inference_count,
            "execution_count": self.execution_count,
            "inference_stats": {
                "success": {"count": self.success_count, "ns": self.success_ns},
                "fail": {"count": self.fail_count, "ns": self.fail_ns},
                "cancel": {"count": self.cancel_count, "ns": self.cancel_ns},
                "queue": {"count": self.success_count, "ns": self.queue_ns},
                "compute_input": {
                    "count": self.success_count,
                    "ns": self.compute_input_ns,
                },
                "compute_infer": {
                    "count": self.success_count,
                    "ns": self.compute_infer_ns,
                },
                "compute_output": {
                    "count": self.success_count,
                    "ns": self.compute_output_ns,
                },
                "cache_hit": {"count": 0, "ns": 0},
                "cache_miss": {"count": 0, "ns": 0},
            },
            "batch_stats": [],
        }


_DEFAULT_TRACE_SETTINGS = {
    "trace_level": ["OFF"],
    "trace_rate": ["1000"],
    "trace_count": ["-1"],
    "log_frequency": ["0"],
    "trace_file": [""],
    "trace_mode": ["triton"],
}

_DEFAULT_LOG_SETTINGS = {
    "log_file": "",
    "log_info": True,
    "log_warning": True,
    "log_error": True,
    "log_verbose_level": 0,
    "log_format": "default",
}


class _FileOverrideModel:
    """Repository entry created by ``load_model(files=...)``.

    The JAX backend cannot execute foreign model binaries (the reference
    test loads an ONNX blob, cc_client_test.cc:1202-1350); what the
    file-override feature contractually provides is repository semantics:
    the entry serves the version set named by the ``file:<version>/<path>``
    keys, reports the override config, and shadows any same-named
    repository model until a plain load restores it. Inference against it
    is a clear 400.
    """

    def __init__(self, name: str, config_override: dict, files: Dict[str, object]):
        import base64 as _b64

        self.name = name
        self.platform = config_override.get("backend", "")
        self._config_override = dict(config_override)
        self.files: Dict[str, bytes] = {}
        for path, content in files.items():
            if isinstance(content, str):
                # HTTP carries file contents base64-encoded in JSON params.
                try:
                    content = _b64.b64decode(content)
                except (ValueError, TypeError):
                    raise CoreError(
                        f"failed to load '{name}': invalid base64 file "
                        f"content for '{path}'",
                        STATUS_INVALID,
                    )
            self.files[path] = bytes(content)
        # Numeric latest-version semantics: ['2', '10'] must pick '10'
        # (lexicographic sort would pick '2'); non-numeric names sort after.
        versions = sorted(
            {p.split("/", 1)[0] for p in self.files if "/" in p},
            key=lambda v: (
                not v.isdecimal(),
                int(v) if v.isdecimal() else 0,
                v,
            ),
        )
        self.versions = versions or ["1"]
        self.version = self.versions[-1]
        self.inputs: List = []
        self.outputs: List = []

    def metadata(self) -> dict:
        return {
            "name": self.name,
            "versions": self.versions,
            "platform": self.platform,
            "inputs": [],
            "outputs": [],
        }

    def config(self) -> dict:
        cfg = {
            "name": self.name,
            "platform": self.platform,
            "backend": self.platform,
            "max_batch_size": 0,
            "input": [],
            "output": [],
        }
        cfg.update(self._config_override)
        return cfg

    def infer(self, inputs, parameters=None):
        raise CoreError(
            f"model '{self.name}' was loaded with a file override; the JAX "
            "backend cannot execute foreign model binaries",
            STATUS_INVALID,
        )


# --------------------------------------------------------------------------- #
# the core                                                                    #
# --------------------------------------------------------------------------- #


class _BatchSlot:
    __slots__ = ("request", "signature", "rows", "response", "error",
                 "done", "event", "t_enqueue", "deadline_ns")

    def __init__(self, request, signature, rows):
        self.request = request
        self.signature = signature
        self.rows = rows
        self.response = None
        self.error = None
        self.done = False
        # Per-slot completion event: waking only this slot's waiter
        # avoids the thundering herd of a shared cv (every batch
        # completion waking EVERY stream's waiter costs a GIL pass each
        # on a small-core host).
        self.event = threading.Event()
        self.t_enqueue = time.monotonic_ns()
        # Absolute deadline (monotonic ns; 0 = no deadline): the EDF sort
        # key, and the expiry bound the dispatcher sweeps against.
        self.deadline_ns = 0


class _DynamicBatcher:
    """Dispatcher-threaded dynamic batching for one model.

    Arrivals enqueue and wait; a per-model dispatcher thread drains the
    queue into maximal per-signature batches and dispatches each batch
    WITHOUT waiting for its completion — device executions overlap freely
    (XLA queues them in order) and each waiter is woken when its batch's
    responses are built. Batch size therefore self-balances with load:
    the busier the server, the more requests accumulate per drain, while
    an unloaded server dispatches singles with zero added latency.

    Earlier designs executed batches on a leader request thread, one at a
    time: a batch execution costs real wall time (input concat + dispatch
    enqueue — several ms on remote-dispatch links), and serializing
    executions made the batcher the bottleneck (measured ~50 ms queue
    delay at depth 32, ~85% executor utilization). The dispatcher only
    pays the enqueue cost per batch, so its saturation point is an order
    of magnitude higher, and when it IS behind, the backlog turns into
    bigger batches instead of queue delay.

    This is the in-process analog of Triton's dynamic_batching scheduler
    (the reference repo is client-only; its servers batch the same way).
    """

    def __init__(self, core, max_queue_delay_us: int = 0):
        self.core = core
        self._cv = sanitize.named_condition("_DynamicBatcher._cv")
        self._queue: List[_BatchSlot] = []
        # Triton's dynamic_batching.max_queue_delay_microseconds: the
        # dispatcher holds a forming batch open up to this long (or until
        # the row cap) before dispatching — but only under rate pressure
        # (see _run). 0 = natural batching only.
        self.max_queue_delay_us = int(max_queue_delay_us)
        # PER-SIGNATURE arrival windows for the rate half of the pressure
        # gate: one shared deque let a hot shape evict another signature's
        # rate history and flip its serialize/hold regime (ADVICE r5 #2).
        # Each signature keeps its own bounded deque of timestamps —
        # appends stay O(1), and beyond a window's cap that signature's
        # rate is trivially "pressured" anyway.
        import collections

        self._arrival_deque = collections.deque  # bound per signature
        self._arrivals: Dict[tuple, "collections.deque"] = {}
        # Arrivals the rate gate must promise within one delay window
        # before the dispatcher holds (rate * delay >= this).
        try:
            self._rate_factor = float(
                os.environ.get("TPU_SERVER_BATCH_RATE_FACTOR", "1.0")
            )
        except ValueError:
            self._rate_factor = 1.0
        # A few dispatcher threads overlap the blocking per-batch
        # dispatch-enqueue (several ms on remote-dispatch links): one
        # dispatcher's cycle time otherwise lower-bounds every request's
        # queue wait at moderate depth. Batches stay disjoint (the take
        # happens under the lock); more dispatchers trade batch size for
        # cycle latency, and 2-3 measured best at depth 16.
        try:
            self._n_dispatchers = max(
                1, int(os.environ.get("TPU_SERVER_BATCH_DISPATCHERS", "3"))
            )
        except ValueError:
            self._n_dispatchers = 3
        self._threads: List[threading.Thread] = []
        self._dispatching = 0  # batches currently being dispatched
        # Arrivals/100ms above which the batcher serializes dispatches
        # and accumulates (the CPU-bound regime); below it, backlog
        # spreads across dispatchers (the latency-bound regime).
        try:
            self._serial_rate = int(
                os.environ.get("TPU_SERVER_BATCH_SERIAL_RATE", "32")
            )
        except ValueError:
            self._serial_rate = 32
        # Per-SIGNATURE regime state: the rate is measured per signature,
        # so the hysteresis must be too — a shared flag would let a hot
        # signature drag an unrelated one into the wrong regime.
        self._serialized: Dict[tuple, bool] = {}
        # repr(signature) cached per signature: the flight recorder wants
        # it stamped on every request, and rebuilding the string costs
        # more than the rest of the admission bookkeeping combined.
        self._sig_labels: Dict[tuple, str] = {}
        # Per-signature EWMA of recent batch service times (microseconds,
        # enqueue-to-completion of one dispatched batch): the admission
        # gate's service estimate. Updated by the dispatcher under _cv
        # after each batch completes — deliberately NOT under the core
        # stats lock, so the admission path never nests _cv with it.
        self._service_ewma_us: Dict[tuple, float] = {}
        # Queued slots carrying a deadline: lets the EDF head selection
        # and the expiry half of the sweep short-circuit to pure FIFO
        # when no deadline traffic is queued (the default path).
        self._deadline_queued = 0
        self._model = None
        self._stats = None
        self._cap = 0
        # Monotone batch id, stamped onto traced members' queue-wait and
        # compute spans so a trace viewer can group batchmates.
        self._batch_seq = 0

    def qsize(self) -> int:
        """Current queue length (the nv_inference_queue_depth gauge)."""
        with self._cv:
            return len(self._queue)

    def oldest_age_us(self) -> int:
        """Age of the oldest queued request in microseconds (the
        nv_inference_oldest_request_age_us gauge; 0 when the queue is
        empty). Depth alone cannot distinguish a deep-but-moving queue
        from a stalled one — age can."""
        with self._cv:
            if not self._queue:
                return 0
            # Appends at the tail, removals anywhere: index 0 is always
            # the oldest surviving arrival.
            return max(
                (time.monotonic_ns() - self._queue[0].t_enqueue) // 1000, 0
            )

    def eligible(self, request: CoreRequest, cap: int) -> bool:
        # Sequence/priority parameters, BYTES tensors, rank-0 or empty
        # inputs, inconsistent per-input batch dims, and single requests
        # already exceeding the model's batch dimension bypass batching
        # (dim 0 must be one consistent free batch axis the model promised
        # to handle up to `cap` rows of).
        if cap <= 0 or request.parameters or not request.inputs:
            return False
        rows = None
        for t in request.inputs:
            if t.datatype == "BYTES" or not t.shape:
                return False
            if rows is None:
                rows = int(t.shape[0])
            elif int(t.shape[0]) != rows:
                return False
        if rows < 1 or rows > cap:
            return False
        return True

    def submit(self, model, request: CoreRequest, stats,
               cap: int) -> _BatchSlot:
        """Enqueue without waiting (two-phase API for pipelined
        transports: the stream feeder submits, the response yielder
        waits). Never blocks beyond the lock."""
        signature = tuple(
            (t.name, t.datatype, tuple(t.shape[1:])) for t in request.inputs
        )
        slot = _BatchSlot(request, signature,
                          int(request.inputs[0].shape[0]))
        if request.deadline_us:
            slot.deadline_ns = slot.t_enqueue + request.deadline_us * 1000
        trace = request.trace
        if trace is not None:
            trace.record("QUEUE_START", slot.t_enqueue)
        est_us = None
        with self._cv:
            # Per-model batcher: model/stats/cap are stable across calls.
            self._model, self._stats, self._cap = model, stats, cap
            if trace is not None:
                # Batcher context at ADMISSION: what the queue looked like
                # when this request joined it — the flight recorder's
                # backlog-correlation signal (tail_report consumes these).
                trace.set_attribute(
                    "batcher.backlog_at_admission", len(self._queue)
                )
                trace.set_attribute(
                    "batcher.oldest_age_us",
                    max((slot.t_enqueue - self._queue[0].t_enqueue) // 1000,
                        0) if self._queue else 0,
                )
                label = self._sig_labels.get(signature)
                if label is None:
                    if len(self._sig_labels) > 64:
                        self._sig_labels.clear()  # one-off shape churn
                    label = self._sig_labels[signature] = repr(signature)
                trace.set_attribute("batcher.signature", label)
            if slot.deadline_ns:
                # Admission control: reject NOW when the deadline budget is
                # provably smaller than a conservative (under-)estimate of
                # time-to-response — a fast 504 instead of a guaranteed
                # queue-then-miss. Conservative on purpose: with no service
                # evidence yet (cold EWMA) the request is admitted.
                est_us = self._estimate_service_us(
                    signature, slot.deadline_ns, cap
                )
                if est_us is not None and est_us <= request.deadline_us:
                    est_us = None  # budget covers the estimate: admit
            # Arrival bookkeeping feeds both the hold gate and the
            # serialize/spread regime switch — always on. Per-signature
            # windows: one shape's burst cannot evict another's history.
            self._note_arrival(signature, time.monotonic())
            if est_us is None:
                if slot.deadline_ns:
                    self._deadline_queued += 1
                self._queue.append(slot)
                self._threads = [t for t in self._threads if t.is_alive()]
                if len(self._threads) < self._n_dispatchers:
                    t = threading.Thread(
                        target=self._run, daemon=True,
                        name=f"tpu-batcher-{model.name}",
                    )
                    self._threads.append(t)
                    t.start()
                self._cv.notify_all()
        if est_us is not None:
            # Shed accounting + the raise happen OUTSIDE the cv: the stats
            # lock must never nest under the batcher cv (tpusan's lock-
            # order witness watches exactly this pair).
            self._record_shed(stats, SHED_REASON_ADMISSION, trace)
            raise CoreError(
                f"request to model '{request.model_name}' shed at "
                f"admission: deadline budget {request.deadline_us} us "
                f"cannot cover the estimated queue+service time of "
                f"{est_us} us",
                STATUS_SHED,
            )
        return slot

    def _note_arrival(self, signature, now: float):  # tpulint: disable=TPU002 - caller holds self._cv
        """Record one arrival in the signature's own rate window."""
        window = self._arrivals.get(signature)
        if window is None:
            if len(self._arrivals) > 64:
                # Bound churn from one-off shapes (same policy as the
                # _serialized regime map).
                self._arrivals.clear()
            window = self._arrivals[signature] = self._arrival_deque(
                maxlen=128
            )
        window.append(now)
        while window and now - window[0] > 0.1:
            window.popleft()

    def _recent(self, signature, now: float) -> int:  # tpulint: disable=TPU002 - caller holds self._cv
        """Arrivals of ``signature`` in the last 100 ms."""
        return sum(
            1 for t in self._arrivals.get(signature, ()) if now - t < 0.1
        )

    # -- deadline-aware scheduling --------------------------------------------

    def _estimate_service_us(self, signature, deadline_ns, cap):  # tpulint: disable=TPU002 - caller holds self._cv
        """Conservative time-to-response estimate for a deadline request.

        Under EDF only earlier-deadline work runs ahead of this request,
        so the estimate is (same-signature earlier-deadline batches ahead
        + the request's own batch) x the signature's service EWMA. Every
        term UNDER-estimates (floor division, same-signature only, queue
        work only) so admission control sheds only provable misses.
        Returns None when there is no service evidence yet (cold EWMA).
        """
        ewma = self._service_ewma_us.get(signature)
        if ewma is None or cap <= 0:
            return None
        ahead = sum(
            s.rows for s in self._queue
            if s.deadline_ns and s.deadline_ns <= deadline_ns
            and s.signature == signature
        )
        return int((ahead // cap + 1) * ewma)

    def _record_shed(self, stats, reason: str, trace):
        """Shed bookkeeping (NO locks held by the caller): counter bump
        under the core lock, reason stamped on the flight record."""
        if trace is not None:
            trace.set_attribute("shed.reason", reason)
        with self.core._lock:
            stats.shed_counts[reason] += 1

    def _sweep_shed(self):  # tpulint: disable=TPU002 - caller holds self._cv
        """Remove expired/cancelled slots from the queue.

        Returns [(slot, reason)] for the caller to finalize OUTSIDE the
        cv (_finalize_shed). An expired deadline is answered here in
        queue-removal time — the 504 costs the waiter a wakeup, not the
        tail of the backlog ahead of it.
        """
        shed = []
        now_ns = time.monotonic_ns() if self._deadline_queued else 0
        for s in self._queue:
            ev = s.request.cancel_event
            if ev is not None and ev.is_set():
                shed.append((s, SHED_REASON_CANCELLED))
            elif s.deadline_ns and now_ns > s.deadline_ns:
                shed.append((s, SHED_REASON_EXPIRED))
        for s, _reason in shed:
            self._remove_slot(s)
        return shed

    def _remove_slot(self, slot):  # tpulint: disable=TPU002 - caller holds self._cv
        """Queue removal that keeps the deadline count honest."""
        self._queue.remove(slot)
        if slot.deadline_ns:
            self._deadline_queued -= 1

    def _finalize_shed(self, shed):
        """Answer swept slots (caller must NOT hold the cv): stats under
        the core lock, then per-slot error + waiter wakeup."""
        # Stable per-model reference; GIL-atomic read (same contract as
        # the dispatcher's model/stats snapshot).
        stats = self._stats  # tpulint: disable=TPU002,TPU009
        with self.core._lock:
            for _slot, reason in shed:
                stats.shed_counts[reason] += 1
        now_ns = time.monotonic_ns()
        for slot, reason in shed:
            request = slot.request
            trace = request.trace
            if trace is not None:
                trace.set_attribute("shed.reason", reason)
                # Where in the decode loop the request died: engines
                # mirror tokens-delivered onto the cancel event (see
                # gpt_engine._Distributor). Batcher-queued requests never
                # started a decode loop, so the attribute defaults to 0.
                trace.set_attribute("steps_completed", int(getattr(
                    request.cancel_event, "steps_completed", 0) or 0))
                # KV pages the request was holding when it died: engines
                # mirror the committed reservation onto the cancel event
                # (gpt_engine._reserve). Queued-never-started requests
                # held nothing, so the attributes default to 0.
                trace.set_attribute("kv_pages_held", int(getattr(
                    request.cancel_event, "kv_pages_held", 0) or 0))
                trace.set_attribute("kv_bytes_held", int(getattr(
                    request.cancel_event, "kv_bytes_held", 0) or 0))
            waited_us = max((now_ns - slot.t_enqueue) // 1000, 0)
            if reason == SHED_REASON_CANCELLED:
                slot.error = CoreError(
                    f"request to model '{request.model_name}' cancelled "
                    f"by the client after {waited_us} us in queue",
                    STATUS_CANCELLED,
                )
            else:
                slot.error = CoreError(
                    f"request to model '{request.model_name}' shed: "
                    f"deadline budget {request.deadline_us} us expired "
                    f"after {waited_us} us in queue",
                    STATUS_SHED,
                )
            slot.done = True
            slot.event.set()

    def wait(self, slot: _BatchSlot, model) -> CoreResponse:
        extensions = 0
        while not slot.event.wait(timeout=60.0):
            # Still queued -> the dispatcher never took it: fail this
            # request. Already captured into an in-flight batch -> it
            # should complete; extend a bounded number of times rather
            # than answering 500 for work that is executing, but a
            # wedged batch must not hang this thread forever.
            with self._cv:
                still_queued = slot in self._queue
                if still_queued:
                    self._remove_slot(slot)
            if not still_queued and extensions < 4:
                extensions += 1
                continue
            if slot.done:
                # Completed in the window between the wait() timeout
                # and this check: deliver the result, not a spurious
                # 500 for work that finished.
                break
            raise CoreError(
                f"dynamic batch wait timed out for model "
                f"'{model.name}'",
                500,
            )
        if slot.error is not None:
            raise slot.error
        return slot.response

    def infer(self, model, request: CoreRequest, stats,
              cap: int) -> CoreResponse:
        return self.wait(self.submit(model, request, stats, cap), model)

    # -- dispatcher thread ----------------------------------------------------

    def _take_batch(self):  # tpulint: disable=TPU002 - caller holds self._cv
        """Under the lock: form one batch for the head-of-line signature.

        Head selection is earliest-deadline-first among deadline-carrying
        slots; with no deadline traffic queued the head is queue[0] — the
        no-deadline default path stays byte-identical FIFO. Batch mates
        (same signature, FIFO order) ride along regardless of deadline.

        Returns the batch, or None when a gate wants to keep waiting
        (caller re-checks after a cv wait)."""
        head = self._queue[0]
        if self._deadline_queued:
            best_ns = 0
            for s in self._queue:
                if s.deadline_ns and (best_ns == 0
                                      or s.deadline_ns < best_ns):
                    head, best_ns = s, s.deadline_ns
        signature = head.signature
        cap = self._cap
        # Head first so a cap-full batch can never cut the EDF head. The
        # remaining mates fill EDF-first too: deadline slots in deadline
        # order, then no-deadline FIFO — otherwise a deep no-deadline
        # backlog fills every batch and deadline traffic drains one head
        # per dispatch instead of a batch per dispatch.
        if self._deadline_queued:
            others = [
                s for s in self._queue
                if s is not head and s.signature == signature
            ]
            mates = [head] + sorted(
                (s for s in others if s.deadline_ns),
                key=lambda s: s.deadline_ns,
            ) + [s for s in others if not s.deadline_ns]
        else:
            mates = [head] + [
                s for s in self._queue
                if s is not head and s.signature == signature
            ]
        rows = 0
        batch = []
        for s in mates:
            if batch and rows + s.rows > cap:
                break
            batch.append(s)
            rows += s.rows
        # The head ALWAYS rides (even if a live config override shrank
        # the cap below its rows since submit-time eligibility): an
        # empty take would spin the dispatcher while the head starves.
        # Regime switch on the measured arrival rate of this signature
        # (last 100 ms). Two bottleneck regimes need opposite policies:
        #   * high rate -> the host CPU is the bottleneck (per-dispatch
        #     fixed cost x rate saturates a small-core host): SERIALIZE —
        #     one dispatch at a time, accumulate the backlog into big
        #     batches (fewer ops, lowest CPU/request);
        #   * low/moderate rate -> latency is the bottleneck: SPREAD the
        #     backlog across free dispatchers (ceil(backlog/free) each),
        #     overlapping dispatch-enqueues. This also breaks the small-
        #     batch phase-lock where batchmates complete, re-arrive, and
        #     re-batch together, paying formation latency for no
        #     amortization.
        # Both measured (r5 A/B): serialize wins ~7% at depth 32, spread
        # wins ~15-20% at depth 16 / batch 1. The threshold is the rate
        # where fixed per-dispatch CPU (~1 ms) becomes a ~third of a
        # core, env-tunable for bigger hosts.
        now = time.monotonic()
        recent = self._recent(signature, now)
        # Hysteresis: a workload sitting AT the threshold would flap
        # between regimes (each flap pays the worse policy's cost);
        # enter serialize at the threshold, leave only when the rate
        # falls 30% below it (at least 1 — a zero exit threshold could
        # never be crossed and would latch serialize forever).
        serialized = self._serialized.get(signature, False)
        if serialized:
            if recent < max(1, int(0.7 * self._serial_rate)):
                serialized = False
        elif recent >= self._serial_rate:
            serialized = True
        if len(self._serialized) > 64 and signature not in self._serialized:
            self._serialized.clear()  # bound churn from one-off shapes
        self._serialized[signature] = serialized
        if serialized:
            if self._dispatching >= 1:
                return None  # accumulate behind the in-flight dispatch
        else:
            free = max(1, self._n_dispatchers - self._dispatching)
            take_n = -(-len(batch) // free)  # ceil
            batch = batch[:take_n]
        rows = sum(s.rows for s in batch)
        # Pressure-gated hold: keep the batch open only while the arrival
        # rate of THIS signature promises >= rate_factor more arrivals
        # within one delay window (measured over the last 100 ms) and the
        # row cap is not yet reached. Light load never pays the hold.
        # Deadline heads are never held: batch-formation latency spends
        # the one budget EDF exists to protect.
        delay_s = self.max_queue_delay_us / 1e6
        if delay_s > 0 and rows < cap and not head.deadline_ns:
            rate_pressured = recent >= max(
                2, int(self._rate_factor * 0.1 / delay_s)
            )
            # Hold relative to the head's enqueue time so a batch is
            # never held past max_queue_delay total.
            head_age = now - self._enqueue_monotonic(head)
            if rate_pressured and head_age < delay_s:
                return None
        for s in batch:
            self._remove_slot(s)
        return batch

    @staticmethod
    def _enqueue_monotonic(slot) -> float:
        # t_enqueue is monotonic_ns (shared with the stats clock).
        return slot.t_enqueue / 1e9

    # tpulint: hot-path
    def _run(self):
        while True:
            batch = None
            with self._cv:
                while not self._queue:
                    got = self._cv.wait(timeout=5.0)
                    if not got and not self._queue:
                        # Idle: park this dispatcher. Deregister UNDER
                        # THE LOCK so a concurrent submit() never counts
                        # a departing thread as live capacity (it would
                        # spawn nothing and strand the request until the
                        # wait() timeout).
                        try:
                            self._threads.remove(threading.current_thread())
                        except ValueError:
                            pass
                        return
                # Deadline sweep at take time: expired and cancelled slots
                # leave the queue NOW and are answered below, OUTSIDE the
                # cv — a blown deadline costs its waiter one wakeup, not
                # the backlog ahead of it.
                shed = self._sweep_shed()
                if self._queue:
                    batch = self._take_batch()
                if batch is None and not shed:
                    # Gate open (hold window / overlap minimum): wait for
                    # arrivals, an age-out, or an in-flight dispatch to
                    # finish (its completion notifies). Bounded park, not
                    # a predicate wait — the loop re-derives sweep/take
                    # state from scratch every pass, so timeout-vs-wakeup
                    # carries no information.
                    self._cv.wait(timeout=0.005)  # tpulint: disable=TPU011
                    continue
                if batch is not None:
                    self._dispatching += 1
                    self._batch_seq += 1
                    batch_id = self._batch_seq
                    model, stats = self._model, self._stats
                    # The hold/regime decision in force when this batch
                    # formed (per-signature hysteresis state, read under
                    # the cv).
                    regime = (
                        "serialize"
                        if self._serialized.get(batch[0].signature)
                        else "spread"
                    )
                if self._queue:
                    # The spread rule may leave backlog for siblings:
                    # wake them to take it concurrently.
                    self._cv.notify_all()
            if shed:
                self._finalize_shed(shed)
            if batch is None:
                continue
            t_exec = 0
            try:
                # Triton queue-duration semantics: time a request waited
                # between batcher enqueue and batch execution start.
                t_exec = time.monotonic_ns()
                oldest_wait_us = (
                    t_exec - min(s.t_enqueue for s in batch)
                ) // 1000
                with self.core._lock:
                    for s in batch:
                        stats.queue_ns += t_exec - s.t_enqueue
                        stats.sketches["queue"].insert(
                            (t_exec - s.t_enqueue) // 1000
                        )
                for i, s in enumerate(batch):
                    if s.request.trace is not None:
                        # Batch identity on the spans batching shapes: the
                        # span-tree builder copies these onto the
                        # queue-wait and compute child spans. BATCH_FORM is
                        # the queue-wait/batch-formation stage boundary.
                        trace = s.request.trace
                        trace.record("BATCH_FORM", t_exec)
                        trace.set_attribute("batch.id", batch_id)
                        trace.set_attribute("batch.size", len(batch))
                        trace.set_attribute("batch.slot", i)
                        trace.set_attribute("batcher.regime", regime)
                        trace.set_attribute(
                            "batch.oldest_wait_us", oldest_wait_us
                        )
                try:
                    results = self.core._infer_batch(
                        model, [s.request for s in batch], stats
                    )
                    for s, res in zip(batch, results):
                        if isinstance(res, CoreError):
                            s.error = res
                        else:
                            s.response = res
                except CoreError as e:
                    for s in batch:
                        s.error = e
                except Exception as e:  # defensive: surface to every waiter
                    err = CoreError(
                        f"inference failed for model '{model.name}': {e}",
                        500,
                    )
                    for s in batch:
                        s.error = err
                for s in batch:
                    s.done = True
                    s.event.set()  # wakes exactly this slot's waiter
            finally:
                with self._cv:
                    self._dispatching -= 1
                    if t_exec:
                        # Per-signature EWMA of batch service time (the
                        # admission gate's evidence), updated under the cv
                        # it is read under. Includes failed batches — a
                        # wedged model should make admission MORE
                        # pessimistic, not blind.
                        service_us = (time.monotonic_ns() - t_exec) // 1000
                        sig = batch[0].signature
                        prior = self._service_ewma_us.get(sig)
                        if prior is None:
                            if len(self._service_ewma_us) > 64:
                                self._service_ewma_us.clear()  # shape churn
                            self._service_ewma_us[sig] = float(service_us)
                        else:
                            self._service_ewma_us[sig] = (
                                0.75 * prior + 0.25 * service_us
                            )
                    self._cv.notify_all()


class InferenceCore:
    """Model repository + executor + admin surface, shared by both transports."""

    def __init__(self, models=None, server_name: str = SERVER_NAME):
        self.server_name = server_name
        self.server_version = SERVER_VERSION
        self.extensions = list(SERVER_EXTENSIONS)
        self._repository: Dict[str, object] = {}
        self._loaded: Dict[str, bool] = {}
        self._stats: Dict[str, _ModelStats] = {}
        # name -> the repository model shadowed by a file-override load
        # (restored on the next plain/config-only load, Triton semantics).
        self._overridden: Dict[str, object] = {}
        self._lock = sanitize.named_lock("InferenceCore._lock")
        self.system_shm = SystemShmRegistry()
        self.tpu_shm = TpuShmRegistry()
        # Trace settings: the "" entry is the complete global dict; model
        # entries hold ONLY the keys explicitly overridden for that model,
        # so un-overridden keys *track* later global updates (Triton
        # semantics — get_trace_settings merges at read time).
        self._trace_settings: Dict[str, dict] = {"": dict(_DEFAULT_TRACE_SETTINGS)}
        self.trace_collector = TraceCollector()
        # Tail-based retention, the inverse of the collector's head
        # sampling: always on (TPU_FLIGHT_RECORDER=0 disables), dumped via
        # v2/debug/flight_recorder on both front-ends.
        self.flight_recorder = FlightRecorder(
            on_deadline_miss=self._record_deadline_miss
        )
        self._log_settings = dict(_DEFAULT_LOG_SETTINGS)
        self._log = logging.getLogger("tritonclient_tpu.server")
        self._log_verbose = 0
        # Per-protocol ingress counters ("http", "grpc"), fed by the
        # front-ends via record_protocol_request.
        self._protocol_requests: Dict[str, int] = {}
        self._batchers: Dict[str, _DynamicBatcher] = {}
        self._dynamic_batching = (
            os.environ.get("TPU_SERVER_DYNAMIC_BATCH", "1") != "0"
        )
        # Fleet drain state: while draining, v2/health/ready reports 400
        # (the router — or any health-driven balancer — stops admitting)
        # but in-flight requests keep executing to completion. Guarded by
        # self._lock; readiness_detail() is what the router polls to know
        # the drain has settled (in_flight == 0).
        self._draining = False
        for model in models or []:
            self.add_model(model)

    # -- repository ----------------------------------------------------------

    def add_model(self, model, loaded: bool = True):
        with self._lock:
            self._repository[model.name] = model
            self._loaded[model.name] = loaded
            self._stats.setdefault(model.name, _ModelStats())
        if (
            self._dynamic_batching
            and getattr(model, "dynamic_batching", False)
            and not model.decoupled
        ):
            default_us = getattr(model, "max_queue_delay_us", 0)
            try:
                delay_us = int(
                    os.environ.get("TPU_SERVER_BATCH_DELAY_US", default_us)
                )
            except ValueError:
                # An empty/garbage env value must not take down model
                # registration (ADVICE r4) — fall back to the model's own
                # delay and say so.
                logging.getLogger("tritonclient_tpu.server").warning(
                    "ignoring non-numeric TPU_SERVER_BATCH_DELAY_US=%r; "
                    "using model default %d us",
                    os.environ.get("TPU_SERVER_BATCH_DELAY_US"), default_us,
                )
                delay_us = int(default_us)
            with self._lock:
                self._batchers[model.name] = _DynamicBatcher(self, delay_us)

    def _get_model(self, name: str, version: str = ""):
        with self._lock:
            model = self._repository.get(name)
            loaded = self._loaded.get(name, False)
        if model is None:
            raise CoreError(f"Request for unknown model: '{name}'", 404)
        if not loaded:
            raise CoreError(
                f"Request for unknown model: '{name}' is not ready", STATUS_INVALID
            )
        versions = getattr(model, "versions", None) or [model.version]
        if version and str(version) not in [str(v) for v in versions]:
            raise CoreError(
                f"Request for unknown model version: '{name}' version {version}", STATUS_INVALID
            )
        return model

    def peek_model(self, name: str):
        """Locked best-effort repository lookup (no readiness check) for
        the front-ends' routing predicates — the stream serial barrier
        and the aio blocking-model offload race load/unload, which
        mutate the repository under the core lock (TPU009)."""
        with self._lock:
            return self._repository.get(name)

    def is_server_live(self) -> bool:
        return True

    def is_server_ready(self) -> bool:
        # A draining server is alive but not READY: health-driven routers
        # stop admitting while in-flight work finishes (rolling restart).
        with self._lock:
            return not self._draining

    # -- fleet drain ---------------------------------------------------------

    def set_draining(self, draining: bool) -> dict:
        """Enter/leave drain mode; returns the readiness detail after the
        change. Draining only flips the readiness signal — requests
        already admitted (and any that race the flip) execute normally,
        which is what makes a drain graceful."""
        with self._lock:
            self._draining = bool(draining)
        return self.readiness_detail()

    def readiness_detail(self) -> dict:
        """The readiness-detail document served beside ``v2/health/ready``
        and by the drain endpoints: whether this replica admits new work,
        whether it is draining, and how many requests are still in
        flight (admitted, not yet answered — the drain-settled signal)."""
        with self._lock:
            in_flight = sum(s.pending for s in self._stats.values())
            return {
                "ready": not self._draining,
                "draining": self._draining,
                "in_flight": int(in_flight),
            }

    def is_model_ready(self, name: str, version: str = "") -> bool:
        with self._lock:
            model = self._repository.get(name)
            loaded = self._loaded.get(name, False)
        if model is None:
            raise CoreError(f"Request for unknown model: '{name}'", STATUS_INVALID)
        if not loaded:
            return False
        if version:
            # Per-version readiness: file-override models expose the version
            # set their override directory provides (cc_client_test.cc:1202+).
            versions = getattr(model, "versions", None) or [model.version]
            return str(version) in [str(v) for v in versions]
        return True

    def server_metadata(self) -> dict:
        return {
            "name": self.server_name,
            "version": self.server_version,
            "extensions": self.extensions,
        }

    def model_metadata(self, name: str, version: str = "") -> dict:
        return self._get_model(name, version).metadata()

    def model_config(self, name: str, version: str = "") -> dict:
        return self._get_model(name, version).config()

    def repository_index(self, ready: bool = False) -> List[dict]:
        out = []
        with self._lock:
            items = sorted(self._repository.items())
            loaded = dict(self._loaded)
        for name, model in items:
            is_ready = loaded.get(name, False)
            if ready and not is_ready:
                continue
            out.append(
                {
                    "name": name,
                    "version": model.version,
                    "state": "READY" if is_ready else "UNAVAILABLE",
                    "reason": "",
                }
            )
        return out

    def load_model(self, name: str, parameters: Optional[dict] = None):
        parameters = parameters or {}
        config_override = parameters.get("config")
        files = {
            k[len("file:"):]: v
            for k, v in parameters.items()
            if k.startswith("file:")
        }

        if files:
            # File-override load (reference semantics, cc_client_test.cc:
            # 1202-1350): a config override is mandatory — the requirement
            # is Triton's reminder that the existing model directory will
            # not be used — and the loaded entry serves exactly the versions
            # the override directory provides, shadowing any repository
            # model of the same name until a plain load restores it.
            if not config_override:
                raise CoreError(
                    f"failed to load '{name}', file override requires a "
                    "config override parameter",
                    STATUS_INVALID,
                )
            try:
                override = json.loads(config_override)
            except (TypeError, ValueError):
                raise CoreError(
                    f"failed to load '{name}': invalid config override", STATUS_INVALID
                )
            override_model = _FileOverrideModel(name, override, files)
            with self._lock:
                original = self._repository.get(name)
                if original is not None and name not in self._overridden:
                    if isinstance(original, _FileOverrideModel):
                        pass  # re-override: nothing repository-owned to keep
                    else:
                        self._overridden[name] = original
                self._repository[name] = override_model
                self._loaded[name] = True
                self._stats.setdefault(name, _ModelStats())
            return

        # Plain / config-only load: revert any file override first (Triton
        # polls the repository directory again on such loads).
        with self._lock:
            if name in self._overridden:
                self._repository[name] = self._overridden.pop(name)
            model = self._repository.get(name)
            if model is None or isinstance(model, _FileOverrideModel):
                raise CoreError(f"failed to load '{name}', no such model", STATUS_INVALID)
            if config_override:
                try:
                    override = json.loads(config_override)
                except (TypeError, ValueError):
                    raise CoreError(
                        f"failed to load '{name}': invalid config override", STATUS_INVALID
                    )
                model._config_override = override
            else:
                # A plain reload reverts to the model's own config (Triton
                # semantics: no config parameter means repository config).
                model._config_override = {}
            self._loaded[name] = True
        if hasattr(model, "warmup"):
            model.warmup()

    def unload_model(self, name: str, parameters: Optional[dict] = None):
        with self._lock:
            if name not in self._repository:
                raise CoreError(f"failed to unload '{name}', no such model", STATUS_INVALID)
            self._loaded[name] = False
        # Retire the model's param/scratch ledger rows; the KV pool closes
        # itself via engine.shutdown() when the engine is torn down.
        _memscope.drop_scope(name)

    def prometheus_metrics(self) -> str:
        """Triton-compatible Prometheus exposition (the server repo's
        metrics endpoint; the reference client never scrapes it, but a
        complete serving stack exposes it — same nv_inference_* family
        and labels as Triton's /metrics on :8002)."""
        counters = (
            ("nv_inference_request_success",
             "Number of successful inference requests",
             lambda s: s.success_count),
            ("nv_inference_request_failure",
             "Number of failed inference requests",
             lambda s: s.fail_count),
            ("nv_inference_count", "Number of inferences performed",
             lambda s: s.inference_count),
            ("nv_inference_exec_count",
             "Number of model executions performed (batched)",
             lambda s: s.execution_count),
            ("nv_inference_queue_duration_us",
             "Cumulative inference queuing duration in microseconds",
             lambda s: s.queue_ns // 1000),
            ("nv_inference_compute_input_duration_us",
             "Cumulative compute input duration in microseconds",
             lambda s: s.compute_input_ns // 1000),
            ("nv_inference_compute_infer_duration_us",
             "Cumulative compute inference duration in microseconds",
             lambda s: s.compute_infer_ns // 1000),
            ("nv_inference_compute_output_duration_us",
             "Cumulative compute output duration in microseconds",
             lambda s: s.compute_output_ns // 1000),
            ("nv_inference_deadline_exceeded_total",
             "Number of inference requests that exceeded their KServe "
             "timeout budget",
             lambda s: s.deadline_exceeded_count),
        )
        quantile_families = (
            ("request", "nv_inference_request_duration_us_quantiles",
             "Request duration quantiles in microseconds (DDSketch, "
             "<=2% relative error)"),
            ("queue", "nv_inference_queue_duration_us_quantiles",
             "Queue duration quantiles in microseconds (DDSketch, "
             "<=2% relative error)"),
            ("compute_input", "nv_inference_compute_input_duration_us_quantiles",
             "Compute input duration quantiles in microseconds (DDSketch, "
             "<=2% relative error)"),
            ("compute_infer", "nv_inference_compute_infer_duration_us_quantiles",
             "Compute infer duration quantiles in microseconds (DDSketch, "
             "<=2% relative error)"),
            ("compute_output", "nv_inference_compute_output_duration_us_quantiles",
             "Compute output duration quantiles in microseconds (DDSketch, "
             "<=2% relative error)"),
        )
        with self._lock:
            # Same readiness filter as model_statistics(): unloaded models
            # must not report rows (their stats persist for a later reload,
            # but a scrape only sees what is serving).
            rows = [
                (name, self._repository[name].version, stats)
                for name, stats in sorted(self._stats.items())
                if name in self._repository and self._loaded.get(name, False)
            ]
            proto_counts = sorted(self._protocol_requests.items())
            batchers = dict(self._batchers)
            # Quantiles resolved UNDER the lock: sketch reads iterate the
            # bucket dict, and every insert happens under this same lock.
            sketch_rows = {
                (name, stage): (
                    stats.sketches[stage].quantiles(_METRIC_QUANTILES),
                    stats.sketches[stage].count,
                    stats.sketches[stage].sum,
                )
                for name, _version, stats in rows
                for stage in _SKETCH_STAGES
            }
        def esc(v: str) -> str:
            # Prometheus exposition label escaping: backslash, quote, LF.
            return (str(v).replace("\\", "\\\\").replace('"', '\\"')
                    .replace("\n", "\\n"))

        lines = []
        for metric, help_text, getter in counters:
            lines.append(f"# HELP {metric} {help_text}")
            lines.append(f"# TYPE {metric} counter")
            for name, version, stats in rows:
                lines.append(
                    f'{metric}{{model="{esc(name)}",version="{esc(version)}"}} '
                    f"{getter(stats)}"
                )
        # Shed counters: requests answered with a fast 504/cancel instead
        # of being served, by reason. All three reason rows always render
        # (zeros included) so scrapers see a stable label set and the
        # reasons provably sum to the observed sheds.
        metric = "nv_inference_shed_total"
        lines.append(
            f"# HELP {metric} Number of inference requests shed by "
            "deadline-aware scheduling instead of served, by reason"
        )
        lines.append(f"# TYPE {metric} counter")
        for name, version, stats in rows:
            for reason in SHED_REASONS:
                lines.append(
                    f'{metric}{{model="{esc(name)}",version="{esc(version)}"'
                    f',reason="{reason}"}} {stats.shed_counts[reason]}'
                )
        # Invalid-request counters: boundary-validation rejections by
        # canonical reason. Like the shed family, every reason row always
        # renders (zeros included) so scrapers see a stable label set and
        # the reasons provably sum to the observed rejections.
        metric = "nv_inference_invalid_request_total"
        lines.append(
            f"# HELP {metric} Number of inference requests rejected by "
            "boundary validation before execution, by reason"
        )
        lines.append(f"# TYPE {metric} counter")
        for name, version, stats in rows:
            for reason in INVALID_REASONS:
                lines.append(
                    f'{metric}{{model="{esc(name)}",version="{esc(version)}"'
                    f',reason="{reason}"}} {stats.invalid_counts[reason]}'
                )
        # Request-duration histogram (per-request latency distribution; the
        # cumulative sum Triton reports as a counter is this family's _sum).
        metric = "nv_inference_request_duration_us"
        lines.append(
            f"# HELP {metric} Inference request duration distribution "
            "in microseconds"
        )
        lines.append(f"# TYPE {metric} histogram")
        for name, version, stats in rows:
            labels = f'model="{esc(name)}",version="{esc(version)}"'
            cumulative = 0
            for edge, count in zip(_DURATION_BUCKETS_US,
                                   stats.duration_buckets):
                cumulative += count
                lines.append(
                    f'{metric}_bucket{{{labels},le="{edge}"}} {cumulative}'
                )
            cumulative += stats.duration_buckets[-1]
            lines.append(
                f'{metric}_bucket{{{labels},le="+Inf"}} {cumulative}'
            )
            lines.append(
                f"{metric}_sum{{{labels}}} "
                f"{(stats.success_ns + stats.fail_ns) // 1000}"
            )
            lines.append(f"{metric}_count{{{labels}}} {cumulative}")
        # Sketch-backed quantile families (Prometheus summary type): the
        # histogram above smears the tail into fixed buckets; these report
        # p50/p90/p99/p999 within <=2% relative error from the mergeable
        # DDSketch each stage maintains. Quantile rows appear once the
        # stage has samples; _sum/_count always.
        for stage, metric, help_text in quantile_families:
            lines.append(f"# HELP {metric} {help_text}")
            lines.append(f"# TYPE {metric} summary")
            for name, version, stats in rows:
                labels = f'model="{esc(name)}",version="{esc(version)}"'
                values, count, total = sketch_rows[(name, stage)]
                if count:
                    for q, value in zip(_METRIC_QUANTILES, values):
                        lines.append(
                            f'{metric}{{{labels},quantile="{q}"}} '
                            f"{value:.3f}"
                        )
                lines.append(f"{metric}_sum{{{labels}}} {total:.3f}")
                lines.append(f"{metric}_count{{{labels}}} {count}")
        # stepscope families: per-step stage breakdown + collective
        # counters for the engines (TPU_STEPSCOPE). Quantiles resolve
        # under the stepscope aggregator's own lock, mirroring
        # sketch_rows above; headers always render so scrapers see a
        # stable family set, rows appear once steps have been recorded.
        step_rows, collective_rows = _stepscope.metrics_snapshot(
            _METRIC_QUANTILES
        )
        metric = _stepscope.STEP_METRIC
        lines.append(
            f"# HELP {metric} Engine step duration quantiles in "
            "microseconds by phase and stage (DDSketch, stepscope)"
        )
        lines.append(f"# TYPE {metric} summary")
        for sname, phase, stage, values, count, total in step_rows:
            labels = (f'model="{esc(sname)}",phase="{phase}"'
                      f',stage="{stage}"')
            if count:
                for q, value in zip(_METRIC_QUANTILES, values):
                    lines.append(
                        f'{metric}{{{labels},quantile="{q}"}} {value:.3f}'
                    )
            lines.append(f"{metric}_sum{{{labels}}} {total:.3f}")
            lines.append(f"{metric}_count{{{labels}}} {count}")
        metric = _stepscope.COLLECTIVES_METRIC
        lines.append(
            f"# HELP {metric} Number of collective operations issued by "
            "engine steps, by op (stepscope; GSPMD-implicit all-reduces "
            "are charged at their expected per-step count)"
        )
        lines.append(f"# TYPE {metric} counter")
        for sname, op, ccount in collective_rows:
            lines.append(
                f'{metric}{{model="{esc(sname)}",op="{esc(op)}"}} {ccount}'
            )
        metric = _stepscope.INFLIGHT_METRIC
        lines.append(
            f"# HELP {metric} Number of dispatched decode steps whose "
            "token delivery has not completed (pipelined dispatch depth)"
        )
        lines.append(f"# TYPE {metric} gauge")
        for sname, depth in _stepscope.inflight_snapshot():
            lines.append(f'{metric}{{model="{esc(sname)}"}} {depth}')
        metric = _stepscope.KV_BYTES_METRIC
        lines.append(
            f"# HELP {metric} Paged-KV bytes engine steps touched "
            "(blocks gathered x block bytes over the block-table "
            "extent), by phase (stepscope)"
        )
        lines.append(f"# TYPE {metric} counter")
        for sname, phase, total in _stepscope.kv_bytes_snapshot():
            lines.append(
                f'{metric}{{model="{esc(sname)}",phase="{phase}"}} '
                f"{total}"
            )
        # Compile plane: distinct dispatch signatures (= XLA compile
        # cache entries) per jitted callable, and how many arrived after
        # the first (each one paid a fresh trace+compile). A growing
        # retrace counter in steady state is the TPU017 bucket-
        # discipline signal; the tpusan compile-cache watcher turns the
        # same stream into findings against declared budgets.
        compile_rows = _stepscope.compile_snapshot()
        metric = _stepscope.COMPILE_CACHE_METRIC
        lines.append(
            f"# HELP {metric} Distinct dispatch signatures recorded per "
            "jitted engine callable (compile cache entries, stepscope)"
        )
        lines.append(f"# TYPE {metric} gauge")
        for sname, cname, entries, _retraces in compile_rows:
            lines.append(
                f'{metric}{{model="{esc(sname)}",callable="{esc(cname)}"}} '
                f"{entries}"
            )
        metric = _stepscope.RETRACE_METRIC
        lines.append(
            f"# HELP {metric} Dispatch signatures first seen after a "
            "callable's initial compile — each paid a fresh XLA "
            "trace+compile (stepscope)"
        )
        lines.append(f"# TYPE {metric} counter")
        for sname, cname, _entries, retraces in compile_rows:
            lines.append(
                f'{metric}{{model="{esc(sname)}",callable="{esc(cname)}"}} '
                f"{retraces}"
            )
        # Paged-KV families (tritonclient_tpu._kvcache registry): pool
        # occupancy gauges plus the prefix-cache event counter for every
        # live engine. Headers always render (stable family set for
        # scrapers); rows appear per registered engine, and every
        # canonical event renders per model (zeros included) so hit rate
        # is computable from any single scrape.
        kv_rows = _kvcache.metrics_snapshot()
        metric = _kvcache.KV_BLOCKS_USED_METRIC
        lines.append(
            f"# HELP {metric} Number of KV cache blocks currently "
            "referenced by live requests (scratch block included)"
        )
        lines.append(f"# TYPE {metric} gauge")
        for sname, snap in kv_rows:
            lines.append(
                f'{metric}{{model="{esc(sname)}"}} {snap["used"]}'
            )
        metric = _kvcache.KV_BLOCKS_TOTAL_METRIC
        lines.append(
            f"# HELP {metric} Total number of KV cache blocks in the "
            "engine's block pool"
        )
        lines.append(f"# TYPE {metric} gauge")
        for sname, snap in kv_rows:
            lines.append(
                f'{metric}{{model="{esc(sname)}"}} {snap["total"]}'
            )
        metric = _kvcache.PREFIX_EVENTS_METRIC
        lines.append(
            f"# HELP {metric} Number of prefix-cache block events at "
            "admission, by event (hit = block reused from cache, miss = "
            "block prefilled fresh, evict = cached block reclaimed)"
        )
        lines.append(f"# TYPE {metric} counter")
        for sname, snap in kv_rows:
            events = snap.get("events", {})
            for event in PREFIX_EVENTS:
                lines.append(
                    f'{metric}{{model="{esc(sname)}",event="{event}"}} '
                    f"{events.get(event, 0)}"
                )
        # Queue-depth gauge: requests admitted but not yet answered.
        metric = "nv_inference_pending_request_count"
        lines.append(
            f"# HELP {metric} Number of inference requests awaiting "
            "execution per model"
        )
        lines.append(f"# TYPE {metric} gauge")
        for name, version, stats in rows:
            lines.append(
                f'{metric}{{model="{esc(name)}",version="{esc(version)}"}} '
                f"{stats.pending}"
            )
        # Batcher queue-depth gauge: requests sitting in the dynamic
        # batcher's queue right now (models without a batcher report 0 —
        # their requests never queue). Taken AFTER the row snapshot so the
        # readiness filter matches the other families.
        metric = "nv_inference_queue_depth"
        lines.append(
            f"# HELP {metric} Number of inference requests currently in "
            "the dynamic batching queue per model"
        )
        lines.append(f"# TYPE {metric} gauge")
        for name, version, stats in rows:
            batcher = batchers.get(name)
            depth = batcher.qsize() if batcher is not None else 0
            lines.append(
                f'{metric}{{model="{esc(name)}",version="{esc(version)}"}} '
                f"{depth}"
            )
        # Backlog-age gauge: age of the oldest queued request. Depth alone
        # cannot distinguish a deep-but-moving queue from a stalled one;
        # a high age at modest depth IS the stall signature.
        metric = "nv_inference_oldest_request_age_us"
        lines.append(
            f"# HELP {metric} Age in microseconds of the oldest request "
            "in the dynamic batching queue per model"
        )
        lines.append(f"# TYPE {metric} gauge")
        for name, version, stats in rows:
            batcher = batchers.get(name)
            age = batcher.oldest_age_us() if batcher is not None else 0
            lines.append(
                f'{metric}{{model="{esc(name)}",version="{esc(version)}"}} '
                f"{age}"
            )
        # Device-memory ledger families (tritonclient_tpu._memscope): live
        # vs peak vs reserved bytes per (model, pool), the alloc/free/park/
        # evict event counters, and the admission headroom gauge. Headers
        # always render (stable family set); rows appear per ledger cell,
        # and every canonical event renders per cell (zeros included) so
        # churn rates are computable from any single scrape.
        mem_rows = _memscope.metrics_rows()
        metric = _memscope.MEM_BYTES_METRIC
        lines.append(
            f"# HELP {metric} Accelerator memory bytes on the device-"
            "memory ledger, by pool and kind (live = resident now, peak "
            "= high-water of live, reserved = sum of per-request "
            "reservations; reserved > live measures prefix sharing)"
        )
        lines.append(f"# TYPE {metric} gauge")
        for sname, pool, kind, value in mem_rows["bytes"]:
            lines.append(
                f'{metric}{{model="{esc(sname)}",pool="{pool}"'
                f',kind="{kind}"}} {value}'
            )
        metric = _memscope.MEM_EVENTS_METRIC
        lines.append(
            f"# HELP {metric} Number of device-memory ledger events, by "
            "pool and event (alloc/free move live bytes, park/evict move "
            "prefix-cache parked bytes)"
        )
        lines.append(f"# TYPE {metric} counter")
        for sname, pool, event, count in mem_rows["events"]:
            lines.append(
                f'{metric}{{model="{esc(sname)}",pool="{pool}"'
                f',event="{event}"}} {count}'
            )
        metric = _memscope.MEM_HEADROOM_METRIC
        lines.append(
            f"# HELP {metric} Device memory bytes grantable to a new "
            "request before the model's KV pool is exhausted (parked "
            "prefix-cache bytes count as grantable)"
        )
        lines.append(f"# TYPE {metric} gauge")
        for sname, value in mem_rows["headroom"]:
            lines.append(f'{metric}{{model="{esc(sname)}"}} {value}')
        # Admission near-miss counter: requests whose shape-derived byte
        # estimate exceeded the headroom gauge at admission (observation
        # only; see _stamp_headroom).
        metric = "nv_inference_headroom_near_miss_total"
        lines.append(
            f"# HELP {metric} Number of admitted inference requests "
            "whose estimated device bytes exceeded the model's memory "
            "headroom at admission (observation only, nothing rejected)"
        )
        lines.append(f"# TYPE {metric} counter")
        for name, version, stats in rows:
            lines.append(
                f'{metric}{{model="{esc(name)}",version="{esc(version)}"}} '
                f"{stats.headroom_near_miss}"
            )
        # Shared-memory registration gauges (system + tpu planes).
        metric = "nv_shared_memory_region_count"
        lines.append(
            f"# HELP {metric} Number of registered shared memory regions"
        )
        lines.append(f"# TYPE {metric} gauge")
        for kind, registry in (("system", self.system_shm),
                               ("tpu", self.tpu_shm)):
            lines.append(
                f'{metric}{{kind="{kind}"}} {len(registry.status())}'
            )
        # Per-protocol ingress counters.
        metric = "nv_inference_protocol_request_count"
        lines.append(
            f"# HELP {metric} Number of inference requests received per "
            "protocol front-end"
        )
        lines.append(f"# TYPE {metric} counter")
        for protocol, count in proto_counts:
            lines.append(f'{metric}{{protocol="{esc(protocol)}"}} {count}')
        return "\n".join(lines) + "\n"

    def model_statistics(self, name: str = "", version: str = "") -> List[dict]:
        if name:
            model = self._get_model(name, version)
            with self._lock:
                stats = self._stats[name]
            return [stats.as_dict(name, model.version)]
        with self._lock:
            rows = [
                (n, m.version, self._stats[n])
                for n, m in sorted(self._repository.items())
                if self._loaded.get(n, False)
            ]
        return [stats.as_dict(n, version) for n, version, stats in rows]

    def sketches_dump(self) -> dict:
        """Raw per-model/per-stage DDSketch state (GET
        v2/debug/sketches): the fleet router scrapes this and merges the
        buckets bucket-wise into fleet-wide quantiles — exact, unlike
        any recombination of already-resolved quantiles. Loaded models
        only, same readiness filter as the /metrics exposition."""
        with self._lock:
            return {
                "kind": "sketches",
                "models": {
                    name: {
                        stage: stats.sketches[stage].to_dict()
                        for stage in _SKETCH_STAGES
                    }
                    for name, stats in sorted(self._stats.items())
                    if name in self._repository
                    and self._loaded.get(name, False)
                },
            }

    def memscope_dump(self) -> dict:
        """Raw device-memory ledger state (GET v2/debug/memscope):
        per-(scope, pool) cells with live/peak/reserved/parked bytes,
        per-owner reservations, static entries, recorded leaks, and the
        monotonic alloc/free event ring. mem_report.py consumes this."""
        return _memscope.dump()

    # -- trace / log settings ------------------------------------------------

    def update_trace_settings(self, model_name: str = "", settings: Optional[dict] = None) -> dict:
        for key in settings or {}:
            if key not in _DEFAULT_TRACE_SETTINGS:
                raise CoreError(f"Unknown trace setting: '{key}'", STATUS_INVALID)

        def norm(value):
            return (
                [str(v) for v in value]
                if isinstance(value, (list, tuple))
                else [str(value)]
            )

        with self._lock:
            if model_name == "":
                current = self._trace_settings[""]
                for key, value in (settings or {}).items():
                    # Clearing a global setting restores the server default.
                    current[key] = (
                        list(_DEFAULT_TRACE_SETTINGS[key])
                        if value is None
                        else norm(value)
                    )
            else:
                overrides = self._trace_settings.setdefault(model_name, {})
                for key, value in (settings or {}).items():
                    if value is None:
                        # Triton semantics: clearing a model override makes
                        # the model TRACK the global setting again (later
                        # global updates apply), not snapshot its value.
                        overrides.pop(key, None)
                    else:
                        overrides[key] = norm(value)
        return self.get_trace_settings(model_name)

    def get_trace_settings(self, model_name: str = "") -> dict:
        with self._lock:
            merged = dict(self._trace_settings[""])
            if model_name:
                merged.update(self._trace_settings.get(model_name, {}))
        return merged

    def start_trace(
        self,
        model_name: str,
        model_version: str = "",
        request_id: str = "",
        recv_ns: Optional[int] = None,
        traceparent: Optional[str] = None,
        deadline_us: int = 0,
        tenant: str = "",
    ):
        """Sample one request against the effective trace settings, and
        arm the flight recorder for it.

        Returns a TraceContext (attach it to the CoreRequest) or None.
        Called by the protocol front-ends at ingress, before parse cost is
        known — hence the fast OFF path. ``traceparent`` is the inbound
        W3C header/metadata value (or None); a parseable value continues
        the client's trace, anything else restarts it.

        Head sampling decides only whether the request lands in the
        *trace collector*; the flight recorder sees every request, so
        unsampled requests get a lightweight flight-only context (no
        collector, no W3C identity unless one arrives later). With the
        recorder disabled AND tracing off this still returns None — the
        zero-overhead path.

        ``deadline_us`` is the parsed KServe ``timeout`` request
        parameter: stamped as the ``deadline_budget_us`` span attribute;
        the flight recorder marks ``deadline_exceeded`` and bumps the
        nv_inference_deadline_exceeded_total counter when the response
        takes longer (observation only — no shedding here).
        """
        # Lock-free fast path (runs per request, before parse cost is
        # known): a GIL-atomic read of an always-present dict. The worst
        # race is one request sampled against just-cleared settings.
        ts = self._trace_settings  # tpulint: disable=TPU002,TPU009
        ctx = None
        if not (len(ts) == 1 and ts[""]["trace_level"] == ["OFF"]):
            ctx = self.trace_collector.sample(
                model_name,
                self.get_trace_settings(model_name),
                request_id=request_id,
                model_version=model_version,
                recv_ns=recv_ns,
                traceparent=traceparent,
            )
        flight = self.flight_recorder
        if ctx is None:
            if not flight.enabled:
                return None
            ctx = TraceContext(
                None, 0, model_name, model_version, request_id, (), "", "",
            )
            if recv_ns is not None:
                ctx.record("REQUEST_RECV", recv_ns)
        if flight.enabled:
            ctx._flight = flight
        if deadline_us:
            ctx.deadline_ns = int(deadline_us) * 1000
            ctx.set_attribute("deadline_budget_us", int(deadline_us))
        if tenant:
            # Tenant attribution rides every retained record: tail_report's
            # per-tenant fairness rows key on this attribute.
            ctx.set_attribute("tenant", tenant)
        return ctx

    def _record_deadline_miss(self, model_name: str):
        with self._lock:
            stats = self._stats.get(model_name)
            if stats is not None:
                stats.deadline_exceeded_count += 1

    def record_protocol_request(self, protocol: str):
        with self._lock:
            self._protocol_requests[protocol] = (
                self._protocol_requests.get(protocol, 0) + 1
            )

    def record_invalid_request(self, model_name: str, reason: str,
                               trace=None):
        """Count one boundary-validation rejection and stamp its reason.

        Called by the protocol front-ends when a request dies with a
        CoreError carrying an invalid ``reason`` (it never reached
        execution). Unknown models and unknown reasons fold into the
        canonical vocabulary instead of growing label cardinality — a
        fuzzer-supplied model name must not mint a new metric row.
        """
        if reason not in INVALID_REASONS:
            reason = INVALID_REASON_MALFORMED
        if trace is not None:
            trace.set_attribute("invalid.reason", reason)
        with self._lock:
            stats = self._stats.get(model_name)
            if stats is not None:
                stats.invalid_counts[reason] += 1

    def update_log_settings(self, settings: Optional[dict] = None) -> dict:
        for key, value in (settings or {}).items():
            if key not in self._log_settings:
                raise CoreError(f"Unknown log setting: '{key}'", STATUS_INVALID)
            if value is not None:
                self._log_settings[key] = value
        # Apply, not just store: the settings drive a real structured
        # logger (file sink + level), and the verbose flag gates the
        # per-request log line on the infer path.
        configure_logging(self._log_settings)
        try:
            self._log_verbose = int(self._log_settings["log_verbose_level"])
        except (TypeError, ValueError):
            self._log_verbose = 0
        return dict(self._log_settings)

    def get_log_settings(self) -> dict:
        return dict(self._log_settings)

    # -- shared memory admin -------------------------------------------------

    def shm_registry(self, kind: str):
        if kind == "system":
            return self.system_shm
        if kind == "tpu":
            return self.tpu_shm
        raise CoreError(f"Unsupported shared memory kind: '{kind}'", STATUS_INVALID)

    def find_shm_kind(self, region: str) -> str:
        """Which registry holds a region name (system first, then tpu).

        Hot path (runs per shm-routed tensor): lock-free membership checks.
        """
        if region in self.system_shm:
            return "system"
        if region in self.tpu_shm:
            return "tpu"
        return "system"

    # -- inference -----------------------------------------------------------

    @staticmethod
    def _effective_max_batch(model) -> int:
        """The batch-dimension contract currently in force for `model`:
        a live config override wins over the declared class attribute."""
        override = getattr(model, "_config_override", None) or {}
        return int(override.get("max_batch_size",
                                getattr(model, "max_batch_size", 0)))

    def _stamp_headroom(self, model, request: CoreRequest, stats):
        """Observation-only headroom check at admission.

        Asks the model to cost the request from its input SHAPES (no data
        is resolved) and compares against the memscope headroom gauge for
        the model's KV pool. Admitted requests whose estimate exceeds the
        headroom are stamped ``would_exceed_headroom`` on their trace and
        counted in nv_inference_headroom_near_miss_total — this PR ships
        the signal, not an admission policy.
        """
        if not _memscope.enabled():
            return
        try:
            estimate = model.estimate_request_bytes(
                {t.name: list(t.shape) for t in request.inputs}
            )
        except Exception:  # a cost model must never fail a request
            return
        if estimate is None:
            return
        headroom = _memscope.headroom(model.name)
        if headroom is None:
            return
        trace = request.trace
        if trace is not None:
            trace.set_attribute("mem.estimated_bytes", int(estimate))
        if estimate > headroom:
            if trace is not None:
                trace.set_attribute("would_exceed_headroom", True)
            with self._lock:
                stats.headroom_near_miss += 1

    def infer(
        self, request: CoreRequest
    ) -> Union[CoreResponse, Iterator[CoreResponse]]:
        model = self._get_model(request.model_name, request.model_version)
        with self._lock:
            stats = self._stats[request.model_name]
            batcher = self._batchers.get(request.model_name)
            stats.pending += 1
        self._stamp_headroom(model, request, stats)
        if self._log_verbose >= 1:
            self._log.debug(
                "infer model=%s version=%s id=%s inputs=%d",
                request.model_name, request.model_version or "latest",
                request.id, len(request.inputs),
            )
        try:
            # dynamic_batching re-checked on the CURRENT model: a file-override
            # load shadows the opted-in model under the same name, and the
            # effective cap follows live config overrides.
            if batcher is not None and getattr(model, "dynamic_batching", False):
                cap = self._effective_max_batch(model)
                if batcher.eligible(request, cap):
                    return batcher.infer(model, request, stats, cap)
            return self._infer_one(model, request, stats)
        finally:
            with self._lock:
                stats.pending -= 1

    def infer_submit(self, request: CoreRequest):
        """Two-phase inference for pipelined transports.

        Returns a finalize callable (blocks until the response is ready,
        then returns it / raises the request's CoreError) when the
        request rides the dynamic batcher, or None when it does not —
        callers fall back to the synchronous path. The submit half never
        blocks, so a stream feeder can pipeline submissions at arrival
        rate while a response thread finalizes in stream order.
        """
        model = self._get_model(request.model_name, request.model_version)
        with self._lock:
            stats = self._stats[request.model_name]
            batcher = self._batchers.get(request.model_name)
        if batcher is not None and getattr(model, "dynamic_batching", False):
            cap = self._effective_max_batch(model)
            if batcher.eligible(request, cap):
                # Fallback (return None) re-enters infer(), which stamps —
                # so stamp only the path that terminates here.
                self._stamp_headroom(model, request, stats)
                slot = batcher.submit(model, request, stats, cap)
                with self._lock:
                    stats.pending += 1
                retired = [False]

                def finalize():
                    try:
                        return batcher.wait(slot, model)
                    finally:
                        # finalize may run twice (ordering barrier + stream
                        # yielder); the gauge must decrement exactly once.
                        with self._lock:
                            if not retired[0]:
                                retired[0] = True
                                stats.pending -= 1

                return finalize
        return None

    def _infer_one(self, model, request: CoreRequest, stats) -> CoreResponse:
        t_start = time.monotonic_ns()
        trace = request.trace
        if trace is not None:
            # Direct (unbatched) path: zero-length queue span. record() is
            # first-write-wins, so a batcher-stamped QUEUE_START survives.
            trace.record("QUEUE_START", t_start)
            trace.record("COMPUTE_INPUT", t_start)

        # Resolve inputs (shm reads / typed views happen here).
        inputs: Dict[str, np.ndarray] = {}
        for tensor in request.inputs:
            inputs[tensor.name] = self._resolve_input(tensor)
        t_input = time.monotonic_ns()
        self._validate_inputs(model, inputs)
        if trace is not None:
            trace.record("COMPUTE_INFER", t_input)
            if trace.wants_tensors:
                trace.set_tensors([
                    {"name": t.name, "datatype": t.datatype,
                     "shape": list(t.shape)}
                    for t in request.inputs
                ])

        params = dict(request.parameters)
        if getattr(model, "accepts_cancel_event", False):
            # Engine-backed models poll the event between decode steps so
            # a departed client's generation frees its slot mid-stream;
            # the request's timeline rides beside it so stepscope can put
            # the receipt stamps on the engine's own record of the
            # request. Injected into the COPY only, and only for models
            # that opt in — request.parameters stays wire-shaped.
            if request.cancel_event is not None:
                params[PARAM_CANCEL_EVENT] = request.cancel_event
            if trace is not None:
                params[PARAM_TRACE_TIMESTAMPS] = trace.timestamps
        try:
            result = model.infer(inputs, params)
        except CoreError:
            self._record_failure(stats, t_start)
            raise
        except Exception as e:  # surface model errors as protocol errors
            self._record_failure(stats, t_start)
            raise CoreError(f"inference failed for model '{model.name}': {e}", 500)
        t_infer = time.monotonic_ns()
        if trace is not None:
            trace.record("COMPUTE_OUTPUT", t_infer)

        if model.decoupled and not isinstance(result, dict):
            return self._decoupled_responses(model, request, result, stats, t_start)

        if not isinstance(result, dict):
            result = dict(result)
        response = self._build_response(model, request, result)
        t_end = time.monotonic_ns()
        with self._lock:
            stats.inference_count += 1
            stats.execution_count += 1
            stats.last_inference = int(time.time() * 1000)
            stats.success_count += 1
            stats.success_ns += t_end - t_start
            stats.compute_input_ns += t_input - t_start
            stats.compute_infer_ns += t_infer - t_input
            stats.compute_output_ns += t_end - t_infer
            stats.observe_duration(t_end - t_start)
            stats.observe_stages(
                t_input - t_start, t_infer - t_input, t_end - t_infer
            )
        return response

    def _record_failure(self, stats, t_start):
        duration = time.monotonic_ns() - t_start
        with self._lock:
            stats.fail_count += 1
            stats.fail_ns += duration
            stats.observe_duration(duration)
        if self._log_settings.get("log_error", True) and (
            self._log_settings.get("log_file") or self._log_verbose >= 1
        ):
            # Gated on an active sink: an unconfigured logger would spray
            # every expected-failure test through logging.lastResort.
            self._log.error("inference request failed after %d ns", duration)

    def _validate_inputs(self, model, inputs: Dict[str, np.ndarray]):
        """Declared-input checks shared by the single and batched paths."""
        declared = {spec.name: spec for spec in model.inputs}
        for spec in model.inputs:
            if not spec.optional and spec.name not in inputs:
                raise CoreError(
                    f"expected {len(model.inputs)} inputs but got "
                    f"{len(inputs)} inputs for model '{model.name}'",
                    STATUS_INVALID,
                )
        for name in inputs:
            if declared and name not in declared:
                raise CoreError(
                    f"unexpected inference input '{name}' for model "
                    f"'{model.name}'",
                    STATUS_INVALID,
                )

    def _infer_batch(self, model, requests: List[CoreRequest], stats):
        """Execute a dynamic batch: one device dispatch for N requests.

        Inputs resolve host-preferring (a region's staged mirror bytes stay
        on the host; a parked device array stays on device), concatenate on
        the batch axis, run once, and split back per request. Returns one
        entry per request: a CoreResponse, or a CoreError for requests that
        individually failed resolution/response-building (a bad request
        must not poison its batchmates; only model-execution errors are
        shared). Triton stats semantics: one execution, N inferences.
        """
        if len(requests) == 1:
            try:
                return [self._infer_one(model, requests[0], stats)]
            except CoreError as e:
                return [e]
        t_start = time.monotonic_ns()
        results: List[object] = [None] * len(requests)
        resolved = []
        live = []  # indices still in the batch
        for i, request in enumerate(requests):
            try:
                inputs = {}
                for tensor in request.inputs:
                    inputs[tensor.name] = self._resolve_input(
                        tensor, prefer_host=True
                    )
                self._validate_inputs(model, inputs)
            except CoreError as e:
                results[i] = e
                self._record_failure(stats, t_start)
                continue
            resolved.append(inputs)
            live.append(i)
        if not resolved:
            return results
        try:
            names = list(resolved[0])
            sizes = [int(r[names[0]].shape[0]) for r in resolved]
            total = sum(sizes)
            # Pad the batch axis up to a power-of-two bucket: without it
            # every distinct request mix compiles a fresh XLA executable
            # (a multi-second stall each); with it the ladder is O(log)
            # shapes. Padded rows replicate row 0 and their outputs are
            # discarded below — rows are independent along the batch axis,
            # which is what dynamic_batching=True asserts.
            bucket = 1 << (total - 1).bit_length()
            pad = bucket - total
            cat = {}
            for name in names:
                parts = [r[name] for r in resolved]
                if all(isinstance(p, np.ndarray) for p in parts):
                    if pad:
                        parts = parts + [
                            np.broadcast_to(
                                parts[0][:1], (pad,) + parts[0].shape[1:]
                            )
                        ]
                    cat[name] = np.concatenate(parts, axis=0)
                else:
                    import jax.numpy as jnp

                    if pad:
                        parts = parts + [
                            jnp.broadcast_to(
                                parts[0][:1], (pad,) + tuple(parts[0].shape[1:])
                            )
                        ]
                    cat[name] = jnp.concatenate(parts, axis=0)
            t_input = time.monotonic_ns()
            # stepscope: the batcher's compute phase is one "step" — the
            # whole-batch dispatch. batch_size is the concatenated row
            # count (padding included: that is what the device runs).
            scope = _stepscope.step_begin(
                model.name, _stepscope.PHASE_COMPUTE,
                stats.execution_count,  # tpulint: disable=TPU002 - informational index; worst race is a reused index
                batch_size=bucket, slots=len(live),
            )
            result = model.infer(cat, {})
            _stepscope.step_dispatched(scope)
            if not isinstance(result, dict):
                result = dict(result)
            for name, array in result.items():
                if array.shape[0] != bucket:
                    raise CoreError(
                        f"dynamic batch output '{name}' has batch dim "
                        f"{array.shape[0]}, expected {bucket} for model "
                        f"'{model.name}'",
                        500,
                    )
            t_infer = time.monotonic_ns()
            # Device outputs: ONE warm d2h for the whole batch, and park
            # per-member row VIEWS of the shared base array. The first
            # member's readback materializes the base (jax caches the host
            # copy); every other member slices the cached numpy — k
            # transfers become one, which is the dominant serving-CPU term
            # on latency-bound links (a readback op costs ~0.8 ms host CPU
            # regardless of size).
            from tritonclient_tpu.utils.tpu_shared_memory import (
                BatchRowView,
                SharedBatch,
            )

            # Readback topology for device outputs, per link regime:
            # shared (default) parks one BatchRowView per member over ONE
            # base transfer — k readback ops become 1, the win when the
            # serving host's CPU is the bottleneck. sliced parks an
            # independent device slice per member — k smaller transfers
            # that can run IN PARALLEL, the win when transfer latency
            # and not host CPU is the bottleneck.
            shared_view = os.environ.get(
                "TPU_SERVER_BATCH_ROWVIEW", "1") == "1"
            bases = {}
            for name, array in result.items():
                if hasattr(array, "copy_to_host_async"):
                    if shared_view:
                        array.copy_to_host_async()
                        # One SharedBatch per output, shared by every
                        # member's view: the first reader materializes the
                        # host copy and the padded device batch is released
                        # (not pinned until every region offset is
                        # overwritten — ADVICE r4).
                        bases[name] = SharedBatch(array)
                    else:
                        bases[name] = array
            ok = 0
            start = 0
            for idx, n in zip(live, sizes):
                sliced = {}
                for k, v in result.items():
                    if k not in bases:
                        sliced[k] = v[start : start + n]
                    elif shared_view:
                        sliced[k] = BatchRowView(bases[k], start, start + n)
                    else:
                        member = bases[k][start : start + n]
                        try:
                            member.copy_to_host_async()
                        except AttributeError:
                            pass
                        sliced[k] = member
                start += n
                try:
                    results[idx] = self._build_response(
                        model, requests[idx], sliced
                    )
                    ok += 1
                except CoreError as e:  # e.g. this request's region too small
                    results[idx] = e
                    self._record_failure(stats, t_start)
            t_end = time.monotonic_ns()
            _stepscope.step_end(scope, outputs=result)
            for idx in live:
                trace = requests[idx].trace
                if trace is not None:
                    # Shared batch timeline: every member's compute spans
                    # are the batch's (Triton reports batched requests the
                    # same way); QUEUE_START was stamped at slot enqueue.
                    trace.record("COMPUTE_INPUT", t_start)
                    trace.record("COMPUTE_INFER", t_input)
                    trace.record("COMPUTE_OUTPUT", t_infer)
        except CoreError:
            _stepscope.step_abandon()
            duration = time.monotonic_ns() - t_start
            with self._lock:
                stats.fail_count += len(live)
                stats.fail_ns += duration * len(live)
                for _ in live:
                    stats.observe_duration(duration)
            raise
        except Exception as e:
            _stepscope.step_abandon()
            duration = time.monotonic_ns() - t_start
            with self._lock:
                stats.fail_count += len(live)
                stats.fail_ns += duration * len(live)
                for _ in live:
                    stats.observe_duration(duration)
            raise CoreError(
                f"inference failed for model '{model.name}': {e}", 500
            )
        with self._lock:
            stats.inference_count += ok
            stats.execution_count += 1  # Triton: one batched execution
            stats.last_inference = int(time.time() * 1000)
            stats.success_count += ok
            stats.success_ns += (t_end - t_start) * ok
            stats.compute_input_ns += (t_input - t_start) * ok
            stats.compute_infer_ns += (t_infer - t_input) * ok
            stats.compute_output_ns += (t_end - t_infer) * ok
            for _ in range(ok):
                stats.observe_duration(t_end - t_start)
            stats.observe_stages(
                t_input - t_start, t_infer - t_input, t_end - t_infer, ok
            )
        return results

    def _decoupled_responses(self, model, request, result_iter, stats, t_start):
        def gen():
            count = 0
            try:
                for result in result_iter:
                    count += 1
                    yield self._build_response(model, request, result)
            except CoreError:
                self._record_failure(stats, t_start)
                raise
            except GeneratorExit:
                # Consumer abandoned the stream (cancel / disconnect):
                # record a terminal cancel stat — duration up to the
                # cancellation, responses generated so far — instead of
                # silently omitting the request (ADVICE r4). Triton's
                # inference_stats carries the same "cancel" bucket.
                trace = request.trace
                if trace is not None:
                    # Cancel finalization stamps WHERE the generation died:
                    # engines mirror delivered-step counts onto the cancel
                    # event; the yielded-response count is the fallback.
                    trace.set_attribute("shed.reason", SHED_REASON_CANCELLED)
                    steps = getattr(
                        request.cancel_event, "steps_completed", None)
                    trace.set_attribute(
                        "steps_completed",
                        count if steps is None else int(steps),
                    )
                    # Pages held at death (mirrored by gpt_engine._reserve)
                    # so tail_report's shed rows carry a memory column.
                    trace.set_attribute("kv_pages_held", int(getattr(
                        request.cancel_event, "kv_pages_held", 0) or 0))
                    trace.set_attribute("kv_bytes_held", int(getattr(
                        request.cancel_event, "kv_bytes_held", 0) or 0))
                with self._lock:
                    stats.inference_count += 1
                    stats.execution_count += count
                    stats.cancel_count += 1
                    stats.cancel_ns += time.monotonic_ns() - t_start
                raise
            except Exception as e:
                # Mirror _infer_one's wrapping for errors raised during
                # lazy generation (e.g. a deferred engine admission): the
                # unary handler sees a CoreError, not a raw exception, and
                # the failure is recorded.
                self._record_failure(stats, t_start)
                raise CoreError(
                    f"inference failed for model '{model.name}': {e}", 500
                )
            t_end = time.monotonic_ns()
            with self._lock:
                stats.inference_count += 1
                stats.execution_count += count
                stats.last_inference = int(time.time() * 1000)
                stats.success_count += 1
                stats.success_ns += t_end - t_start
                stats.observe_duration(t_end - t_start)

        return gen()

    def _resolve_input(
        self, tensor: CoreTensor, prefer_host: bool = False
    ) -> np.ndarray:
        if tensor.shm_region is not None:
            registry = self.shm_registry(tensor.shm_kind or "system")
            if tensor.shm_kind == "tpu" and tensor.datatype != "BYTES":
                # Default: zero-copy typed view (parked device array, or
                # mirror bytes uploaded once and parked for repeat
                # consumers). prefer_host (the dynamic batcher): mirror-
                # staged bytes stay host-side so the whole batch pays ONE
                # upload after concatenation; parked arrays still return
                # as-is.
                return registry.read_array(
                    tensor.shm_region, tensor.datatype, tensor.shape,
                    tensor.shm_offset, prefer_host=prefer_host,
                )
            raw = registry.read(
                tensor.shm_region, tensor.shm_offset, tensor.shm_byte_size
            )
            return self._decode_raw(tensor.datatype, tensor.shape, raw)
        if tensor.data is None:
            raise CoreError(f"no data provided for input '{tensor.name}'", STATUS_INVALID)
        return tensor.data

    @staticmethod
    def _decode_raw(datatype: str, shape: List[int], raw: bytes) -> np.ndarray:
        # Boundary validation (protocol/_validate): dtype membership and
        # the payload-length/shape cross-check run BEFORE the reshape, so
        # a wire-supplied shape can never size the array — both planes
        # decode through here and share one message vocabulary.
        try:
            if datatype == "BYTES":
                try:
                    arr = deserialize_bytes_tensor(raw)
                except InferenceServerException as e:
                    # Truncated or lying length prefixes inside the frame
                    # are the client's fault, not a server error.
                    raise ValidationError(
                        str(e), reason=INVALID_REASON_DATA_MISMATCH)
                validate_data_length(datatype, shape, arr.size)
                return arr.reshape(shape)
            validate_dtype(datatype)
            validate_data_length(datatype, shape, len(raw))
        except ValidationError as e:
            raise invalid_to_core_error(e)
        return np.frombuffer(raw, dtype=triton_to_np_dtype(datatype)).reshape(
            shape
        )

    def _build_response(self, model, request: CoreRequest, result: dict) -> CoreResponse:
        requested = {r.name: r for r in request.outputs}
        out_specs = {spec.name: spec for spec in model.outputs}
        names = list(requested) if requested else list(result)
        outputs = []
        for name in names:
            if name not in result:
                raise CoreError(
                    f"unexpected inference output '{name}' for model '{model.name}'",
                    STATUS_INVALID,
                )
            array = result[name]
            req = requested.get(name)
            spec = out_specs.get(name)
            datatype = spec.datatype if spec is not None else None

            if req is not None and req.class_count > 0:
                array, datatype = self._classify(array, req.class_count, model.labels)
            else:
                array = np.asarray(array) if not hasattr(array, "dtype") else array
                if datatype is None or datatype == "BYTES":
                    from tritonclient_tpu.utils import np_to_triton_dtype

                    # .dtype is metadata — np.asarray here would force a
                    # device->host transfer for jax outputs.
                    datatype = np_to_triton_dtype(np.dtype(array.dtype))

            # shape/nbytes come from the array's metadata — np.asarray on a
            # jax.Array would force a device->host transfer per response.
            shape = list(array.shape)
            if req is not None and req.shm_region is not None:
                registry = self.shm_registry(req.shm_kind or "system")
                if req.shm_kind == "tpu" and datatype != "BYTES":
                    registry.write_array(req.shm_region, array, req.shm_offset)
                    # jax.Array.nbytes is a ~35us Python property (np.prod
                    # over the shape); this runs per request.
                    nbytes = math.prod(array.shape) * array.dtype.itemsize
                else:
                    raw = self._encode_raw(datatype, np.asarray(array))
                    nbytes = len(raw)
                    if req.shm_byte_size and nbytes > req.shm_byte_size:
                        raise CoreError(
                            f"shared memory region '{req.shm_region}' is too small "
                            f"for output '{name}' ({nbytes} > {req.shm_byte_size})",
                            STATUS_INVALID,
                        )
                    registry.write(req.shm_region, req.shm_offset, raw)
                outputs.append(
                    CoreOutput(
                        name=name,
                        datatype=datatype,
                        shape=shape,
                        data=None,
                        shm_kind=req.shm_kind,
                        shm_region=req.shm_region,
                        shm_offset=req.shm_offset,
                        shm_byte_size=nbytes,
                    )
                )
            else:
                outputs.append(
                    CoreOutput(
                        name=name,
                        datatype=datatype,
                        shape=shape,
                        data=np.asarray(array),
                    )
                )
        return CoreResponse(
            model_name=model.name,
            model_version=model.version,
            id=request.id,
            outputs=outputs,
        )

    @staticmethod
    def _encode_raw(datatype: str, array: np.ndarray) -> bytes:
        if datatype == "BYTES":
            return serialize_byte_tensor(array)[0]
        np_dtype = triton_to_np_dtype(datatype)
        return np.ascontiguousarray(array.astype(np_dtype, copy=False)).tobytes()

    @staticmethod
    def _classify(array, class_count: int, labels) -> tuple:
        """Classification extension: top-k as BYTES "value:index[:label]".

        Matches the Triton classification output format the reference's
        image_client.py postprocesses (image_client.py:60-217).
        """
        array = np.asarray(array)
        if array.dtype.kind not in "iuf":
            raise CoreError(
                "classification requested on a non-numeric output "
                f"(dtype kind '{array.dtype.kind}'); top-k ranking is "
                "only defined for numeric tensors",
                STATUS_INVALID,
                reason=INVALID_REASON_DATA_MISMATCH,
            )
        if array.ndim == 1:
            array = array[None, :]
        lead_shape = array.shape[:-1]
        flat = array.reshape(-1, array.shape[-1])
        k = min(class_count, flat.shape[1])
        rows = []
        for row in flat:
            top = np.argsort(-row)[:k]
            for idx in top:
                entry = f"{row[idx]:f}:{idx}"
                if labels and idx < len(labels):
                    entry += f":{labels[idx]}"
                rows.append(entry.encode())
        out = np.array(rows, dtype=np.object_).reshape(*lead_shape, k)
        return out, "BYTES"
