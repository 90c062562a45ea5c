"""TPU shared memory: the zero-copy tensor plane (the BASELINE north star).

Mirrors the reference's ``tritonclient.utils.cuda_shared_memory`` API
one-for-one (create/get_raw_handle/set_shared_memory_region[_from_dlpack]/
get_contents_as_numpy/as_shared_memory_tensor/destroy —
cuda_shared_memory/__init__.py:107-429) with XLA PjRt device buffers in
place of cudaMalloc/cudaIpc:

  * a region is a named, sized reservation on one TPU device;
  * tensors "in" the region are parked jax.Arrays on that device — setting
    from DLPack ingests any producer's capsule without host staging;
  * the raw handle is a process-scoped token (cudaIpc has no cross-process
    analog in PjRt — SURVEY.md §7 hard part 1): a co-located server
    (same process / same PjRt client) resolves it via the module-global
    registry and reads/writes jax.Arrays zero-copy; a remote server
    rejects it with a clear error.
  * stream ordering: every set_* blocks until the transfer is committed
    (the JAX analog of the reference's per-device CUDA stream sync,
    cuda_shared_memory/__init__.py:62-70 — SURVEY.md §7 hard part 3).

A host byte-mirror backs the raw read/write paths (BYTES tensors, partial
offsets); parked device arrays always take precedence over the mirror for
the ranges they cover.
"""

import base64
import json
import math
import os
import threading
import time
import uuid as _uuid_mod
from typing import Dict, List, Optional, Sequence

import numpy as np

from tritonclient_tpu import sanitize
from tritonclient_tpu.utils import np_to_triton_dtype, triton_to_np_dtype


class TpuSharedMemoryException(Exception):
    pass


_registry: Dict[str, "TpuSharedMemoryRegion"] = {}
# Named for the tpusan lock-order witness (plain lock when inactive).
_registry_lock = sanitize.named_lock("tpu_shared_memory:_registry_lock")


def _jax():
    import jax

    return jax


# -- sharded upload pool ----------------------------------------------------- #
# Mesh-sharded regions upload one slice per addressable device instead of
# staging the whole buffer through one jax.device_put; the bounded pool
# lets slice transfers proceed concurrently, so a region set scales with
# the slowest slice rather than the sum. Sized by TPU_SHM_UPLOAD_WORKERS
# (default: cpu count, capped) — on a single-core host the pool degrades
# to the sequential per-slice loop, which still beats the staged path
# (no full-buffer relayout on the host side).

_upload_pool = None
_upload_pool_lock = sanitize.named_lock("tpu_shared_memory:_upload_pool_lock")


def _upload_workers() -> int:
    raw = os.environ.get("TPU_SHM_UPLOAD_WORKERS", "").strip()
    if raw:
        try:
            return max(int(raw), 1)
        except ValueError:
            pass
    return max(min(os.cpu_count() or 1, 8), 1)


def _get_upload_pool(workers: int):
    global _upload_pool
    with _upload_pool_lock:
        if _upload_pool is None:
            from concurrent.futures import ThreadPoolExecutor

            _upload_pool = ThreadPoolExecutor(
                max_workers=workers, thread_name_prefix="tpu-shm-upload"
            )
        return _upload_pool


def _parallel_upload_enabled() -> bool:
    raw = os.environ.get("TPU_SHM_PARALLEL_UPLOAD", "").strip().lower()
    return raw not in ("0", "off", "false", "no")


def _np_dtype_for(datatype: str) -> np.dtype:
    if datatype == "BF16":
        import jax.numpy as jnp

        return np.dtype(jnp.bfloat16)
    np_dtype = triton_to_np_dtype(datatype)
    if np_dtype is None:
        raise TpuSharedMemoryException(f"unsupported datatype '{datatype}'")
    return np.dtype(np_dtype)


def _triton_dtype_for(arr) -> str:
    import jax.numpy as jnp

    if arr.dtype == jnp.bfloat16:
        return "BF16"
    return np_to_triton_dtype(np.dtype(arr.dtype))


def _nbytes(arr) -> int:
    """Byte size from shape/dtype metadata.

    jax.Array.nbytes is a Python property that np.prod's the shape
    (~35us); this runs at request rate on the region hot paths, so
    compute it with math.prod instead (<1us). Works for numpy too.
    """
    return math.prod(arr.shape) * arr.dtype.itemsize


class SharedBatch:
    """Device base + one-shot host materialization shared by all row views
    of one dynamically batched result.

    Once the host copy lands, the device reference is DROPPED: each of the
    k member regions previously pinned the entire pow2-padded batch array
    in device memory (k x bucket rows) until every region offset was
    overwritten, which grows parked HBM ~k-fold for long-lived output
    regions (ADVICE r4). The shared lock also stops concurrent
    first-readers racing the materialization and paying the transfer
    twice.
    """

    __slots__ = ("array", "host", "lock")

    def __init__(self, array, lock=None):
        self.array = array
        self.host = None
        self.lock = lock if lock is not None else threading.Lock()

    def materialize(self) -> np.ndarray:
        with self.lock:
            if self.host is None:
                self.host = np.asarray(self.array)
                self.array = None  # release the padded device batch
            return self.host


class BatchRowView:
    """A row-slice view over a shared (dynamically batched) device array.

    The server's dynamic batcher executes k requests as ONE device array;
    parking per-member *views* instead of per-member device slices means
    the whole batch is read back with a single device->host transfer (the
    first reader materializes the base array into the shared
    ``SharedBatch`` host cache and every other member slices that numpy).
    On latency-bound links a readback op costs ~0.8 ms host CPU
    regardless of size, so this turns k transfers into one: the dominant
    serving-CPU term at high concurrency (VERDICT r4 #3).

    ``base`` is normally a ``SharedBatch`` shared by all batchmates; a
    raw array is wrapped in a private one (with ``lock`` if given).
    """

    __slots__ = ("_sb", "start", "stop", "_shape", "_tail", "_dtype")

    # SharedBatch.array/host have one benign transition (array->None
    # after host publishes, both under the lock in materialize);
    # lock-free readers seeing the old array still read valid device
    # data, readers seeing None take the locked host path.
    # tpulint: disable=TPU009 - benign array->None publication
    def __init__(self, base, start: int, stop: int, lock=None, shape=None):
        self._sb = (
            base if isinstance(base, SharedBatch) else SharedBatch(base, lock)
        )
        self.start = int(start)
        self.stop = int(stop)
        # Explicit shape: the transfer coalescer bundles arbitrary same-
        # dtype outputs as ONE flat base; each member view then reshapes
        # its element range back to the original output shape.
        self._shape = tuple(int(s) for s in shape) if shape is not None else None
        src = self._sb.array if self._sb.array is not None else self._sb.host
        self._tail = tuple(src.shape[1:])
        self._dtype = src.dtype

    @property
    def shape(self):
        if self._shape is not None:
            return self._shape
        return (self.stop - self.start,) + self._tail

    @property
    def dtype(self):
        return self._dtype

    def materialize(self) -> np.ndarray:
        """Host view of this member's rows; base transferred once."""
        host = self._sb.materialize()
        out = host[self.start : self.stop]
        if self._shape is not None:
            out = out.reshape(self._shape)
        return out

    def __array__(self, dtype=None, copy=None):
        out = self.materialize()
        if dtype is not None:
            out = out.astype(dtype, copy=False)
        return out

    # Same benign array->None publication as __init__; a stale device
    # base is still valid, None falls back to the locked materialize.
    # tpulint: disable=TPU009 - benign array->None publication
    def device_slice(self):
        """Lazy device-side slice for device consumers (no host hop).

        After the base has been released (host copy landed) this returns
        the cached host slice instead — callers that require device
        residency re-upload it themselves.
        """
        base = self._sb.array
        if base is None:
            return self.materialize()
        out = base[self.start : self.stop]
        if self._shape is not None:
            out = out.reshape(self._shape)
        return out

    # Advisory warm-copy hint; racing the array->None release just
    # skips a prefetch that is no longer needed.
    # tpulint: disable=TPU009 - benign array->None publication
    def copy_to_host_async(self):
        try:
            base = self._sb.array
            if base is not None:
                base.copy_to_host_async()
        except AttributeError:
            pass


def _parked_host(arr) -> np.ndarray:
    """Host bytes of a parked entry (array or BatchRowView)."""
    if isinstance(arr, BatchRowView):
        return arr.materialize()
    return np.asarray(arr)


class TransferCoalescer:
    """Bundles freshly-parked output arrays into one device->host transfer.

    Where a device-to-host read has a fixed host cost regardless of size
    (~0.8 ms was measured on a machine whose device sat behind a network
    hop; not measured on a local chip), a server answering N concurrent
    requests pays that per response. This coalescer sits behind the server's output-park
    path: each parked output is registered here; within ``max_wait`` (or
    once ``max_bundle`` accumulate) same-dtype/shape outputs are raveled
    and concatenated into ONE flat device array by a single jitted concat,
    the bundle's d2h is warmed once, and every member's region entry is
    atomically replaced by a ``BatchRowView`` over the bundle. Readers
    then share one transfer (the first materializes; jax caches the host
    copy).

    Unlike the dynamic batcher this never delays dispatch or responses —
    requests execute and answer individually; only the *transfer* is
    bundled, after the fact. Singles just get their warm copy started.
    """

    def __init__(self, max_bundle: int = 8, max_wait_s: float = 0.002):
        self.max_bundle = int(max_bundle)
        self.max_wait_s = float(max_wait_s)
        self._cv = threading.Condition()
        self._pending: List[tuple] = []
        self._thread: Optional[threading.Thread] = None
        self._stop = False
        self._bundle_fn = None
        # Effectiveness counters (observability; read by perf probes).
        self.stats = {
            "bundles": 0, "bundled_members": 0, "singles": 0,
            "cas_ok": 0, "cas_miss": 0, "overflow": 0, "errors": 0,
        }

    def stats_snapshot(self) -> dict:
        """Copy of the effectiveness counters taken under the worker cv
        (TPU009: the flush thread mutates them under the same cv)."""
        with self._cv:
            return dict(self.stats)

    def submit(self, region: "TpuSharedMemoryRegion", offset: int, arr):
        with self._cv:
            if self._thread is None or not self._thread.is_alive():
                # is_alive covers a daemon killed by an escaped error:
                # coalescing must degrade, never latch off.
                self._thread = threading.Thread(
                    target=self._run, daemon=True, name="tpu-shm-coalescer"
                )
                self._thread.start()
            if len(self._pending) >= 64:
                # Backpressure (e.g. a first-use XLA compile stalling the
                # flush thread): fall back to the direct warm copy.
                self.stats["overflow"] += 1
                try:
                    arr.copy_to_host_async()
                except AttributeError:
                    pass
                return
            self._pending.append((region, offset, arr, time.monotonic()))
            # Always wake the flush thread: it re-checks age/size and
            # sleeps out the remainder of the bundling window itself.
            self._cv.notify()

    def _run(self):
        while True:
            with self._cv:
                while not self._pending and not self._stop:
                    self._cv.wait(timeout=0.5)
                if self._stop:
                    return
                # Hold the bundle open until it fills or the oldest entry
                # ages out of the window.
                while self._pending and len(self._pending) < self.max_bundle:
                    remaining = self.max_wait_s - (
                        time.monotonic() - self._pending[0][3]
                    )
                    if remaining <= 0:
                        break
                    self._cv.wait(timeout=remaining)
                batch = self._pending[: self.max_bundle]
                del self._pending[: len(batch)]
            if batch:
                try:
                    self._flush(batch)
                except Exception:
                    # The flush thread must survive anything: an escape
                    # here would kill the daemon while self._thread stays
                    # set, permanently disabling coalescing (ADVICE r4).
                    # Readers still get correct data from the originally
                    # parked arrays — just without the warm copy. The
                    # fallback warm copies are themselves guarded: on a
                    # broken runtime they raise the SAME error, which
                    # must not escape either.
                    with self._cv:
                        self.stats["errors"] += 1
                    for item in batch:
                        try:
                            item[2].copy_to_host_async()
                        except Exception:
                            pass

    def _flush(self, batch):
        groups: Dict[tuple, list] = {}
        for item in batch:
            arr = item[2]
            groups.setdefault(
                (str(arr.dtype), tuple(arr.shape)), []
            ).append(item)
        for (_, shp), items in groups.items():
            if len(items) == 1:
                with self._cv:
                    self.stats["singles"] += 1
                try:
                    items[0][2].copy_to_host_async()
                except AttributeError:
                    pass
                continue
            k = len(items)
            kb = 1 << (k - 1).bit_length()  # pow2 arity: O(log) compiles
            arrs = [it[2] for it in items]
            arrs += [arrs[-1]] * (kb - k)
            try:
                bundle = self._bundle(*arrs)
                bundle.copy_to_host_async()
            except Exception:
                # Defensive: bundling is an optimization — on any failure
                # the originals stay parked and get their own warm copies.
                with self._cv:
                    self.stats["errors"] += 1
                for it in items:
                    try:
                        it[2].copy_to_host_async()
                    except AttributeError:
                        pass
                continue
            n = math.prod(shp)
            sb = SharedBatch(bundle)
            cas_ok = cas_miss = 0
            for i, (region, offset, arr, _) in enumerate(items):
                view = BatchRowView(
                    sb, i * n, (i + 1) * n, shape=shp
                )
                if region._replace_parked(offset, arr, view):
                    cas_ok += 1
                else:
                    cas_miss += 1
            with self._cv:
                self.stats["bundles"] += 1
                self.stats["bundled_members"] += k
                self.stats["cas_ok"] += cas_ok
                self.stats["cas_miss"] += cas_miss

    def _bundle(self, *arrs):
        if self._bundle_fn is None:
            import jax
            import jax.numpy as jnp

            self._bundle_fn = jax.jit(
                lambda *xs: jnp.concatenate([x.ravel() for x in xs])
            )
        return self._bundle_fn(*arrs)

    def warm(self, shape, dtype, device_id: int = 0, ks=(2, 4, 8)):
        """Pre-compile the concat ladder for an output shape so no serving
        window pays a first-use XLA compile (multi-second on remote-compile
        links)."""
        import jax
        import jax.numpy as jnp

        dev = _jax().devices()[device_id]
        z = jax.device_put(jnp.zeros(shape, dtype), dev)
        for k in ks:
            if k <= self.max_bundle:
                jax.block_until_ready(self._bundle(*([z] * k)))


_coalescer: Optional[TransferCoalescer] = None


def transfer_coalescer() -> Optional[TransferCoalescer]:
    """Process-wide coalescer, or None when disabled (the default).

    ``TPU_TRANSFER_COALESCE=1`` enables it; ``TPU_TRANSFER_COALESCE_US``
    tunes the bundling window. Off by default: where it was measured
    (rounds <= 5, a device behind a network hop), merging transfers saved
    ~0.6 ms host CPU per bundled response but gave up the overlap of many
    small d2h ops, which netted out slower unless the host was
    CPU-saturated. Not measured on a local chip (ROADMAP C7).
    """
    global _coalescer
    if os.environ.get("TPU_TRANSFER_COALESCE", "0") != "1":
        return None
    if _coalescer is None:
        _coalescer = TransferCoalescer(
            max_wait_s=int(
                os.environ.get("TPU_TRANSFER_COALESCE_US", "2000")
            ) / 1e6
        )
    return _coalescer


class TpuSharedMemoryRegion:
    """One named reservation on a TPU device holding parked jax.Arrays."""

    def __init__(self, triton_shm_name: str, byte_size: int, device_id: int):
        jax = _jax()
        devices = jax.devices()
        if device_id >= len(devices):
            raise TpuSharedMemoryException(
                f"device_id {device_id} out of range ({len(devices)} devices)"
            )
        self.triton_shm_name = triton_shm_name
        self.byte_size = int(byte_size)
        self.device_id = int(device_id)
        self.device = devices[device_id]
        self.uuid = _uuid_mod.uuid4().hex
        self._lock = sanitize.named_lock("TpuSharedMemoryRegion._lock")
        self._parked: Dict[int, object] = {}  # offset -> jax.Array
        self._mirror = bytearray(self.byte_size)
        self._destroyed = False

    # -- internal helpers ----------------------------------------------------

    def _check_range(self, offset: int, nbytes: int):
        if self._destroyed:
            raise TpuSharedMemoryException(
                f"shared memory region '{self.triton_shm_name}' has been destroyed"
            )
        if offset < 0 or offset + nbytes > self.byte_size:
            raise TpuSharedMemoryException(
                f"offset {offset} + byte size {nbytes} exceeds region size "
                f"{self.byte_size} for region '{self.triton_shm_name}'"
            )

    def _drop_overlapping(self, offset, nbytes):  # tpulint: disable=TPU002
        """Evict parked arrays overlapping [offset, offset+nbytes).

        Partially-overlapped arrays are flushed to the byte mirror first so
        their non-overlapped bytes stay readable. The caller holds
        ``self._lock`` (hence the tpulint suppression above).
        """
        for off in list(self._parked):
            arr = self._parked[off]
            an = _nbytes(arr)
            if off < offset + nbytes and offset < off + an:
                if off < offset or off + an > offset + nbytes:
                    self._mirror[off : off + an] = _parked_host(arr).tobytes()
                del self._parked[off]

    # -- typed (zero-copy) plane --------------------------------------------

    def _park_view(self, view: "BatchRowView", offset: int):
        """Park a batched-output view: pure bookkeeping — the base array
        stays shared with its batchmates' regions."""
        an = _nbytes(view)
        self._check_range(offset, an)
        with self._lock:
            self._drop_overlapping(offset, an)
            self._parked[offset] = view

    # tpulint: hot-path
    def set_array(self, array, offset: int = 0, block: bool = True):
        """Park a device array at ``offset`` (the zero-copy set path).

        ``block=True`` (the client-facing default) commits the transfer
        before returning — the JAX analog of the reference's per-device
        stream sync at region-set boundaries. The server's output path
        passes ``block=False``: parking only repoints the region table at
        the (possibly still-computing) result buffer, and readers block
        when they materialize it.
        """
        if isinstance(array, BatchRowView):
            return self._park_view(array, offset)
        jax = _jax()
        if isinstance(array, jax.Array) and array.devices() == {self.device}:
            arr = array  # already resident — parking is pure bookkeeping
        else:
            arr = jax.device_put(array, self.device)
        if block:
            # The designed region-set commit barrier (client default);
            # the server's hot output path passes block=False and never
            # reaches this.
            jax.block_until_ready(arr)  # tpulint: disable=TPU010
        an = _nbytes(arr)
        self._check_range(offset, an)
        with self._lock:
            self._drop_overlapping(offset, an)
            self._parked[offset] = arr

    # tpulint: hot-path
    def as_array(self, datatype: str, shape: Sequence[int], offset: int = 0,
                 prefer_host: bool = False):
        """A jax.Array view of the region contents at ``offset``.

        Zero-copy when a parked array matches dtype/shape; otherwise
        materializes from the byte mirror — on the CALLING thread, which
        for a co-located server means the upload is enqueued back-to-back
        with the compute that consumes it (one enqueuing thread per device
        chain; see set_shared_memory_region). The materialized array is
        parked so repeated consumers pay the upload once.

        ``prefer_host=True``: mirror-staged bytes come back as a host numpy
        array with no upload (a parked device array still returns as-is) —
        for consumers that coalesce uploads themselves, e.g. the server's
        dynamic batcher.
        """
        jax = _jax()
        shape = tuple(int(s) for s in shape)
        np_dtype = _np_dtype_for(datatype)
        nbytes = math.prod(shape) * np_dtype.itemsize
        self._check_range(offset, nbytes)
        released_view = None
        with self._lock:
            parked = self._parked.get(offset)
            if parked is not None and _nbytes(parked) == nbytes:
                if isinstance(parked, BatchRowView):
                    if parked.dtype == np_dtype and parked.shape == shape:
                        # device_slice falls back to host numpy once the
                        # shared base has been released (host copy landed)
                        # — see its docstring; the re-upload for device
                        # readers happens below, OUTSIDE the lock.
                        out = parked.device_slice()
                        if isinstance(out, np.ndarray) and not prefer_host:
                            released_view = parked
                        else:
                            return out
                    # else: reinterpretation gathers through the mirror.
                elif parked.dtype == np_dtype and parked.shape == shape:
                    return parked
                else:
                    return parked.view(np_dtype).reshape(shape)
        if released_view is not None:
            # Base already released to host (SharedBatch): honor the
            # jax.Array contract by re-uploading — WITHOUT holding the
            # region lock across the upload (it would serialize every
            # concurrent reader/writer for its duration — ADVICE
            # r5 #5). Re-park through the CAS so repeat device readers
            # pay the upload once; a racing writer that replaced the
            # entry meanwhile wins and the upload is returned unparked.
            arr = jax.device_put(out, self.device)
            self._replace_parked(offset, released_view, arr)
            return arr
        host = np.frombuffer(
            self.read_bytes(offset, nbytes), dtype=np_dtype
        ).reshape(shape)
        if prefer_host:
            return host
        arr = jax.device_put(host, self.device)
        with self._lock:
            self._drop_overlapping(offset, nbytes)
            self._parked[offset] = arr
        return arr

    def _replace_parked(self, offset: int, old, new, drop_nbytes=None):
        """CAS a parked entry (transfer coalescer: original -> bundle view).

        Only swaps when ``old`` is still the live entry — a racing writer
        or reader-side repark wins and the bundle view is dropped.
        ``drop_nbytes`` additionally evicts entries overlapping
        ``[offset, offset + drop_nbytes)`` on a successful swap — the
        fresh-park variant used when the upload happened outside the lock
        against a possibly-absent prior entry."""
        with self._lock:
            if self._parked.get(offset) is old:
                if drop_nbytes is not None:
                    self._drop_overlapping(offset, drop_nbytes)
                self._parked[offset] = new
                return True
        return False

    def read_typed(self, datatype: str, shape: Sequence[int],
                   offset: int = 0) -> np.ndarray:
        """Host-side typed read: parked device data or mirror bytes.

        Unlike ``as_array`` this never uploads — host readers of
        host-staged data stay entirely on the host.
        """
        shape = tuple(int(s) for s in shape)
        np_dtype = _np_dtype_for(datatype)
        nbytes = math.prod(shape) * np_dtype.itemsize
        self._check_range(offset, nbytes)
        with self._lock:
            parked = self._parked.get(offset)
            keep = parked is not None and _nbytes(parked) == nbytes
        if keep:
            host = np.asarray(parked)
            if host.dtype != np_dtype or host.shape != shape:
                host = host.view(np_dtype).reshape(shape)
            return host
        return np.frombuffer(
            self.read_bytes(offset, nbytes), dtype=np_dtype
        ).reshape(shape)

    # -- raw byte plane ------------------------------------------------------

    def write_bytes(self, offset: int, data: bytes):
        self._check_range(offset, len(data))
        with self._lock:
            self._drop_overlapping(offset, len(data))
            self._mirror[offset : offset + len(data)] = data

    def write_host_array(self, arr: np.ndarray, offset: int):
        """Mirror write straight from a C-contiguous array's buffer.

        Same semantics as ``write_bytes(offset, arr.tobytes())`` without the
        intermediate bytes allocation — this is the per-request host->mirror
        hop of the staged set path, so it runs at request rate.
        """
        nbytes = arr.nbytes
        self._check_range(offset, nbytes)
        try:
            view = memoryview(arr).cast("B")
        except (ValueError, TypeError):
            # Extension dtypes (ml_dtypes bfloat16 etc.) refuse the buffer
            # protocol; reinterpret the same memory as raw bytes instead.
            view = memoryview(arr.view(np.uint8).reshape(-1))
        with self._lock:
            self._drop_overlapping(offset, nbytes)
            self._mirror[offset : offset + nbytes] = view

    def read_bytes(self, offset: int, nbytes: int) -> bytes:
        self._check_range(offset, nbytes)
        with self._lock:
            parked = sorted(self._parked.items())
            # Flush parked ranges overlapping the request into the mirror
            # (device -> host copy only when a raw-byte reader asks).
            for off, arr in parked:
                an = _nbytes(arr)
                if off < offset + nbytes and offset < off + an:
                    self._mirror[off : off + an] = np.asarray(arr).tobytes()
            return bytes(self._mirror[offset : offset + nbytes])

    def __repr__(self):
        return (
            f"TpuSharedMemoryRegion(name={self.triton_shm_name!r}, "
            f"byte_size={self.byte_size}, device={self.device})"
        )


class TpuShardedMemoryRegion(TpuSharedMemoryRegion):
    """A region spanning every device of a ``jax.sharding.Mesh``.

    The §5.7/§5.8 sequence-length-scaling story (SURVEY.md): where the
    single-device region parks one jax.Array per tensor, this region parks
    *sharded* jax.Arrays laid out by a NamedSharding — one buffer shard per
    mesh device, so a registered input/output region holds tensors whose
    bytes never congregate on a single chip and sequence length scales
    across the slice. The raw handle stays process-scoped; a co-located
    server reads/writes the sharded arrays zero-copy through the same
    registry calls as the single-device plane.

    ``partition_spec`` defaults to sharding dimension 0 across all mesh
    axes (the sequence/batch dimension); arrays parked via ``set_array``
    must be divisible accordingly.
    """

    def __init__(self, triton_shm_name: str, byte_size: int, mesh,
                 partition_spec=None):
        from jax.sharding import NamedSharding, PartitionSpec

        devices = list(mesh.devices.flatten())
        if not devices:
            raise TpuSharedMemoryException("mesh has no devices")
        if partition_spec is None:
            partition_spec = PartitionSpec(tuple(mesh.axis_names))
        self.triton_shm_name = triton_shm_name
        self.byte_size = int(byte_size)
        self.mesh = mesh
        self.sharding = NamedSharding(mesh, partition_spec)
        self.devices = devices
        self.device_ids = [d.id for d in devices]
        # Single-device API compatibility: the region's nominal placement is
        # the first mesh device (status reports, handle tokens).
        self.device = devices[0]
        self.device_id = int(self.device.id)
        self.uuid = _uuid_mod.uuid4().hex
        self._lock = sanitize.named_lock("TpuShardedMemoryRegion._lock")
        self._parked: Dict[int, object] = {}
        self._mirror = bytearray(self.byte_size)
        self._destroyed = False

    def _sharded_put(self, host: np.ndarray):
        """Upload a host array one per-device slice at a time instead of
        staging the full buffer through a single ``jax.device_put``.

        The sharding's ``addressable_devices_indices_map`` names each
        device's slice of the host array; slices transfer through the
        bounded module pool concurrently (sequentially on a 1-worker
        host — still cheaper than the staged path, which relayouts the
        whole buffer host-side first) and reassemble zero-copy with
        ``make_array_from_single_device_arrays``. Any geometry the slice
        path cannot express (uneven shards, opaque dtypes) falls back to
        the staged upload, which is always correct.
        """
        jax = _jax()
        if not _parallel_upload_enabled():
            return jax.device_put(host, self.sharding)
        try:
            idx_map = self.sharding.addressable_devices_indices_map(
                host.shape
            )
            items = list(idx_map.items())
            if len(items) <= 1:
                return jax.device_put(host, self.sharding)
            workers = min(_upload_workers(), len(items))
            if workers > 1:
                pool = _get_upload_pool(workers)
                futs = [pool.submit(jax.device_put, host[idx], dev)
                        for dev, idx in items]
                shards = [f.result() for f in futs]
            else:
                shards = [jax.device_put(host[idx], dev)
                          for dev, idx in items]
            return jax.make_array_from_single_device_arrays(
                host.shape, self.sharding, shards
            )
        except Exception:
            return jax.device_put(host, self.sharding)

    def set_array(self, array, offset: int = 0, block: bool = True):
        """Park an array sharded over the mesh (host or device producer).

        Host producers take the parallel per-slice upload path
        (``_sharded_put``); device producers with a foreign layout go
        through the resharding ``device_put`` (XLA moves device bytes
        directly)."""
        if isinstance(array, BatchRowView):
            return self._park_view(array, offset)
        jax = _jax()
        if isinstance(array, jax.Array) and array.sharding == self.sharding:
            arr = array  # already laid out — parking is pure bookkeeping
        elif isinstance(array, np.ndarray):
            arr = self._sharded_put(array)
        else:
            arr = jax.device_put(array, self.sharding)
        if block:
            jax.block_until_ready(arr)
        an = _nbytes(arr)
        self._check_range(offset, an)
        with self._lock:
            self._drop_overlapping(offset, an)
            self._parked[offset] = arr

    def as_array(self, datatype: str, shape: Sequence[int], offset: int = 0,
                 prefer_host: bool = False):
        """A sharded jax.Array view of the region contents at ``offset``.

        Mirror-staged bytes re-upload per-device via ``_sharded_put``
        OUTSIDE the region lock (the upload is the slow part, and holding
        the lock across it would serialize every concurrent reader/writer
        — same ADVICE r5 #5 discipline as the single-device plane), then
        park through the ``_replace_parked`` CAS: a writer that raced the
        upload wins and the fresh array is returned unparked.
        """
        shape = tuple(int(s) for s in shape)
        np_dtype = _np_dtype_for(datatype)
        nbytes = math.prod(shape) * np_dtype.itemsize
        self._check_range(offset, nbytes)
        with self._lock:
            parked = self._parked.get(offset)
            if parked is not None and _nbytes(parked) == nbytes:
                if parked.dtype == np_dtype and parked.shape == shape:
                    return parked
                # A dtype/shape reinterpretation cannot stay sharded in
                # general; gather through the host mirror below instead.
            stale = parked
        host = np.frombuffer(
            self.read_bytes(offset, nbytes), dtype=np_dtype
        ).reshape(shape)
        if prefer_host:
            return host
        arr = self._sharded_put(host)
        self._replace_parked(offset, stale, arr, drop_nbytes=nbytes)
        return arr

    def __repr__(self):
        return (
            f"TpuShardedMemoryRegion(name={self.triton_shm_name!r}, "
            f"byte_size={self.byte_size}, devices={len(self.devices)}, "
            f"sharding={self.sharding})"
        )


# --------------------------------------------------------------------------- #
# module API (cuda_shared_memory parity)                                      #
# --------------------------------------------------------------------------- #


def create_shared_memory_region(
    triton_shm_name: str, byte_size: int, device_id: int = 0
) -> TpuSharedMemoryRegion:
    region = TpuSharedMemoryRegion(triton_shm_name, byte_size, device_id)
    with _registry_lock:
        _registry[region.uuid] = region
    # Device-buffer bytes on the memory ledger (client scope, shm pool).
    # Keyed by uuid — region NAMES may repeat across re-creates.
    from tritonclient_tpu import _memscope

    _memscope.set_static(
        _memscope.SCOPE_CLIENT, _memscope.MEM_POOL_SHM, "tpu:" + region.uuid,
        int(byte_size), {"name": triton_shm_name, "device_id": int(device_id)},
    )
    return region


def create_sharded_memory_region(
    triton_shm_name: str, byte_size: int, mesh, partition_spec=None
) -> TpuShardedMemoryRegion:
    """A region whose parked tensors are sharded across all mesh devices.

    The multi-device extension of create_shared_memory_region: registered
    through the same register_tpu_shared_memory lifecycle, readable and
    writable by a co-located server with per-device buffers (no single-chip
    staging). See TpuShardedMemoryRegion.
    """
    region = TpuShardedMemoryRegion(
        triton_shm_name, byte_size, mesh, partition_spec
    )
    with _registry_lock:
        _registry[region.uuid] = region
    from tritonclient_tpu import _memscope

    _memscope.set_static(
        _memscope.SCOPE_CLIENT, _memscope.MEM_POOL_SHM, "tpu:" + region.uuid,
        int(byte_size),
        {"name": triton_shm_name, "devices": len(region.devices)},
    )
    return region


def get_raw_handle(shm_handle: TpuSharedMemoryRegion) -> bytes:
    """Serialized handle passed to register_tpu_shared_memory.

    Process-scoped: resolvable only by a server sharing this process's PjRt
    client (the TPU analog of cudaIpc's same-machine scope).
    """
    token = {
        "uuid": shm_handle.uuid,
        "pid": os.getpid(),
        "byte_size": shm_handle.byte_size,
        "device_id": shm_handle.device_id,
    }
    device_ids = getattr(shm_handle, "device_ids", None)
    if device_ids is not None:
        token["device_ids"] = device_ids  # mesh-spanning (sharded) region
    return base64.b64encode(json.dumps(token).encode())


def _resolve_raw_handle(raw_handle) -> Optional[TpuSharedMemoryRegion]:
    """Server-side: raw handle -> live region, or None if not co-located."""
    try:
        if isinstance(raw_handle, str):
            raw_handle = raw_handle.encode()
        token = json.loads(base64.b64decode(raw_handle))
    except (ValueError, TypeError):
        return None
    if token.get("pid") != os.getpid():
        return None
    with _registry_lock:
        return _registry.get(token.get("uuid"))


def set_shared_memory_region(
    shm_handle: TpuSharedMemoryRegion, input_values, offset: int = 0,
    block: bool = True,
):
    """Stage host arrays into the region (upload happens at first consume).

    Host producers write the region's host mirror (a memcpy); the device
    upload is performed by the first device-side consumer (``as_array``),
    which enqueues it back-to-back with whatever it dispatches next. On a
    co-located server this keeps every device op of a request chain
    (upload -> execute -> readback) on ONE enqueuing thread — the ordering
    the device pipeline schedules best — instead of splitting the chain
    between producer and consumer threads. Device-array producers that
    want a true zero-copy park use ``set_shared_memory_region_from_dlpack``
    (no host staging at all).

    ``block`` is accepted for API compatibility with the reference's
    stream-sync-at-set contract (cuda_shared_memory/__init__.py:62-70);
    the mirror write is synchronous either way, so the data is always
    visible to consumers when this returns.
    """
    if not isinstance(input_values, (list, tuple)):
        raise TpuSharedMemoryException(
            "input_values must be a list of arrays"
        )
    from tritonclient_tpu.utils import serialize_byte_tensor

    cursor = offset
    for arr in input_values:
        arr = np.asarray(arr)
        if arr.dtype.type == np.str_:
            arr = np.char.encode(arr, "utf-8")
        if arr.dtype == np.object_ and arr.size == 1 and isinstance(arr.item(), bytes):
            # Pre-serialized buffer (reference semantics: object arrays are
            # .item()-ed, shared_memory/__init__.py:155-157). Genuine
            # single-element BYTES tensors must be serialize_byte_tensor-ed
            # by the caller, as with the reference.
            data = arr.item()
            shm_handle.write_bytes(cursor, data)
            cursor += len(data)
        elif arr.dtype == np.object_ or arr.dtype.type == np.bytes_:
            # BYTES tensors have no device representation; the serialized
            # wire bytes land in the region's host mirror.
            data = serialize_byte_tensor(arr)[0]
            shm_handle.write_bytes(cursor, data)
            cursor += len(data)
        else:
            arr = np.ascontiguousarray(arr)
            shm_handle.write_host_array(arr, cursor)
            cursor += arr.nbytes


def set_shared_memory_region_from_dlpack(
    shm_handle: TpuSharedMemoryRegion, input_values, offset: int = 0
):
    """Ingest DLPack-capable tensors (jax.Array, torch, numpy, ...) without
    host staging when the producer is already on the target device."""
    import jax
    import numpy as _np

    if not isinstance(input_values, (list, tuple)):
        raise TpuSharedMemoryException("input_values must be a list of tensors")
    cursor = offset
    for value in input_values:
        if isinstance(value, jax.Array):
            # Already a device array in this process: park it directly —
            # no capsule round-trip needed (and some PjRt plugins don't
            # export DLPack).
            arr = value
        elif hasattr(value, "__dlpack__"):
            try:
                arr = jax.dlpack.from_dlpack(value)
            except (BufferError, TypeError, RuntimeError):
                arr = _np.from_dlpack(value)
        else:
            arr = _np.asarray(value)
        shm_handle.set_array(arr, cursor)
        cursor += arr.nbytes


def get_contents_as_numpy(
    shm_handle: TpuSharedMemoryRegion,
    datatype,
    shape: Sequence[int],
    offset: int = 0,
) -> np.ndarray:
    """Device -> host readback of the region contents."""
    if not isinstance(datatype, str):
        datatype = np_to_triton_dtype(np.dtype(datatype))
    if datatype == "BYTES":
        # BYTES tensors live in the byte mirror (length-prefixed wire
        # format); there is no typed device view for them.
        from tritonclient_tpu.utils import decode_bytes_elements

        raw = shm_handle.read_bytes(offset, shm_handle.byte_size - offset)
        count = math.prod(shape)
        return decode_bytes_elements(raw, count).reshape(shape)
    out = shm_handle.read_typed(datatype, shape, offset)
    if datatype == "BF16":
        # numpy has no bf16; hand back float32 like the reference's
        # triton_to_np_dtype BF16 shim (utils/__init__.py:184).
        out = out.astype(np.float32)
    return out


def as_shared_memory_tensor(
    shm_handle: TpuSharedMemoryRegion, datatype: str, shape: Sequence[int],
    offset: int = 0
):
    """Zero-copy consumer view: a jax.Array exposing __dlpack__ for
    torch/cupy/np from_dlpack interop."""
    return shm_handle.as_array(datatype, shape, offset)


def allocated_shared_memory_regions() -> List[str]:
    with _registry_lock:
        return [r.triton_shm_name for r in _registry.values()]


def destroy_shared_memory_region(shm_handle: TpuSharedMemoryRegion):
    # Drop the registry entry FIRST: a co-located server resolving raw
    # handles must never find a region that is mid-teardown. The two lock
    # scopes stay disjoint (never nested) so the project lock-order graph
    # (tpulint TPU007) keeps registry and region locks unordered.
    with _registry_lock:
        _registry.pop(shm_handle.uuid, None)
    with shm_handle._lock:
        shm_handle._destroyed = True
        shm_handle._parked.clear()
        shm_handle._mirror = bytearray(0)
    from tritonclient_tpu import _memscope

    _memscope.clear_static(
        _memscope.SCOPE_CLIENT, _memscope.MEM_POOL_SHM,
        "tpu:" + shm_handle.uuid,
    )
