"""Canonical KServe v2 wire literals — the single source of truth.

Every endpoint path template, drift-prone JSON/parameter key, and datatype
string the protocol front-ends speak lives here exactly once. The reference
Triton client ecosystem historically leaked bugs through wire-literal drift
between the HTTP and gRPC planes (a key spelled two ways, an endpoint
diverging between client and server); this module plus the tpulint rules
make that drift mechanical to catch:

  * TPU003 flags any ``v2``-prefixed path literal or enforced key literal
    spelled out under ``http/``, ``grpc/``, or ``server/`` instead of
    imported from here;
  * TPU004 cross-checks the numpy<->Triton dtype tables in
    ``tritonclient_tpu.utils`` against ``DATATYPES`` for totality and
    mutual inversion.

Keep this module dependency-free (stdlib ``re`` only): both protocol
front-ends and the analysis package import it.
"""

import re

# --------------------------------------------------------------------------- #
# datatype registry                                                           #
# --------------------------------------------------------------------------- #

#: Every datatype string the v2 protocol can put in a tensor's ``datatype``
#: field. ``BYTES`` is the only variable-size member; the fixed-size set is
#: ``DATATYPES - {DT_BYTES}`` and must match ``_TRITON_DTYPE_SIZES`` in
#: ``tritonclient_tpu.utils`` exactly (enforced by TPU004).
DT_BOOL = "BOOL"
DT_UINT8 = "UINT8"
DT_UINT16 = "UINT16"
DT_UINT32 = "UINT32"
DT_UINT64 = "UINT64"
DT_INT8 = "INT8"
DT_INT16 = "INT16"
DT_INT32 = "INT32"
DT_INT64 = "INT64"
DT_FP16 = "FP16"
DT_FP32 = "FP32"
DT_FP64 = "FP64"
DT_BF16 = "BF16"
DT_BYTES = "BYTES"

DATATYPES = frozenset(
    {
        DT_BOOL,
        DT_UINT8,
        DT_UINT16,
        DT_UINT32,
        DT_UINT64,
        DT_INT8,
        DT_INT16,
        DT_INT32,
        DT_INT64,
        DT_FP16,
        DT_FP32,
        DT_FP64,
        DT_BF16,
        DT_BYTES,
    }
)

# --------------------------------------------------------------------------- #
# JSON body / request-parameter keys                                          #
# --------------------------------------------------------------------------- #

# Shared-memory tensor routing (identical key spelling on the HTTP JSON
# parameters object and the gRPC InferParameter map — the pair of planes
# that historically drifted).
KEY_SHM_REGION = "shared_memory_region"
KEY_SHM_OFFSET = "shared_memory_offset"
KEY_SHM_BYTE_SIZE = "shared_memory_byte_size"

# HTTP binary-tensor-data extension.
KEY_BINARY_DATA = "binary_data"
KEY_BINARY_DATA_SIZE = "binary_data_size"
KEY_BINARY_DATA_OUTPUT = "binary_data_output"

# Classification extension.
KEY_CLASSIFICATION = "classification"

# Sequence extension.
KEY_SEQUENCE_ID = "sequence_id"
KEY_SEQUENCE_START = "sequence_start"
KEY_SEQUENCE_END = "sequence_end"

# Decoupled-streaming markers (gRPC).
KEY_EMPTY_FINAL_RESPONSE = "triton_enable_empty_final_response"
KEY_FINAL_RESPONSE = "triton_final_response"

# Repository control.
KEY_UNLOAD_DEPENDENTS = "unload_dependents"

#: KServe request-level timeout budget in microseconds (the reference
#: clients' ``infer(..., timeout=...)`` kwarg rides the wire under this
#: parameter name). The server parses it into ``CoreRequest.deadline_us``.
KEY_TIMEOUT = "timeout"

# --------------------------------------------------------------------------- #
# load-shed vocabulary (deadline-aware scheduling)                             #
# --------------------------------------------------------------------------- #

#: HTTP status of a request shed by deadline-aware scheduling — rejected at
#: admission (remaining budget provably smaller than the service estimate)
#: or swept out of the queue after its deadline expired. The gRPC plane
#: maps it to ``DEADLINE_EXCEEDED``. Spelled here exactly once so client
#: and server cannot drift on the shed status (enforced by TPU008).
STATUS_SHED = 504

#: HTTP status of a request removed from the queue because its client went
#: away (disconnect / stream cancel). The gRPC plane maps it to
#: ``CANCELLED``.
STATUS_CANCELLED = 499

#: ``reason`` label values of the ``nv_inference_shed_total`` counter and
#: the flight recorder's ``shed.reason`` attribute.
SHED_REASON_ADMISSION = "admission"
SHED_REASON_EXPIRED = "expired"
SHED_REASON_CANCELLED = "cancelled"
SHED_REASONS = (
    SHED_REASON_ADMISSION,
    SHED_REASON_EXPIRED,
    SHED_REASON_CANCELLED,
)

# --------------------------------------------------------------------------- #
# input-validation vocabulary (untrusted request plane)                       #
# --------------------------------------------------------------------------- #

#: HTTP status of a request rejected by boundary validation
#: (``protocol/_validate.py``): malformed JSON, a shape/dtype/byte-size
#: the wire grammar forbids, or shm window arithmetic that cannot fit the
#: registered region. The gRPC plane maps it to ``INVALID_ARGUMENT``.
#: Spelled here exactly once so the two planes cannot drift on what
#: "invalid" means (enforced by TPU008).
STATUS_INVALID = 400

#: HTTP status of a request whose body exceeds the front-end's
#: ``max_request_bytes`` cap — rejected BEFORE the body is read, so an
#: attacker-controlled Content-Length can never size an allocation. The
#: gRPC plane enforces the same cap via ``grpc.max_receive_message_length``
#: and answers ``RESOURCE_EXHAUSTED``.
STATUS_TOO_LARGE = 413

#: Default request-body cap (bytes) for both front-ends. Generous enough
#: for any sane tensor payload over the wire plane (bulk data belongs in
#: shared memory), small enough that a forged Content-Length cannot stage
#: an allocation bomb.
MAX_REQUEST_BYTES_DEFAULT = 64 * 1024 * 1024

#: ``reason`` label values of the ``nv_inference_invalid_request_total``
#: counter and the flight recorder's ``invalid.reason`` attribute. All
#: rows always render (zeros included) so scrapers see a stable label
#: set. Spelled here exactly once (enforced by TPU008): a front-end
#: stamping reason X while the metric renders reason Y silently
#: un-attributes every rejection.
INVALID_REASON_MALFORMED = "malformed"        # unparseable body / frame
INVALID_REASON_SHAPE = "invalid_shape"        # dim type/range/product cap
INVALID_REASON_DTYPE = "invalid_dtype"        # unknown Triton datatype
INVALID_REASON_DATA_MISMATCH = "data_mismatch"  # shape product vs payload
INVALID_REASON_SHM_BOUNDS = "shm_bounds"      # offset/byte_size vs region
INVALID_REASON_TOO_LARGE = "too_large"        # body over max_request_bytes
INVALID_REASONS = (
    INVALID_REASON_MALFORMED,
    INVALID_REASON_SHAPE,
    INVALID_REASON_DTYPE,
    INVALID_REASON_DATA_MISMATCH,
    INVALID_REASON_SHM_BOUNDS,
    INVALID_REASON_TOO_LARGE,
)

# --------------------------------------------------------------------------- #
# multi-tenant fleet vocabulary                                               #
# --------------------------------------------------------------------------- #

#: HTTP header / gRPC invocation-metadata key naming the tenant a request
#: belongs to. The fleet router keys token-bucket quotas and priority
#: classes on it; the replicas stamp it onto ``CoreRequest.tenant`` and
#: the flight recorder so fairness regressions attribute to a tenant.
#: Spelled here exactly once (enforced by TPU008): a router admitting
#: header X while the replica stamps header Y silently un-attributes
#: every record.
HEADER_TENANT_ID = "tenant-id"

#: HTTP status of a request rejected at the fleet router's per-tenant
#: admission (token-bucket exhausted, concurrency cap, or priority
#: pressure-shed). The gRPC plane maps it to ``RESOURCE_EXHAUSTED``.
#: Like STATUS_SHED it is answered *fast* — before any replica I/O.
STATUS_OVER_QUOTA = 429

#: ``reason`` label values of the router's
#: ``nv_fleet_tenant_quota_rejections_total`` counter.
QUOTA_REASON_RATE = "rate"
QUOTA_REASON_CONCURRENCY = "concurrency"
QUOTA_REASON_PRESSURE = "pressure"
QUOTA_REASONS = (
    QUOTA_REASON_RATE,
    QUOTA_REASON_CONCURRENCY,
    QUOTA_REASON_PRESSURE,
)

# --------------------------------------------------------------------------- #
# fleet SLO plane vocabulary (fleetscope)                                     #
# --------------------------------------------------------------------------- #

#: ``window`` label values of ``nv_fleet_slo_burn_rate``: the fast
#: (1-minute-equivalent) and slow (1-hour-equivalent) burn-rate windows
#: of multi-window SLO alerting. Spelled here exactly once (enforced by
#: TPU008): alert rules match on these strings, and an engine burning
#: window X while the exposition renders window Y silently disarms the
#: page.
SLO_WINDOW_FAST = "fast"
SLO_WINDOW_SLOW = "slow"
SLO_WINDOWS = (SLO_WINDOW_FAST, SLO_WINDOW_SLOW)

#: Cohort-delta detector verdicts (``v2/fleet/cohorts`` documents and
#: the ``verdict`` field fleet_report.py renders). ``insufficient-data``
#: covers both too-few samples and stale-scraped replicas — an honest
#: "cannot judge", never silently ``clean``.
COHORT_REGRESSED = "regressed"
COHORT_CLEAN = "clean"
COHORT_INSUFFICIENT = "insufficient-data"
COHORT_VERDICTS = (COHORT_REGRESSED, COHORT_CLEAN, COHORT_INSUFFICIENT)

#: Default cohort every replica belongs to until assigned otherwise.
COHORT_BASELINE = "baseline"

#: Canonical cohort label shape: lowercase slug, so the ``cohort``
#: metric label and the admin/journal spelling cannot drift by case or
#: whitespace. Enforced at assignment AND by the exposition checker.
COHORT_LABEL_RE = re.compile(r"^[a-z0-9][a-z0-9_-]*$")

# --------------------------------------------------------------------------- #
# resilience vocabulary (retries, hedging, circuit breakers)                  #
# --------------------------------------------------------------------------- #

#: HTTP header / gRPC invocation-metadata key carrying a caller-chosen
#: idempotency key. Its PRESENCE is the contract: the caller asserts the
#: request may be executed more than once, which is what authorizes a
#: client/proxy to replay it after a failure that is NOT provably
#: pre-execution (e.g. a mid-response FIN) and to hedge it onto a second
#: replica. Spelled here exactly once (enforced by TPU008): a retrying
#: proxy honoring key X while a client stamps key Y silently disables
#: every replay.
HEADER_IDEMPOTENCY_KEY = "idempotency-key"

#: Header stamped on replayed attempts (value = attempt ordinal, "1" on
#: the first retry) so replicas and traces can tell a replay from fresh
#: offered load.
HEADER_RETRY_ATTEMPT = "retry-attempt"

#: Header stamped on the hedge duplicate of a hedged request (value =
#: "1") so the loser's shed shows up attributably in server metrics.
HEADER_HEDGE_ATTEMPT = "hedge-attempt"

#: Standard HTTP backpressure header honored by RetryPolicy: a 429/503
#: carrying ``Retry-After: <seconds>`` overrides the computed backoff.
HEADER_RETRY_AFTER = "retry-after"

#: Response statuses that are retryable WITHOUT an idempotency key: the
#: server answered without executing the request (quota rejection /
#: no-capacity), so a replay cannot double-execute.
RETRYABLE_STATUSES = (STATUS_OVER_QUOTA, 503)

#: ``reason`` label values of ``nv_client_retries_total`` (and the
#: RetryPolicy counter keys): why a replay was authorized.
RETRY_REASON_CONNECT = "connect"        # connect-phase transport failure
RETRY_REASON_SEND = "send"              # send-phase transport failure
RETRY_REASON_STATUS = "status"          # retryable status (429/503)
RETRY_REASON_IDEMPOTENT = "idempotent"  # post-send failure + idempotency key
RETRY_REASONS = (
    RETRY_REASON_CONNECT,
    RETRY_REASON_SEND,
    RETRY_REASON_STATUS,
    RETRY_REASON_IDEMPOTENT,
)

#: Circuit-breaker states and their ``nv_client_breaker_state`` gauge
#: encoding (closed=0, half_open=1, open=2 — higher is less available).
BREAKER_CLOSED = "closed"
BREAKER_HALF_OPEN = "half_open"
BREAKER_OPEN = "open"
BREAKER_STATES = (BREAKER_CLOSED, BREAKER_HALF_OPEN, BREAKER_OPEN)
BREAKER_STATE_VALUES = {
    BREAKER_CLOSED: 0,
    BREAKER_HALF_OPEN: 1,
    BREAKER_OPEN: 2,
}

#: ``outcome`` label values of ``nv_fleet_hedges_total``: who won a
#: hedged request (``primary`` = hedge fired but the primary still won,
#: ``hedge`` = the hedge won, ``failed`` = both attempts failed).
HEDGE_OUTCOME_PRIMARY = "primary"
HEDGE_OUTCOME_HEDGE = "hedge"
HEDGE_OUTCOME_FAILED = "failed"
HEDGE_OUTCOMES = (
    HEDGE_OUTCOME_PRIMARY,
    HEDGE_OUTCOME_HEDGE,
    HEDGE_OUTCOME_FAILED,
)

# --------------------------------------------------------------------------- #
# paged KV cache vocabulary (prefix caching)                                  #
# --------------------------------------------------------------------------- #

#: ``event`` label values of the ``nv_engine_prefix_cache_events_total``
#: counter: the gpt engine's block-pool prefix cache resolving a full
#: prompt block by cumulative token hash (``hit``), computing it fresh
#: (``miss``), or reclaiming an LRU zero-ref cached block to satisfy an
#: allocation (``evict``). Spelled here exactly once (enforced by
#: TPU008): dashboards alert on these strings, and an engine counting
#: event X while the exposition renders event Y silently zeroes the
#: hit-rate panel.
PREFIX_EVENT_HIT = "hit"
PREFIX_EVENT_MISS = "miss"
PREFIX_EVENT_EVICT = "evict"
PREFIX_EVENTS = (
    PREFIX_EVENT_HIT,
    PREFIX_EVENT_MISS,
    PREFIX_EVENT_EVICT,
)

# --------------------------------------------------------------------------- #
# device-memory vocabulary (memscope)                                         #
# --------------------------------------------------------------------------- #

#: ``pool`` label values of ``nv_device_memory_bytes`` /
#: ``nv_device_memory_events_total``: which device-resident byte
#: population a ledger row accounts. ``kv`` = paged KV block pools,
#: ``params`` = model parameters (per-device bytes from the actual
#: jax.Array shardings), ``shm`` = registered shared-memory regions
#: (system + TPU device buffers), ``scratch`` = engine slot-state /
#: scratch buffers. Spelled here exactly once (enforced by TPU008):
#: dashboards and the exposition checker match on these strings, and a
#: ledger reporting pool X while the exposition renders pool Y silently
#: zeroes the occupancy panel.
MEM_POOL_KV = "kv"
MEM_POOL_PARAMS = "params"
MEM_POOL_SHM = "shm"
MEM_POOL_SCRATCH = "scratch"
MEM_POOLS = (
    MEM_POOL_KV,
    MEM_POOL_PARAMS,
    MEM_POOL_SHM,
    MEM_POOL_SCRATCH,
)

#: ``kind`` label values of ``nv_device_memory_bytes``: ``live`` =
#: bytes resident right now (parked prefix-cache pages included —
#: they occupy HBM), ``peak`` = high-water mark of live since reset,
#: ``reserved`` = sum of per-request reservations
#: (``ceil((prompt+max_new)/block_size)`` pages each; shared prefix
#: pages count once per holder, so ``reserved`` above ``live`` is the
#: sharing win, not an error).
MEM_KIND_LIVE = "live"
MEM_KIND_PEAK = "peak"
MEM_KIND_RESERVED = "reserved"
MEM_KINDS = (
    MEM_KIND_LIVE,
    MEM_KIND_PEAK,
    MEM_KIND_RESERVED,
)

#: ``event`` label values of ``nv_device_memory_events_total``:
#: ``alloc`` = bytes granted (fresh page, cache-hit grant, region
#: registration, params load), ``free`` = bytes returned, ``park`` =
#: zero-ref prefix-cache pages parked evictable (still live), ``evict``
#: = parked pages reclaimed to satisfy an allocation.
MEM_EVENT_ALLOC = "alloc"
MEM_EVENT_FREE = "free"
MEM_EVENT_PARK = "park"
MEM_EVENT_EVICT = "evict"
MEM_EVENTS = (
    MEM_EVENT_ALLOC,
    MEM_EVENT_FREE,
    MEM_EVENT_PARK,
    MEM_EVENT_EVICT,
)

#: Server-internal parameter key carrying a request's ``cancel_event``
#: into engine-backed models (gpt/tp engines poll it between decode
#: steps). Never on the wire: the front-ends strip/never accept it, and
#: the core injects it only for models declaring
#: ``accepts_cancel_event = True``.
PARAM_CANCEL_EVENT = "_tpu_cancel_event"

#: Server-internal parameter key carrying the request's ``TraceContext``
#: timeline (its ``timestamps`` dict: REQUEST_RECV, COMPUTE_INFER, ...)
#: beside the cancel event, under the same opt-in and never on the wire.
#: stepscope copies the receipt stamps onto the engine's request record.
PARAM_TRACE_TIMESTAMPS = "_tpu_trace_timestamps"

#: Request parameters the clients reserve for dedicated kwargs; user-supplied
#: ``parameters`` dicts may not name these (reference:
#: tritonclient/http/_utils.py:114-117 and grpc/_utils.py equivalent).
RESERVED_REQUEST_PARAMS = (
    KEY_SEQUENCE_ID,
    KEY_SEQUENCE_START,
    KEY_SEQUENCE_END,
    "priority",
    KEY_BINARY_DATA_OUTPUT,
)

# --------------------------------------------------------------------------- #
# server capability vocabulary                                                #
# --------------------------------------------------------------------------- #

#: Extension names reported in ``v2`` server metadata. Wire-visible protocol
#: vocabulary: language clients switch on these strings.
SERVER_EXTENSIONS = (
    KEY_CLASSIFICATION,
    "sequence",
    "model_repository",
    "model_configuration",
    "system_shared_memory",
    "cuda_shared_memory",
    "tpu_shared_memory",
    "binary_tensor_data",
    "parameters",
    "statistics",
    "trace",
    "logging",
)

# --------------------------------------------------------------------------- #
# endpoint paths                                                              #
# --------------------------------------------------------------------------- #

EP_SERVER_METADATA = "v2"
EP_HEALTH_LIVE = "v2/health/live"
EP_HEALTH_READY = "v2/health/ready"
EP_REPOSITORY_INDEX = "v2/repository/index"
EP_LOGGING = "v2/logging"
EP_TRACE_SETTING = "v2/trace/setting"
#: Flight-recorder dump (tail-based retention): slowest-K span trees per
#: sliding window plus every error/deadline miss. ``?format=perfetto``
#: renders the retained records as Chrome trace-event JSON.
EP_FLIGHT_RECORDER = "v2/debug/flight_recorder"
#: Device-memory ledger dump (memscope): the self-describing document
#: ``scripts/mem_report.py`` loads — per-(model, pool) live/peak/
#: reserved bytes, the alloc/free event ring, per-owner residue, and
#: headroom. Served by both front-ends.
EP_DEBUG_MEMSCOPE = "v2/debug/memscope"
#: Raw per-model/per-stage DDSketch state (replica-side): the fleet
#: router's prober fetches these each scrape tick so fleetscope can
#: merge quantiles EXACTLY (bucket-wise) instead of pooling resolved
#: quantile rows (which cannot be merged).
EP_DEBUG_SKETCHES = "v2/debug/sketches"
#: Replica drain control (fleet tier): POST ``{"drain": true|false}``;
#: draining flips ``v2/health/ready`` to 400 (stop new admissions) while
#: in-flight requests finish. The response — and GETs of
#: ``v2/health/ready`` — carry the readiness-detail document
#: ``{"ready", "draining", "in_flight"}`` the router polls to know when
#: a drain has settled.
EP_FLEET_DRAIN = "v2/fleet/drain"
#: Router-side fleet status document (replica states, outstanding counts,
#: admission counters). Served by the ROUTER, not the replicas.
EP_FLEET_STATUS = "v2/fleet/status"
#: Merged fleet flight-recorder dump (router-side): fans out to every
#: READY replica's EP_FLIGHT_RECORDER, stamps each record with the
#: replica name, and merges in the router's own proxy-side records
#: keyed by traceparent — one dump, the full router→replica timeline.
EP_FLEET_FLIGHT_RECORDER = "v2/fleet/debug/flight_recorder"
#: SLO objective admin (router-side): GET lists objectives + burn
#: state; POST ``{"model", "tenant", "latency_target_us",
#: "error_budget"}`` declares one (journaled, survives restarts).
EP_FLEET_SLO = "v2/fleet/slo"
#: Cohort-delta detector (router-side): GET returns per-cohort verdict
#: documents; POST ``{"replica": ..., "cohort": ...}`` assigns a
#: replica to a labeled cohort (journaled, survives restarts).
EP_FLEET_COHORTS = "v2/fleet/cohorts"
#: Full fleetscope dump (router-side): the self-describing document
#: ``scripts/fleet_report.py`` loads — scrape health, retained time
#: series, merged sketch quantiles, SLO burn state, cohort verdicts.
EP_FLEET_FLEETSCOPE = "v2/fleet/debug/fleetscope"
#: Prometheus exposition (Triton serves this on a dedicated port; the
#: in-process server shares its one HTTP port).
EP_METRICS = "metrics"

#: Maps the URL path segment of a shared-memory admin endpoint to the
#: registry kind the core understands.
SHM_URL_KINDS = {
    "systemsharedmemory": "system",
    "cudasharedmemory": "cuda",
    "tpusharedmemory": "tpu",
}


def model_path(name: str, version: str = "") -> str:
    """``v2/models/{name}[/versions/{version}]`` — model metadata GET."""
    if version:
        return f"v2/models/{name}/versions/{version}"
    return f"v2/models/{name}"


def model_ready_path(name: str, version: str = "") -> str:
    return model_path(name, version) + "/ready"


def model_config_path(name: str, version: str = "") -> str:
    return model_path(name, version) + "/config"


def model_infer_path(name: str, version: str = "") -> str:
    return model_path(name, version) + "/infer"


def model_stats_path(name: str = "", version: str = "") -> str:
    """Per-model statistics, or the all-models aggregate when ``name`` is
    empty (``v2/models/stats``)."""
    if not name:
        return "v2/models/stats"
    return model_path(name, version) + "/stats"


def trace_setting_path(model_name: str = "") -> str:
    """Per-model trace settings, or the global endpoint when unnamed."""
    if model_name:
        return f"v2/models/{model_name}/trace/setting"
    return EP_TRACE_SETTING


def repository_load_path(name: str) -> str:
    return f"v2/repository/models/{name}/load"


def repository_unload_path(name: str) -> str:
    return f"v2/repository/models/{name}/unload"


def shm_admin_path(plane: str, action: str, region: str = "") -> str:
    """Shared-memory admin endpoint for one plane.

    ``plane`` is ``system`` | ``cuda`` | ``tpu``; ``action`` is ``status`` |
    ``register`` | ``unregister``. ``region`` is required for ``register``
    and optional for the other two (empty = all regions).
    """
    base = f"v2/{plane}sharedmemory"
    if region:
        return f"{base}/region/{region}/{action}"
    return f"{base}/{action}"


# --------------------------------------------------------------------------- #
# server-side route patterns                                                  #
# --------------------------------------------------------------------------- #

#: The HTTP front-end's dispatch table, kept beside the client-side path
#: builders so the two cannot drift apart.
MODEL_ROUTE_RE = re.compile(
    r"^v2/models/(?P<model>[^/]+)(?:/versions/(?P<version>[^/]+))?"
    r"(?:/(?P<action>ready|config|stats|infer|trace/setting))?$"
)
REPOSITORY_ROUTE_RE = re.compile(
    r"^v2/repository/models/(?P<model>[^/]+)/(?P<action>load|unload)$"
)
SHM_ROUTE_RE = re.compile(
    r"^v2/(?P<kind>systemsharedmemory|cudasharedmemory|tpusharedmemory)"
    r"(?:/region/(?P<region>[^/]+))?/(?P<action>status|register|unregister)$"
)
#: Router-side replica admin: drain / undrain one replica by name.
FLEET_REPLICA_ROUTE_RE = re.compile(
    r"^v2/fleet/replicas/(?P<replica>[^/]+)/(?P<action>drain|undrain|cohort)$"
)
