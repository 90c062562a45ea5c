#!/usr/bin/env python
"""Validate Prometheus exposition output from the server's /metrics.

Invoked from tier-1 tests (tests/test_observability.py) against the live
endpoint, and usable standalone::

    curl -s http://HOST:PORT/metrics | python scripts/check_metrics_exposition.py
    python scripts/check_metrics_exposition.py metrics.txt

Checks (exit 1 with one line per violation):
  * every sample's metric family is preceded by ``# HELP`` and ``# TYPE``
  * ``# TYPE`` names a valid Prometheus type
  * sample lines parse, with correctly escaped label values
    (backslash, quote, and newline must be escaped)
  * histogram families: ``le`` bucket bounds strictly ascending, cumulative
    bucket values non-decreasing, a ``+Inf`` bucket present, ``_count``
    equal to the ``+Inf`` bucket, and ``_sum`` present and >= 0
  * summary families (the sketch-backed ``*_quantiles`` rows): every
    ``quantile`` label in [0, 1], values monotone non-decreasing in the
    quantile, ``_sum``/``_count`` present and >= 0
  * counter samples non-negative; gauges reporting ages (``*_age_us``)
    non-negative (a negative age means a broken clock, not a quiet queue)
  * the ``nv_inference_shed_total`` family: every sample carries exactly
    the {model, version, reason} label set with ``reason`` drawn from the
    canonical shed vocabulary, and all three reasons are present per
    (model, version) series so reason sums are well-defined
  * the ``nv_inference_invalid_request_total`` family (PR 19): exactly
    {model, version, reason} with ``reason`` drawn from the canonical
    invalid-request vocabulary (``protocol._literals.INVALID_REASONS``)
    and EVERY reason row rendered per (model, version) series (zeros
    included) — rejection-rate dashboards must never guess
    absent-as-zero, and a non-canonical reason means a front-end
    bypassed ``protocol/_validate``
  * the fleet-router families: ``nv_fleet_tenant_quota_rejections_total``
    carries exactly {tenant, reason} with canonical quota reasons and
    every reason row present per tenant;
    ``nv_fleet_replica_up`` is a per-replica gauge valued 0/1;
    ``nv_fleet_replica_outstanding`` / ``nv_fleet_replica_queue_depth``
    carry a replica label and are non-negative
  * the stepscope families: ``nv_engine_step_duration_us_quantiles``
    quantile rows carry exactly {model, phase, stage, quantile} with
    ``stage``/``phase`` drawn from the canonical stepscope vocabularies
    (and the shared summary checks — quantile monotonicity, _sum/_count);
    no stage row is required: a counters-mode server emits ``dispatch``
    alone (it has no device clock), a ``sync`` one all three;
    ``nv_engine_collectives_total`` carries exactly {model, op}
  * the pipelined-dispatch depth gauge ``nv_engine_inflight_steps``
    carries exactly {model}, non-negative
  * the paged-KV families: ``nv_engine_kv_blocks_used`` /
    ``nv_engine_kv_blocks_total`` carry exactly {model}, are
    non-negative, and used <= total per model;
    ``nv_engine_prefix_cache_events_total`` carries exactly
    {model, event} with ``event`` drawn from the canonical prefix-cache
    vocabulary and every event row present per model (so hit rates are
    computable from any single scrape)
  * the fleetscope families (PR 16): ``nv_fleet_scrape_age_s`` carries
    exactly {replica} and is non-negative;
    ``nv_fleet_scrape_failures_total`` carries exactly {replica};
    ``nv_fleet_slo_burn_rate`` carries exactly {model, tenant, window}
    with ``window`` drawn from the canonical SLO window vocabulary and
    a non-negative value; ``nv_fleet_slo_budget_remaining`` carries
    exactly {model, tenant} with a value in [0, 1];
    ``nv_fleet_cohort_requests_total`` carries exactly {cohort} with
    the cohort label in canonical (lowercase slug) form;
    ``nv_engine_kv_bytes_touched_total`` carries exactly
    {model, phase} with ``phase`` from the stepscope vocabulary
  * the compile-plane families (PR 20): ``nv_engine_compile_cache_entries``
    carries exactly {model, callable} with a value >= 1 (a row exists
    only once a dispatch signature was recorded);
    ``nv_engine_retrace_total`` carries exactly {model, callable}; and
    per (model, callable) series retraces <= entries - 1 (every retrace
    is a distinct signature beyond the first, so a counter exceeding
    that means double-counted compiles)
  * the memscope families (PR 18): ``nv_device_memory_bytes`` carries
    exactly {model, pool, kind} with ``pool``/``kind`` drawn from the
    canonical memscope vocabularies and non-negative values, with
    live <= peak per (model, pool);
    ``nv_device_memory_events_total`` carries exactly
    {model, pool, event} with canonical events and EVERY event row
    rendered per (model, pool) cell (zeros included);
    ``nv_device_memory_headroom_bytes`` carries exactly {model} and is
    non-negative
"""

import os
import re
import sys
from typing import Dict, List, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:
    sys.path.insert(0, _REPO_ROOT)

try:
    from tritonclient_tpu.protocol._literals import (
        HEDGE_OUTCOMES,
        INVALID_REASONS,
        QUOTA_REASONS,
        RETRY_REASONS,
        SHED_REASONS,
    )
except ImportError:  # standalone copy of the script: keep it usable
    SHED_REASONS = ("admission", "expired", "cancelled")
    QUOTA_REASONS = ("rate", "concurrency", "pressure")
    RETRY_REASONS = ("connect", "send", "status", "idempotent")
    HEDGE_OUTCOMES = ("primary", "hedge", "failed")
    INVALID_REASONS = ("malformed", "invalid_shape", "invalid_dtype",
                       "data_mismatch", "shm_bounds", "too_large")

try:
    from tritonclient_tpu._stepscope import STEP_PHASES, STEP_STAGES
except ImportError:  # standalone copy of the script: keep it usable
    STEP_STAGES = ("dispatch", "device", "other")
    STEP_PHASES = ("prefill", "prefill_chunk", "decode", "compute")

try:
    from tritonclient_tpu.protocol._literals import PREFIX_EVENTS
except ImportError:  # standalone copy of the script: keep it usable
    PREFIX_EVENTS = ("hit", "miss", "evict")

try:
    from tritonclient_tpu.protocol._literals import (
        COHORT_LABEL_RE,
        SLO_WINDOWS,
    )
except ImportError:  # standalone copy of the script: keep it usable
    SLO_WINDOWS = ("fast", "slow")
    COHORT_LABEL_RE = re.compile(r"^[a-z0-9][a-z0-9_-]*$")

try:
    from tritonclient_tpu.protocol._literals import (
        MEM_EVENTS,
        MEM_KINDS,
        MEM_POOLS,
    )
except ImportError:  # standalone copy of the script: keep it usable
    MEM_POOLS = ("kv", "params", "shm", "scratch")
    MEM_KINDS = ("live", "peak", "reserved")
    MEM_EVENTS = ("alloc", "free", "park", "evict")

_SHED_FAMILY = "nv_inference_shed_total"
# Invalid-request counter (PR 19): boundary-validation rejections with
# the same stable-label-set discipline as the shed counter — canonical
# reasons only, every reason row rendered per (model, version).
_INVALID_FAMILY = "nv_inference_invalid_request_total"
# Fleet-router families (served by the router's own /metrics): same
# stable-label-set discipline as the shed counter.
_QUOTA_FAMILY = "nv_fleet_tenant_quota_rejections_total"
_REPLICA_UP_FAMILY = "nv_fleet_replica_up"
_REPLICA_GAUGE_FAMILIES = (
    "nv_fleet_replica_outstanding",
    "nv_fleet_replica_queue_depth",
)
# Resilience families (PR 9): canonical-vocabulary counters with every
# row always rendered, plus the breaker-state gauge's 3-value encoding.
_RETRY_FAMILY = "nv_client_retries_total"
_HEDGE_FAMILY = "nv_fleet_hedges_total"
_RESTARTS_FAMILY = "nv_fleet_replica_restarts_total"
_BREAKER_FAMILY = "nv_client_breaker_state"
# Stepscope families (engine step profiling): fixed label sets with
# canonical stage/phase vocabularies so dashboards can group blindly.
_STEP_FAMILY = "nv_engine_step_duration_us_quantiles"
_COLLECTIVES_FAMILY = "nv_engine_collectives_total"
# Paged-KV families (block pool occupancy + prefix-cache events).
_KV_USED_FAMILY = "nv_engine_kv_blocks_used"
_KV_TOTAL_FAMILY = "nv_engine_kv_blocks_total"
_PREFIX_FAMILY = "nv_engine_prefix_cache_events_total"
# The pipelined-dispatch depth gauge.
_INFLIGHT_FAMILY = "nv_engine_inflight_steps"
# Fleetscope families (PR 16): scrape-health gauges/counters on the
# router plus the SLO plane (burn rates, budget, cohort attribution)
# and the engine's per-phase KV traffic counter.
_SCRAPE_AGE_FAMILY = "nv_fleet_scrape_age_s"
_SCRAPE_FAILURES_FAMILY = "nv_fleet_scrape_failures_total"
_BURN_FAMILY = "nv_fleet_slo_burn_rate"
_BUDGET_FAMILY = "nv_fleet_slo_budget_remaining"
_COHORT_FAMILY = "nv_fleet_cohort_requests_total"
_KV_BYTES_FAMILY = "nv_engine_kv_bytes_touched_total"
# Memscope families (PR 18): the device-memory ledger's byte gauges,
# event counters, and the admission headroom gauge.
_MEM_BYTES_FAMILY = "nv_device_memory_bytes"
_MEM_EVENTS_FAMILY = "nv_device_memory_events_total"
_MEM_HEADROOM_FAMILY = "nv_device_memory_headroom_bytes"
# Compile-plane families (PR 20): distinct dispatch signatures per
# jitted callable (compile cache entries) and retrace events beyond the
# first compile — the runtime face of TPU017 bucket discipline.
_COMPILE_FAMILY = "nv_engine_compile_cache_entries"
_RETRACE_FAMILY = "nv_engine_retrace_total"

_VALID_TYPES = {"counter", "gauge", "histogram", "summary", "untyped"}
_METRIC_NAME = r"[a-zA-Z_:][a-zA-Z0-9_:]*"
_HELP_RE = re.compile(rf"^# HELP ({_METRIC_NAME}) (.*)$")
_TYPE_RE = re.compile(rf"^# TYPE ({_METRIC_NAME}) (\S+)$")
_SAMPLE_RE = re.compile(
    rf"^({_METRIC_NAME})(\{{.*\}})? ([^ ]+)( [0-9]+)?$"
)
_LABEL_RE = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\[\\"n])*)"')


def _parse_labels(raw: str, errors: List[str], lineno: int) -> Dict[str, str]:
    """Parse {k="v",...}; any residue after consuming valid pairs means a
    malformed pair or bad escaping."""
    body = raw[1:-1]
    labels: Dict[str, str] = {}
    pos = 0
    while pos < len(body):
        m = _LABEL_RE.match(body, pos)
        if m is None:
            errors.append(
                f"line {lineno}: bad label syntax or escaping near "
                f"{body[pos:pos + 40]!r}"
            )
            return labels
        labels[m.group(1)] = m.group(2)
        pos = m.end()
        if pos < len(body):
            if body[pos] != ",":
                errors.append(
                    f"line {lineno}: expected ',' between labels, got "
                    f"{body[pos]!r}"
                )
                return labels
            pos += 1
    return labels


def _family_of(name: str, types: Dict[str, str]) -> str:
    """Map a sample name back to its declared family (histogram/summary
    series carry _bucket/_sum/_count suffixes)."""
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix) and name[: -len(suffix)] in types:
            return name[: -len(suffix)]
    return name


def check_exposition(text: str) -> List[str]:
    """Return a list of violations (empty = valid)."""
    errors: List[str] = []
    helps: Dict[str, str] = {}
    types: Dict[str, str] = {}
    # family -> list of (labels, float value, sample name, lineno)
    samples: Dict[str, List[Tuple[Dict[str, str], float, str, int]]] = {}

    for lineno, line in enumerate(text.splitlines(), start=1):
        if not line.strip():
            continue
        if line.startswith("#"):
            m = _HELP_RE.match(line)
            if m:
                helps[m.group(1)] = m.group(2)
                continue
            m = _TYPE_RE.match(line)
            if m:
                if m.group(2) not in _VALID_TYPES:
                    errors.append(
                        f"line {lineno}: invalid TYPE '{m.group(2)}' for "
                        f"{m.group(1)}"
                    )
                if m.group(1) in samples:
                    errors.append(
                        f"line {lineno}: # TYPE {m.group(1)} appears after "
                        "its samples"
                    )
                types[m.group(1)] = m.group(2)
                continue
            continue  # other comments are legal
        m = _SAMPLE_RE.match(line)
        if m is None:
            errors.append(f"line {lineno}: unparseable sample: {line!r}")
            continue
        name, raw_labels, value = m.group(1), m.group(2), m.group(3)
        labels = (
            _parse_labels(raw_labels, errors, lineno) if raw_labels else {}
        )
        try:
            fvalue = float(value)
        except ValueError:
            errors.append(f"line {lineno}: non-numeric value {value!r}")
            continue
        family = _family_of(name, types)
        samples.setdefault(family, []).append((labels, fvalue, name, lineno))

    for family in samples:
        if family not in helps:
            errors.append(f"metric family {family} has no # HELP")
        if family not in types:
            errors.append(f"metric family {family} has no # TYPE")

    for family, ftype in types.items():
        if ftype == "counter":
            for labels, value, name, lineno in samples.get(family, []):
                if value < 0:
                    errors.append(
                        f"line {lineno}: counter {name} value {value} < 0"
                    )
            if family == _SHED_FAMILY:
                # Shed-counter contract: fixed {model, version, reason}
                # label set, canonical reasons only, and every reason row
                # present per series (so reasons provably sum to the
                # observed sheds).
                series_reasons: Dict[tuple, set] = {}
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "version", "reason"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != "
                            "['model', 'reason', 'version']"
                        )
                        continue
                    if labels["reason"] not in SHED_REASONS:
                        errors.append(
                            f"line {lineno}: {family} reason "
                            f"{labels['reason']!r} not in "
                            f"{list(SHED_REASONS)}"
                        )
                        continue
                    series_reasons.setdefault(
                        (labels["model"], labels["version"]), set()
                    ).add(labels["reason"])
                for (model, version), reasons in series_reasons.items():
                    missing = [r for r in SHED_REASONS if r not in reasons]
                    if missing:
                        errors.append(
                            f'{family}{{model="{model}",'
                            f'version="{version}"}}: missing reason '
                            f"rows {missing}"
                        )
            if family == _INVALID_FAMILY:
                # Invalid-request contract: fixed {model, version, reason}
                # label set, reasons drawn from the canonical
                # INVALID_REASONS vocabulary (a stray reason means a
                # front-end invented its own classification instead of
                # going through protocol/_validate), and every reason row
                # present per series so rejection sums never need
                # absent-as-zero guessing.
                series_reasons: Dict[tuple, set] = {}
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "version", "reason"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != "
                            "['model', 'reason', 'version']"
                        )
                        continue
                    if labels["reason"] not in INVALID_REASONS:
                        errors.append(
                            f"line {lineno}: {family} reason "
                            f"{labels['reason']!r} not in "
                            f"{list(INVALID_REASONS)}"
                        )
                        continue
                    series_reasons.setdefault(
                        (labels["model"], labels["version"]), set()
                    ).add(labels["reason"])
                for (model, version), reasons in series_reasons.items():
                    missing = [
                        r for r in INVALID_REASONS if r not in reasons
                    ]
                    if missing:
                        errors.append(
                            f'{family}{{model="{model}",'
                            f'version="{version}"}}: missing reason '
                            f"rows {missing}"
                        )
            if family == _QUOTA_FAMILY:
                # Quota-rejection contract: fixed {tenant, reason} label
                # set, canonical reasons, every reason row present per
                # tenant (so per-tenant rejection sums are well-defined).
                tenant_reasons: Dict[str, set] = {}
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"tenant", "reason"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['reason', 'tenant']"
                        )
                        continue
                    if labels["reason"] not in QUOTA_REASONS:
                        errors.append(
                            f"line {lineno}: {family} reason "
                            f"{labels['reason']!r} not in "
                            f"{list(QUOTA_REASONS)}"
                        )
                        continue
                    tenant_reasons.setdefault(
                        labels["tenant"], set()
                    ).add(labels["reason"])
                for tenant, reasons in tenant_reasons.items():
                    missing = [r for r in QUOTA_REASONS if r not in reasons]
                    if missing:
                        errors.append(
                            f'{family}{{tenant="{tenant}"}}: missing '
                            f"reason rows {missing}"
                        )
            if family in (_RETRY_FAMILY, _HEDGE_FAMILY):
                # Canonical-vocabulary counters: one label, canonical
                # values only, EVERY canonical row rendered (zeros
                # included) so rates are always well-defined.
                label, vocab = (
                    ("reason", RETRY_REASONS)
                    if family == _RETRY_FAMILY
                    else ("outcome", HEDGE_OUTCOMES)
                )
                seen = set()
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {label}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['{label}']"
                        )
                        continue
                    if labels[label] not in vocab:
                        errors.append(
                            f"line {lineno}: {family} {label} "
                            f"{labels[label]!r} not in {list(vocab)}"
                        )
                        continue
                    seen.add(labels[label])
                if samples.get(family):
                    missing = [v for v in vocab if v not in seen]
                    if missing:
                        errors.append(
                            f"{family}: missing {label} rows {missing}"
                        )
            if family == _RESTARTS_FAMILY:
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"replica"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['replica']"
                        )
            if family == _PREFIX_FAMILY:
                # Prefix-cache event contract: fixed {model, event} label
                # set, canonical events only, every event row present per
                # model (hit rate = hit / (hit + miss) must be computable
                # from one scrape without guessing at absent-as-zero).
                model_events: Dict[str, set] = {}
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "event"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['event', 'model']"
                        )
                        continue
                    if labels["event"] not in PREFIX_EVENTS:
                        errors.append(
                            f"line {lineno}: {family} event "
                            f"{labels['event']!r} not in "
                            f"{list(PREFIX_EVENTS)}"
                        )
                        continue
                    model_events.setdefault(
                        labels["model"], set()
                    ).add(labels["event"])
                for model, events in model_events.items():
                    missing = [e for e in PREFIX_EVENTS if e not in events]
                    if missing:
                        errors.append(
                            f'{family}{{model="{model}"}}: missing event '
                            f"rows {missing}"
                        )
            if family == _SCRAPE_FAILURES_FAMILY:
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"replica"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['replica']"
                        )
            if family == _COHORT_FAMILY:
                # Cohort attribution: exactly {cohort} with the label in
                # canonical (lowercase slug) form — uncanonicalized
                # cohort names would split one cohort's series in two.
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"cohort"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['cohort']"
                        )
                        continue
                    if not COHORT_LABEL_RE.match(labels["cohort"]):
                        errors.append(
                            f"line {lineno}: {family} cohort "
                            f"{labels['cohort']!r} is not a canonical "
                            "lowercase slug"
                        )
            if family == _KV_BYTES_FAMILY:
                # KV traffic counter: exactly {model, phase} with phase
                # from the stepscope vocabulary (value non-negativity is
                # the generic counter check above).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "phase"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['model', 'phase']"
                        )
                        continue
                    if labels["phase"] not in STEP_PHASES:
                        errors.append(
                            f"line {lineno}: {family} phase "
                            f"{labels['phase']!r} not in "
                            f"{list(STEP_PHASES)}"
                        )
            if family == _MEM_EVENTS_FAMILY:
                # Memscope event contract: fixed {model, pool, event}
                # label set, canonical pools/events only, and EVERY
                # canonical event row present per (model, pool) cell so
                # churn rates never need absent-as-zero guessing.
                cell_events: Dict[tuple, set] = {}
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "pool", "event"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != "
                            "['event', 'model', 'pool']"
                        )
                        continue
                    if labels["pool"] not in MEM_POOLS:
                        errors.append(
                            f"line {lineno}: {family} pool "
                            f"{labels['pool']!r} not in {list(MEM_POOLS)}"
                        )
                        continue
                    if labels["event"] not in MEM_EVENTS:
                        errors.append(
                            f"line {lineno}: {family} event "
                            f"{labels['event']!r} not in "
                            f"{list(MEM_EVENTS)}"
                        )
                        continue
                    cell_events.setdefault(
                        (labels["model"], labels["pool"]), set()
                    ).add(labels["event"])
                for (model, pool), events in cell_events.items():
                    missing = [e for e in MEM_EVENTS if e not in events]
                    if missing:
                        errors.append(
                            f'{family}{{model="{model}",pool="{pool}"}}: '
                            f"missing event rows {missing}"
                        )
            if family == _RETRACE_FAMILY:
                # Retrace counter: exactly {model, callable} (value
                # non-negativity is the generic counter check above; the
                # retraces-vs-entries bound is the cross-family check at
                # the bottom).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "callable"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['callable', 'model']"
                        )
            if family == _COLLECTIVES_FAMILY:
                # Stepscope collectives: fixed {model, op} label set (the
                # op value is open vocabulary — psum/ppermute/all_to_all
                # today, whatever the parallel plane adds tomorrow).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "op"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['model', 'op']"
                        )
            continue
        if ftype == "gauge":
            if family.endswith("_age_us"):
                for labels, value, name, lineno in samples.get(family, []):
                    if value < 0:
                        errors.append(
                            f"line {lineno}: age gauge {name} value "
                            f"{value} < 0"
                        )
            if family == _REPLICA_UP_FAMILY:
                # Membership gauge: one {replica} label, value 0 or 1.
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"replica"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['replica']"
                        )
                    if value not in (0.0, 1.0):
                        errors.append(
                            f"line {lineno}: {family} value {value} "
                            "not in {0, 1}"
                        )
            if family == _BREAKER_FAMILY:
                # Breaker-state gauge: one {endpoint} label, value in
                # the 3-state encoding (0=closed, 1=half_open, 2=open).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"endpoint"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['endpoint']"
                        )
                    if value not in (0.0, 1.0, 2.0):
                        errors.append(
                            f"line {lineno}: {family} value {value} "
                            "not in {0, 1, 2}"
                        )
            if family in _REPLICA_GAUGE_FAMILIES:
                for labels, value, name, lineno in samples.get(family, []):
                    if "replica" not in labels:
                        errors.append(
                            f"line {lineno}: {family} sample without a "
                            "'replica' label"
                        )
                    if value < 0:
                        errors.append(
                            f"line {lineno}: {family} value {value} < 0 "
                            "(outstanding/depth cannot be negative)"
                        )
            if family == _INFLIGHT_FAMILY:
                # Dispatch-depth gauge: exactly {model}, non-negative (a
                # negative depth means the submit/deliver accounting
                # leaked, not an idle engine).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['model']"
                        )
                    if value < 0:
                        errors.append(
                            f"line {lineno}: {family} value {value} < 0 "
                            "(in-flight depth cannot be negative)"
                        )
            if family == _SCRAPE_AGE_FAMILY:
                # Staleness gauge: exactly {replica}, non-negative (a
                # negative age means a broken clock, not a fresh scrape).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"replica"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['replica']"
                        )
                    if value < 0:
                        errors.append(
                            f"line {lineno}: {family} value {value} < 0 "
                            "(scrape age cannot be negative)"
                        )
            if family == _BURN_FAMILY:
                # Burn-rate gauge: exactly {model, tenant, window} with
                # the window drawn from the canonical SLO vocabulary,
                # non-negative (burn is a rate of budget consumption).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "tenant", "window"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != "
                            "['model', 'tenant', 'window']"
                        )
                        continue
                    if labels["window"] not in SLO_WINDOWS:
                        errors.append(
                            f"line {lineno}: {family} window "
                            f"{labels['window']!r} not in "
                            f"{list(SLO_WINDOWS)}"
                        )
                    if value < 0:
                        errors.append(
                            f"line {lineno}: {family} value {value} < 0 "
                            "(burn rate cannot be negative)"
                        )
            if family == _BUDGET_FAMILY:
                # Budget gauge: exactly {model, tenant} (slow-window
                # rows only, so no window label), value a fraction.
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "tenant"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['model', 'tenant']"
                        )
                    if not 0.0 <= value <= 1.0:
                        errors.append(
                            f"line {lineno}: {family} value {value} "
                            "outside [0, 1]"
                        )
            if family == _MEM_BYTES_FAMILY:
                # Memscope byte gauge: fixed {model, pool, kind} label
                # set, canonical pools/kinds, non-negative (live <= peak
                # is the cross-family check at the bottom).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "pool", "kind"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != "
                            "['kind', 'model', 'pool']"
                        )
                        continue
                    if labels["pool"] not in MEM_POOLS:
                        errors.append(
                            f"line {lineno}: {family} pool "
                            f"{labels['pool']!r} not in {list(MEM_POOLS)}"
                        )
                    if labels["kind"] not in MEM_KINDS:
                        errors.append(
                            f"line {lineno}: {family} kind "
                            f"{labels['kind']!r} not in {list(MEM_KINDS)}"
                        )
                    if value < 0:
                        errors.append(
                            f"line {lineno}: {family} value {value} < 0 "
                            "(resident bytes cannot be negative)"
                        )
            if family == _MEM_HEADROOM_FAMILY:
                # Headroom gauge: exactly {model}, non-negative (the
                # ledger clamps at zero; a negative value means the
                # capacity bookkeeping broke).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['model']"
                        )
                    if value < 0:
                        errors.append(
                            f"line {lineno}: {family} value {value} < 0 "
                            "(headroom cannot be negative)"
                        )
            if family == _COMPILE_FAMILY:
                # Compile-cache gauge: exactly {model, callable}, value
                # >= 1 (a series renders only once a dispatch signature
                # was recorded, and the first dispatch is an entry).
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model", "callable"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['callable', 'model']"
                        )
                        continue
                    if value < 1:
                        errors.append(
                            f"line {lineno}: {family} value {value} < 1 "
                            "(a rendered series has at least one entry)"
                        )
            if family in (_KV_USED_FAMILY, _KV_TOTAL_FAMILY):
                # Pool-occupancy gauges: exactly {model}, non-negative.
                for labels, value, name, lineno in samples.get(family, []):
                    if set(labels) != {"model"}:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != ['model']"
                        )
                    if value < 0:
                        errors.append(
                            f"line {lineno}: {family} value {value} < 0 "
                            "(block counts cannot be negative)"
                        )
            continue
        if ftype == "summary":
            if family == _STEP_FAMILY:
                # Stepscope step-duration summary: quantile rows carry
                # exactly {model, phase, stage, quantile}; _sum/_count
                # rows drop the quantile label; stage and phase come from
                # the canonical stepscope vocabularies.
                for labels, value, name, lineno in samples.get(family, []):
                    want = {"model", "phase", "stage"}
                    if name == family:
                        want = want | {"quantile"}
                    if set(labels) != want:
                        errors.append(
                            f"line {lineno}: {family} label set "
                            f"{sorted(labels)} != {sorted(want)}"
                        )
                        continue
                    if labels["stage"] not in STEP_STAGES:
                        errors.append(
                            f"line {lineno}: {family} stage "
                            f"{labels['stage']!r} not in "
                            f"{list(STEP_STAGES)}"
                        )
                    if labels["phase"] not in STEP_PHASES:
                        errors.append(
                            f"line {lineno}: {family} phase "
                            f"{labels['phase']!r} not in "
                            f"{list(STEP_PHASES)}"
                        )
            # Group per label set (minus 'quantile'); quantile rows must be
            # valid quantiles and monotone non-decreasing in q, _sum/_count
            # present and non-negative.
            series: Dict[tuple, dict] = {}
            for labels, value, name, lineno in samples.get(family, []):
                key = tuple(sorted(
                    (k, v) for k, v in labels.items() if k != "quantile"
                ))
                entry = series.setdefault(
                    key, {"quantiles": [], "sum": None, "count": None}
                )
                if name == family:
                    if "quantile" not in labels:
                        errors.append(
                            f"line {lineno}: summary sample without "
                            "'quantile' label"
                        )
                        continue
                    try:
                        q = float(labels["quantile"])
                    except ValueError:
                        errors.append(
                            f"line {lineno}: non-numeric quantile "
                            f"{labels['quantile']!r}"
                        )
                        continue
                    if not 0.0 <= q <= 1.0:
                        errors.append(
                            f"line {lineno}: quantile {q} outside [0, 1]"
                        )
                    entry["quantiles"].append((q, value, lineno))
                elif name == family + "_sum":
                    entry["sum"] = value
                elif name == family + "_count":
                    entry["count"] = value
            for key, entry in series.items():
                label_desc = "{%s}" % ",".join(
                    f'{k}="{v}"' for k, v in key
                )
                prev = None
                for q, value, lineno in sorted(entry["quantiles"]):
                    if value < 0:
                        errors.append(
                            f"line {lineno}: {family}{label_desc} "
                            f'quantile="{q}" value {value} < 0'
                        )
                    if prev is not None and value < prev:
                        errors.append(
                            f"line {lineno}: {family}{label_desc} "
                            f'quantile="{q}" value {value} < previous '
                            f"{prev} (quantiles must be non-decreasing "
                            "in q)"
                        )
                    prev = value
                if entry["sum"] is None:
                    errors.append(f"{family}{label_desc}: missing _sum")
                elif entry["sum"] < 0:
                    errors.append(
                        f"{family}{label_desc}: _sum {entry['sum']} < 0"
                    )
                if entry["count"] is None:
                    errors.append(f"{family}{label_desc}: missing _count")
                elif entry["count"] < 0:
                    errors.append(
                        f"{family}{label_desc}: _count {entry['count']} < 0"
                    )
            continue
        if ftype != "histogram":
            continue
        # Group this family's series per label set (minus 'le').
        series: Dict[tuple, dict] = {}
        for labels, value, name, lineno in samples.get(family, []):
            key = tuple(sorted(
                (k, v) for k, v in labels.items() if k != "le"
            ))
            entry = series.setdefault(
                key, {"buckets": [], "sum": None, "count": None}
            )
            if name == family + "_bucket":
                if "le" not in labels:
                    errors.append(
                        f"line {lineno}: histogram bucket without 'le' label"
                    )
                    continue
                le = labels["le"]
                bound = float("inf") if le == "+Inf" else float(le)
                entry["buckets"].append((bound, value, lineno))
            elif name == family + "_sum":
                entry["sum"] = value
            elif name == family + "_count":
                entry["count"] = value
        for key, entry in series.items():
            label_desc = "{%s}" % ",".join(f'{k}="{v}"' for k, v in key)
            buckets = sorted(entry["buckets"])
            if not buckets:
                continue
            bounds = [b for b, _, _ in buckets]
            if len(set(bounds)) != len(bounds):
                errors.append(
                    f"{family}{label_desc}: duplicate bucket bounds"
                )
            if bounds[-1] != float("inf"):
                errors.append(f"{family}{label_desc}: missing +Inf bucket")
            prev = None
            for bound, value, lineno in buckets:
                if prev is not None and value < prev:
                    errors.append(
                        f"line {lineno}: {family}{label_desc} bucket "
                        f'le="{bound}" value {value} < previous {prev} '
                        "(non-monotonic histogram)"
                    )
                prev = value
            if entry["sum"] is None:
                errors.append(f"{family}{label_desc}: missing _sum")
            elif entry["sum"] < 0:
                errors.append(
                    f"{family}{label_desc}: _sum {entry['sum']} < 0 "
                    "(durations cannot be negative)"
                )
            if entry["count"] is None:
                errors.append(f"{family}{label_desc}: missing _count")
            elif bounds[-1] == float("inf") and entry["count"] != buckets[-1][1]:
                errors.append(
                    f"{family}{label_desc}: _count {entry['count']} != "
                    f"+Inf bucket {buckets[-1][1]}"
                )
    # Cross-family paged-KV invariant: a model can never reference more
    # blocks than its pool holds (used > total means broken accounting,
    # e.g. a leaked refcount, not heavy load).
    totals = {
        labels.get("model"): value
        for labels, value, _name, _lineno in samples.get(_KV_TOTAL_FAMILY, [])
    }
    for labels, value, name, lineno in samples.get(_KV_USED_FAMILY, []):
        model = labels.get("model")
        if model in totals and value > totals[model]:
            errors.append(
                f"line {lineno}: {_KV_USED_FAMILY}{{model=\"{model}\"}} "
                f"{value} > {_KV_TOTAL_FAMILY} {totals[model]}"
            )
    # Cross-family compile-plane invariant: every retrace is a distinct
    # dispatch signature seen after the first, so per (model, callable)
    # series retraces can never exceed entries - 1 (a violation means
    # the watcher double-counted compiles or the gauge went stale).
    entries_by_series = {
        (labels.get("model"), labels.get("callable")): value
        for labels, value, _name, _lineno in samples.get(_COMPILE_FAMILY, [])
    }
    for labels, value, name, lineno in samples.get(_RETRACE_FAMILY, []):
        key = (labels.get("model"), labels.get("callable"))
        if key in entries_by_series and value > entries_by_series[key] - 1:
            errors.append(
                f'line {lineno}: {_RETRACE_FAMILY}{{model="{key[0]}",'
                f'callable="{key[1]}"}} {value} > '
                f"{_COMPILE_FAMILY} - 1 ({entries_by_series[key] - 1})"
            )
    # Cross-kind memscope invariant: live can never exceed peak for a
    # (model, pool) cell — peak is by definition the high-water of live,
    # so a violation means the ledger's peak tracking broke.
    mem_kind: Dict[tuple, Dict[str, Tuple[float, int]]] = {}
    for labels, value, _name, lineno in samples.get(_MEM_BYTES_FAMILY, []):
        if {"model", "pool", "kind"} <= set(labels):
            mem_kind.setdefault(
                (labels["model"], labels["pool"]), {}
            )[labels["kind"]] = (value, lineno)
    for (model, pool), kinds in mem_kind.items():
        if "live" in kinds and "peak" in kinds:
            live, lineno = kinds["live"]
            peak, _ = kinds["peak"]
            if live > peak:
                errors.append(
                    f"line {lineno}: {_MEM_BYTES_FAMILY}"
                    f'{{model="{model}",pool="{pool}"}} live {live} > '
                    f"peak {peak}"
                )
    return errors


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if argv:
        with open(argv[0]) as f:
            text = f.read()
    else:
        text = sys.stdin.read()
    errors = check_exposition(text)
    for error in errors:
        print(error, file=sys.stderr)
    if errors:
        print(f"{len(errors)} exposition violation(s)", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
