"""Headline benchmark: serving throughput vs in-process JAX throughput.

Measures the BASELINE.json north-star configuration — the perf_analyzer
equivalent driving the full KServe v2 stack over **gRPC streaming with
``--shared-memory=tpu``** (device-buffer regions, only metadata on the
wire) — against the raw in-process jit-compiled forward on the same model
("≥90% of in-process JAX throughput"). Prints one JSON line per
completed run — the LAST line is the result (interim lines carry
``partial_runs`` so a truncated invocation still records its finished
runs) — of the form:

    {"metric": ..., "value": <client infer/s>, "unit": "infer/s",
     "vs_baseline": <min(worst_ratio/0.90, 2*inproc_p99/serve_p99)>}

vs_baseline >= 1.0 means the serving stack meets BOTH north-star gates
(BASELINE.md): every swept point >= 90% of in-process throughput, and
serving p99 < 2x in-process p99 at the deepest level.

The printed line is deliberately COMPACT (metric, value, unit,
vs_baseline, worst point, runs summary) so the driver's tail capture
parses it; the full per-point matrix is written to
``BENCH_DETAIL.json`` beside this script (round 4's line carried the
whole matrix and overflowed the capture — ``BENCH_r04.json``
``parsed: null``).

The measured configuration is the flagship serving path end-to-end:
BERT-base with the Pallas flash-attention kernel (BENCH_FLASH=1 default)
behind the server's dispatcher-threaded dynamic batcher (pressure-gated
max_queue_delay = TPU_SERVER_BATCH_DELAY_US, default 2000 here; regime
switch + hysteresis per PERF.md), which executes concurrent requests as
batched device dispatches and parks row VIEWS of the shared output so a
whole batch is read back with a single d2h transfer
(utils/tpu_shared_memory.BatchRowView). The in-process comparator is
the same jitted forward driven by N closed-loop threads with full h2d +
readback per request.

Methodology:
  * serving and in-process windows ALTERNATE and the median pair ratio
    is reported per depth, so slow drift of the machine hits both sides
    of a pair alike;
  * every payload is distinct;
  * each depth gets a discard window (thread spin-up, first transfers);
  * dynamic-batch bucket shapes and the jit ladder are pre-warmed so no
    measured window pays an XLA compile.

Coverage beyond the headline (BASELINE "batch 1-128" matrix):
  * BENCH_BATCH_SWEEP (default "1,32,128") re-measures BERT at those
    request batch sizes, one depth each, recorded in detail.batch_sweep;
  * BENCH_RESNET_SWEEP (default "1,4,16") measures ResNet50 at those
    batch sizes (detail.resnet50) through the same serving stack,
    write_once region semantics — every point gates.

The WHOLE gate matrix repeats BENCH_RUNS times (default 3): the
headline vs_baseline gates on POOLED pair ratios (every point's
drift-correlated pairs from all runs, UNTRIMMED pooled median — the
trimmed mean plus outage re-rolls biased the headline upward, ADVICE r5
bench #4; the trimmed variant is recorded alongside) and on a POOLED
tail margin: per-run serving/in-process latency distributions are kept
as mergeable DDSketch quantile sketches (tritonclient_tpu/_sketch.py)
and the deepest level's p99 is computed over the MERGED sketches, with
the worst single run (``p99_margin_min_run``) and per-run history
(``runs``/``vs_baseline_min_run``) recorded alongside — round 4 passed
on one draw with 0.5% headroom on a ±15% link; a robust record needs
the distribution, not a sample (VERDICT r4 #1), and a min-over-runs p99
both understates a recurring tail and lets one clean run mask two bad
ones (the r5 failure mode).

Per-depth breakdown (detail.sweep[d]): compute_infer_per_sec (in-process
dispatch-only, no readback) and d2h_ms (single-stream readback latency)
attribute any ratio miss to compute vs transfer vs dispatch.

Env knobs: BENCH_MODEL (bert_base|simple), BENCH_BATCH (8), BENCH_SEQ
(128), BENCH_RUNS (3), BENCH_SECONDS (10 multi-run / 24 single, per
depth per side), BENCH_WINDOWS (6 / 8), BENCH_CONCURRENCY ("8,16,32"),
BENCH_SHM (tpu|system|none), BENCH_STREAMING (1), BENCH_FLASH (1),
BENCH_BATCHING (1), BENCH_BATCH_SWEEP ("1,32,128"; "" disables),
BENCH_RESNET_SWEEP ("1,4,16"; "" disables), BENCH_ASYNC_WINDOW (0 —
sliding-window single-client mode), BENCH_OVERLOAD (1 — the seeded
overload scenario gating the deadline path: past-deadline probes must
504 in <5 ms p99 and in-deadline traffic must hold <=1.3x its
no-overload p99, folded into vs_baseline as overload_margin;
BENCH_OVERLOAD_{FG,BULK,REQS,PROBES,PROBE_REQS} size it),
BENCH_DETAIL_PATH (BENCH_DETAIL.json).
"""

import json
import os
import sys
import time

import numpy as np

# Dynamic batching IS the measured serving configuration (one dispatch +
# one shared readback per formed batch); the pressure gate keeps it out
# of the way at light load. BENCH_BATCHING=0 measures the unbatched path.
if os.environ.get("BENCH_BATCHING", "1") == "1":
    os.environ.setdefault("TPU_SERVER_DYNAMIC_BATCH", "1")
    # Mild rate-gated hold. With the dispatcher-threaded batcher,
    # natural batching (requests accumulating behind the in-flight
    # dispatch) does most of the amortization; long holds measured as
    # pure added latency at moderate depth (r5 A/B: 8 ms cost ~6% at
    # c16, 2 ms was neutral-to-positive at c32).
    os.environ.setdefault("TPU_SERVER_BATCH_DELAY_US", "2000")
else:
    os.environ["TPU_SERVER_DYNAMIC_BATCH"] = "0"

# Both measured paths run tens of threads in one interpreter; CPython's
# default 5 ms GIL switch interval starves whichever thread must dispatch
# next (measured: server-side jit dispatch wall 3.6 ms -> 0.37 ms at
# depth 16 with a 0.2 ms interval). Applies to serving AND in-process
# sides alike, so the ratio stays honest.
sys.setswitchinterval(float(os.environ.get("BENCH_GIL_SWITCH_S", "0.0002")))


def _pipelined_inprocess(dispatch, readback, payloads, seconds, depth):
    """`depth` threads each running full request loops (h2d+exec+d2h).

    Symmetric with the serving measurement: device RPCs overlap across
    threads exactly the way the serving workers overlap them.
    """
    from concurrent.futures import ThreadPoolExecutor

    readback(dispatch(payloads[0]))  # warmup/compile
    stop = [False]
    counts = [0] * depth
    latencies = []

    def worker(wid):
        i = wid
        local = []
        while not stop[0]:
            t0 = time.perf_counter()
            readback(dispatch(payloads[i % len(payloads)]))
            local.append(time.perf_counter() - t0)
            counts[wid] += 1
            i += depth
        latencies.extend(local)

    start = time.perf_counter()
    with ThreadPoolExecutor(max_workers=depth) as pool:
        futs = [pool.submit(worker, w) for w in range(depth)]
        time.sleep(seconds)
        stop[0] = True
        for f in futs:
            f.result()
    elapsed = time.perf_counter() - start
    return sum(counts) / elapsed, sorted(latencies)


def _compute_only(dispatch, payloads, seconds, depth):
    """Dispatch-only throughput: device pipeline kept full, no readback."""
    import jax
    from concurrent.futures import ThreadPoolExecutor

    stop = [False]
    counts = [0] * depth

    def worker(wid):
        i = wid
        while not stop[0]:
            jax.block_until_ready(dispatch(payloads[i % len(payloads)]))
            counts[wid] += 1
            i += depth

    start = time.perf_counter()
    with ThreadPoolExecutor(max_workers=depth) as pool:
        futs = [pool.submit(worker, w) for w in range(depth)]
        time.sleep(seconds)
        stop[0] = True
        for f in futs:
            f.result()
    return sum(counts) / (time.perf_counter() - start)


def _d2h_ms(dispatch, readback, payloads, n=12):
    """Single-stream readback latency (compute finished before timing)."""
    import jax

    lats = []
    for i in range(n):
        out = jax.block_until_ready(dispatch(payloads[i % len(payloads)]))
        t0 = time.perf_counter()
        readback(out)
        lats.append((time.perf_counter() - t0) * 1000)
    lats.sort()
    return lats[len(lats) // 2]


# -- absolute MFU accounting ------------------------------------------------ #


def _analytic_fwd_flops(model_name, batch, seq, d_model=0, n_layers=0):
    """Analytic forward FLOPs for ONE inference request (a batch of
    ``batch`` samples), from model geometry — not a profiler count.

    * bert_base: per layer per token, 2 FLOPs per weight over the four
      HxH attention projections and the HxI/IxH FFN pair, plus the
      4*seq*H score/value matmuls (QK^T and AV).
    * resnet50: the canonical 224x224 forward — 2.05 GMACs, 2 FLOPs per
      MAC — as a constant; conv-by-conv accounting adds nothing here.
    * gpt: same transformer accounting as bert with I=4H, parameterized
      by (d_model, n_layers) and ``seq`` = mean context length, so the
      genai/engine benches can reuse it for tokens/s -> FLOPs/s.

    Returns 0 for models whose FLOPs are not meaningful (`simple`), which
    suppresses the mfu fields rather than reporting noise.
    """
    if model_name == "bert_base":
        L, H, I = 12, 768, 3072
        per_token = 2 * (4 * H * H + 2 * H * I) + 4 * seq * H
        return batch * seq * L * per_token
    if model_name == "resnet50":
        return batch * 2 * 2_050_000_000
    if model_name == "gpt" and d_model and n_layers:
        per_token = 2 * 12 * d_model * d_model + 4 * seq * d_model
        return batch * seq * n_layers * per_token
    return 0


# Published per-chip peaks, keyed by jax's ``device_kind``. A TPU v5e
# (which jax reports as "TPU v5 lite"): 197 TFLOP/s bf16 and 819 GB/s of
# HBM bandwidth (Google Cloud documentation, "TPU v5e"). A device that is
# not listed is an error, never a default.
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
}


def _peak_flops():
    """Peak bf16 FLOP/s the MFU fields divide by, from ``DEVICE_PEAKS``.

    None when the run asked for the CPU backend (``JAX_PLATFORMS=cpu``):
    the ``mfu`` fields are then left out, because a host figure under that
    name would not be a device metric. Any other device must be listed —
    a backend JAX fell back to unasked is an error here, not a default.
    """
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return None
    import jax

    kind = jax.devices()[0].device_kind
    if kind not in DEVICE_PEAKS:
        raise SystemExit(
            f"bench: no published peak for device_kind '{kind}'; add it to "
            "DEVICE_PEAKS with its source"
        )
    return DEVICE_PEAKS[kind]["bf16_flops"]


def _payload_factory(model_name, batch, seq):
    """Payload maker only — no model construction (the batch sweep reuses
    the already-built model instead of a fresh 110M-param device init per
    sweep point)."""
    if model_name == "bert_base":
        return lambda: np.random.randint(0, 30000, (batch, seq)).astype(
            np.int32
        )
    if model_name == "resnet50":
        return lambda: np.random.rand(batch, 224, 224, 3).astype(np.float32)
    return lambda: np.random.randint(0, 100, (batch, 16)).astype(np.int32)


def _make_model(model_name, batch, seq):
    """model, payload factory, dispatch fn, shape overrides."""
    if model_name == "bert_base":
        from tritonclient_tpu.models.bert import BertBaseModel

        model = BertBaseModel(
            use_flash_attention=os.environ.get("BENCH_FLASH", "1") == "1"
        )

        dispatch = lambda p: model._fwd(model._params, p)  # noqa: E731
        return (model, _payload_factory(model_name, batch, seq), dispatch,
                {"INPUT_IDS": seq})
    if model_name == "resnet50":
        from tritonclient_tpu.models.resnet import ResNet50Model

        model = ResNet50Model()
        dispatch = lambda p: model._fwd(model._params, p)  # noqa: E731
        return model, _payload_factory(model_name, batch, seq), dispatch, None
    from tritonclient_tpu.models.simple import SimpleModel, _add_sub

    model = SimpleModel()
    dispatch = lambda p: _add_sub(p, p)  # noqa: E731
    return model, _payload_factory(model_name, batch, seq), dispatch, None


def _prewarm_buckets(model, dispatch, payload, batch):
    """Compile the dynamic batcher's bucket shapes up front.

    The batcher pads a formed batch (total rows = k*batch for k >= 2) up
    to the next power of two, so the executed shapes are those pow2
    ceilings — not batch*2^k, which diverges for non-pow2 batch sizes.
    """
    import jax

    if os.environ.get("TPU_SERVER_DYNAMIC_BATCH", "0") != "1":
        return
    cap = getattr(model, "max_batch_size", 0)
    sample = payload()
    buckets = {
        1 << (k * batch - 1).bit_length()
        for k in range(2, max(cap // batch, 1) + 1)
    }
    for rows in sorted(buckets):
        shape = (rows,) + sample.shape[1:]
        jax.block_until_ready(dispatch(np.zeros(shape, sample.dtype)))


def _measure_depths(model, payload, dispatch, shape_overrides, batch,
                    depths, seconds, n_windows, shm_mode, streaming,
                    async_window, server, record_aux=True,
                    write_once=False, flops_per_infer=0):
    """Alternating-window serving/in-process measurement at each depth.

    ``write_once`` (reference --shared-memory semantics: inputs written to
    the region once at setup) also stages the in-process comparator's
    payloads on device, so BOTH sides measure compute+readback rather
    than the link's h2d bandwidth — the honest pairing for models whose
    inputs dwarf their outputs (resnet50).
    """
    import contextlib
    from statistics import median

    import jax

    from tritonclient_tpu.perf_analyzer import PerfAnalyzer
    from tritonclient_tpu.perf_analyzer._stats import percentile

    payloads = [payload() for _ in range(32)]
    if write_once:
        payloads = [jax.device_put(p) for p in payloads]
        jax.block_until_ready(payloads)
    analyzer = PerfAnalyzer(
        server.grpc_address,
        model.name,
        protocol="grpc",
        batch_size=batch,
        shared_memory=shm_mode,
        streaming=streaming,
        async_window=async_window,
        read_outputs=True,
        measurement_interval_s=seconds / n_windows,
        warmup_s=1.0,
        shape_overrides=shape_overrides,
        write_once=write_once,
    )
    class _Acc:
        __slots__ = ("pairs", "inproc", "serve", "ilat", "slat",
                     "errors", "execs", "infers")

        def __init__(self):
            self.pairs, self.inproc, self.serve = [], [], []
            self.ilat, self.slat = [], []
            self.errors = self.execs = self.infers = 0

    def record(acc, concurrency, serving_window):
        ips, lat = _pipelined_inprocess(
            dispatch, jax.device_get, payloads,
            seconds / n_windows, concurrency,
        )
        acc.inproc.append(ips)
        acc.ilat.extend(lat)
        st0 = server.core.model_statistics(model.name)[0]
        window = serving_window(seconds / n_windows)
        st1 = server.core.model_statistics(model.name)[0]
        summary = window.summary()
        serve_ips = summary["throughput_infer_per_sec"]
        acc.serve.append(serve_ips)
        if ips:
            acc.pairs.append(serve_ips / ips)
        acc.slat.extend([ns / 1000 for ns in window.latencies_ns])
        acc.errors += summary["errors"]
        acc.execs += st1["execution_count"] - st0["execution_count"]
        acc.infers += st1["inference_count"] - st0["inference_count"]

    def finalize(acc, concurrency):
        from tritonclient_tpu._sketch import LatencySketch

        acc.ilat.sort()
        acc.slat.sort()
        # Mergeable latency sketches (microseconds, <=2% relative error):
        # the aggregate gate pools TAIL latency across runs by MERGING
        # these — pooled p99 over the pooled sample — instead of taking a
        # min/median over per-run p99s (ADVICE r5 bench #4 / ROADMAP
        # item 1: a single-window min-over-runs hid the c32 blowup).
        serving_sketch = LatencySketch()
        serving_sketch.extend(acc.slat)
        inproc_sketch = LatencySketch()
        inproc_sketch.extend(v * 1e6 for v in acc.ilat)
        entry = {
            "serving_sketch": serving_sketch.to_dict(),
            "inprocess_sketch": inproc_sketch.to_dict(),
            "serving_infer_per_sec": round(median(acc.serve), 2),
            "inprocess_infer_per_sec": round(median(acc.inproc), 2),
            "ratio": round(_trimmed_mean(acc.pairs), 4),
            # Raw drift-correlated pairs: the aggregate gate pools these
            # across runs (3x the sample per point beats any single
            # run's estimator on a ±15% link).
            "pairs": [round(p, 4) for p in acc.pairs],
            "errors": acc.errors,
            "serving_p50_latency_ms": round(
                percentile(acc.slat, 50) / 1000, 2
            ),
            "serving_p99_latency_ms": round(
                percentile(acc.slat, 99) / 1000, 2
            ),
            "inprocess_p50_latency_ms": round(
                percentile(acc.ilat, 50) * 1e3, 2
            ),
            "inprocess_p99_latency_ms": round(
                percentile(acc.ilat, 99) * 1e3, 2
            ),
            "avg_dynamic_batch": round(
                acc.infers / acc.execs, 2
            ) if acc.execs else 0.0,
        }
        peak = _peak_flops()
        if flops_per_infer and peak:
            # Absolute MFU per point: achieved FLOPs/s over the device's
            # published peak, serving and in-process sides.
            entry["mfu_serving"] = round(
                entry["serving_infer_per_sec"] * flops_per_infer / peak, 4
            )
            entry["mfu_inprocess"] = round(
                entry["inprocess_infer_per_sec"] * flops_per_infer / peak, 4
            )
        from tritonclient_tpu import _memscope

        if _memscope.enabled():
            # Device-memory high-water beside MFU: peak KV-pool bytes and
            # peak total device bytes for this model over the sweep, so a
            # throughput point can be correlated with the memory it cost.
            entry.update(_memscope.peaks(model.name))
        if record_aux:
            # Attribution aux: pure-compute ceiling and raw d2h latency
            # (VERDICT r3 #5 — makes ratio misses attributable).
            entry["compute_infer_per_sec"] = round(
                _compute_only(dispatch, payloads, 2.0, concurrency), 2
            )
            entry["d2h_ms"] = round(
                _d2h_ms(dispatch, jax.device_get, payloads), 2
            )
        return entry

    per_depth = {}
    if async_window:
        # One-shot mode has no persistent sessions; depth-major order.
        for concurrency in depths:
            acc = _Acc()

            def one_shot(interval_s, c=concurrency):
                analyzer.measurement_interval_s = interval_s
                return analyzer.measure(c)

            one_shot(2.0)  # discard
            for _ in range(n_windows):
                record(acc, concurrency, one_shot)
            per_depth[concurrency] = finalize(acc, concurrency)
        return per_depth

    # Interleaved sweep: sessions for every depth live at once and the
    # window pairs round-robin across depths, so a slow phase of the
    # machine lands on every depth's median and not on one. Footprint
    # note: peak region count is the SUM of all depths' workers (56
    # in+out regions for the default sweep) rather than the deepest
    # depth — fine for these KB-scale regions; cap BENCH_CONCURRENCY for
    # huge outputs.
    sessions = {}
    accs = {d: _Acc() for d in depths}
    with contextlib.ExitStack() as stack:
        for d in depths:
            sessions[d] = stack.enter_context(analyzer.session(d))
            # Discard window: thread spin-up, stream setup, first
            # transfers — no real window pays them.
            sessions[d].measure(interval_s=2.0)
        for _ in range(n_windows):
            for d in depths:
                record(
                    accs[d], d,
                    lambda interval_s, dd=d: sessions[dd].measure(
                        interval_s=interval_s
                    ),
                )
    for d in depths:
        per_depth[d] = finalize(accs[d], d)
    return per_depth


def _overload_point(server, model_name, payload):
    """Seeded overload scenario: arrival rate > service rate with mixed
    deadlines, gating the deadline-aware scheduling path end to end.

    Three traffic classes against the live serving stack (gRPC unary,
    wire data — the overload is a queue-policy measurement, not a
    bandwidth one):

      * BULK: no-deadline closed-loop threads far past capacity — the
        deep backlog that used to stretch every request's tail (the
        round-5 c32 failure mode);
      * FOREGROUND: deadline-carrying requests with a generous budget —
        EDF orders them ahead of the no-deadline backlog, so their p99
        must hold near the no-overload baseline (<= 1.3x);
      * PROBES: deadline budgets far below one batch service time —
        admission control must answer each with a fast 504 (client-
        observed p99 < 5 ms; client_timeout explicitly roomy so only the
        SERVER's shed is measured, not a client-side abort).

    Phase A measures the foreground class at CAPACITY (a light bulk load
    keeps the batcher in its busy regime — offered ~ service rate, queue
    shallow; it also warms the admission EWMA); phase B floods it with
    bulk far past the service rate. Without deadline-aware scheduling
    the foreground would wait out the whole phase-B backlog (the 245 ms
    r5 tail); with it, its p99 must stay within 1.3x of phase A.
    Returns the recorded point incl. ``overload_margin`` =
    min(5ms / shed_p99, 1.3 x base_p99 / overload_p99) — >= 1.0 means
    both halves of the gate hold.
    """
    import threading

    import tritonclient_tpu.grpc as grpcclient
    from tritonclient_tpu.perf_analyzer._stats import (
        is_shed_error,
        percentile,
    )

    fg_n = int(os.environ.get("BENCH_OVERLOAD_FG", "8"))
    bulk_n = int(os.environ.get("BENCH_OVERLOAD_BULK", "24"))
    base_bulk_n = int(os.environ.get("BENCH_OVERLOAD_BASE_BULK", "4"))
    per_fg = int(os.environ.get("BENCH_OVERLOAD_REQS", "14"))
    # One probe thread by default: the backlog pressure comes from the
    # bulk class, and the <5 ms shed gate measures the SERVER's fast-504
    # path — a storm of probe threads would measure client-side GIL
    # scheduling instead. >=100 sequential probes (a shed costs ~1-2 ms
    # each) so the nearest-rank p99 is the 2nd-worst sample, not the
    # worst single GIL-scheduling draw.
    probe_n = int(os.environ.get("BENCH_OVERLOAD_PROBES", "1"))
    per_probe = int(os.environ.get("BENCH_OVERLOAD_PROBE_REQS", "120"))
    sample = payload()

    def run_class(n_threads, per_thread, timeout_us, lat_sink, shed_sink,
                  err_sink):
        def worker():
            client = grpcclient.InferenceServerClient(server.grpc_address)
            try:
                # Warm the channel off the clock: the first RPC on a fresh
                # gRPC channel pays connection setup, which is not a
                # scheduling latency.
                client.is_server_ready()
                for _ in range(per_thread):
                    inp = grpcclient.InferInput(
                        "INPUT_IDS", list(sample.shape), "INT32"
                    )
                    inp.set_data_from_numpy(payload())
                    t0 = time.perf_counter()
                    try:
                        client.infer(
                            model_name, [inp], timeout=timeout_us,
                            client_timeout=60.0,
                        )
                        lat_sink.append(time.perf_counter() - t0)
                    except Exception as e:
                        if is_shed_error(e):
                            shed_sink.append(time.perf_counter() - t0)
                        else:
                            err_sink.append(str(e))
            finally:
                client.close()

        threads = [threading.Thread(target=worker) for _ in range(n_threads)]
        for t in threads:
            t.start()
        return threads

    def join(threads):
        for t in threads:
            t.join(timeout=300)

    errors = []
    # Phase A: foreground at capacity — a light bulk load keeps the
    # batcher in its busy regime so the comparison isolates QUEUE POLICY
    # from the idle-vs-busy shift (and warms the admission EWMA).
    base_lat, base_shed = [], []
    base_bulk_lat, base_bulk_shed = [], []
    base_bulk = run_class(base_bulk_n, per_fg, None, base_bulk_lat,
                          base_bulk_shed, errors)
    join(run_class(fg_n, per_fg, 10_000_000, base_lat, base_shed, errors))
    join(base_bulk)
    # Phase B: deep no-deadline backlog + the same foreground + probes.
    bulk_lat, bulk_shed = [], []
    fg_lat, fg_shed = [], []
    probe_lat, probe_shed = [], []
    bulk_threads = run_class(bulk_n, per_fg, None, bulk_lat, bulk_shed,
                             errors)
    time.sleep(0.25)  # let the backlog stand up before probing it
    fg_threads = run_class(fg_n, per_fg, 10_000_000, fg_lat, fg_shed,
                           errors)
    probe_threads = run_class(probe_n, per_probe, 2_000, probe_lat,
                              probe_shed, errors)
    join(probe_threads)
    join(fg_threads)
    join(bulk_threads)

    base_p99_ms = percentile(sorted(base_lat), 99) * 1000
    fg_all = sorted(fg_lat)
    fg_p99_ms = percentile(fg_all, 99) * 1000 if fg_all else 0.0
    shed_sorted = sorted(probe_shed)
    shed_p99_ms = percentile(shed_sorted, 99) * 1000 if shed_sorted else 0.0
    # Both halves of the acceptance gate as margins (>= 1.0 passes):
    # every past-deadline probe must have been SHED (not served late),
    # fast; in-deadline traffic must hold its no-overload p99.
    served_probes = len(probe_lat)
    if len(probe_shed) < max(probe_n * per_probe // 2, 1):
        shed_margin = 0.0  # the shed path did not engage: an honest fail
    else:
        shed_margin = 5.0 / max(shed_p99_ms, 1e-9)
    hold_margin = (
        1.3 * base_p99_ms / max(fg_p99_ms, 1e-9) if fg_all else 0.0
    )
    return {
        "base_p99_ms": round(base_p99_ms, 2),
        "overload_p99_ms": round(fg_p99_ms, 2),
        "shed_p99_ms": round(shed_p99_ms, 3),
        "sheds": len(probe_shed) + len(fg_shed) + len(bulk_shed),
        "probe_sheds": len(probe_shed),
        "probes_served": served_probes,
        "fg_served": len(fg_lat),
        "bulk_served": len(bulk_lat),
        "shed_margin": round(min(shed_margin, 99.0), 4),
        "hold_margin": round(min(hold_margin, 99.0), 4),
        "overload_margin": round(min(shed_margin, hold_margin, 99.0), 4),
        "errors": len(errors),
        "error_sample": errors[:3],
    }


def _trimmed_mean(vals, min_trim=1):
    """Trimmed mean shared by per-point ratios and the pooled gate:
    drops max(min_trim, ~10% of n) pairs per end for n >= 4, then
    averages the rest — uses every surviving pair instead of only the
    middle one (tighter than the median under drift noise) while
    staying immune to outlier windows. The pooled gate passes
    min_trim = number of runs, preserving one-stall-PER-RUN immunity
    (two ~hourly stalls landing in different runs at the same point
    must both be trimmable)."""
    if not vals:
        return 0.0
    s = sorted(vals)
    if len(s) >= 4:
        k = min(max(min_trim, len(s) // 10), (len(s) - 1) // 2)
        s = s[k:-k]
    return sum(s) / len(s)


def _shielded(point_fn):
    """Stall shield: short aux points have only a few window pairs, so
    one multi-second stall can corrupt the median. Two triggers, both
    re-measured once with the
    retry recorded verbatim:
      * ratio below any structurally possible value (<0.6);
      * the stall signature — serving p99 an order of magnitude above
        its own p50 while the medians sit at parity — which is a single
        wedged window, not a throughput property (a real serving
        regression moves p50 too).
    """
    entry = point_fn()
    stall = (
        entry["ratio"] < 0.9
        and entry["serving_p99_latency_ms"]
        > 8 * max(entry["serving_p50_latency_ms"], 1e-9)
    )
    if entry["ratio"] < 0.6 or stall:
        retried = point_fn()
        retried["outage_retry"] = True
        retried["first_attempt"] = {
            "ratio": entry["ratio"],
            "serving_p50_latency_ms": entry["serving_p50_latency_ms"],
            "serving_p99_latency_ms": entry["serving_p99_latency_ms"],
        }
        entry = retried
    return entry


def _log(msg):
    """Progress marker on stderr: stdout carries only the result JSON
    lines (one per completed run; the LAST line is the result — interim
    lines are marked ``partial_runs``); a wedged or slow run must be
    attributable from stderr."""
    print(f"[bench +{time.perf_counter() - _T0:.0f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()


def _run_gate_matrix(run_idx, server, bert, rmodel, cfg):
    """One full pass over the gate matrix; returns the run record."""
    model, payload, dispatch, overrides = bert
    _log(f"run {run_idx + 1}: depth sweep {cfg['depths']}")
    per_depth = _measure_depths(
        model, payload, dispatch, overrides, cfg["batch"], cfg["depths"],
        cfg["seconds"], cfg["n_windows"], cfg["shm"], cfg["streaming"],
        cfg["async_window"], server, record_aux=(run_idx == 0),
        flops_per_infer=_analytic_fwd_flops(
            model.name, cfg["batch"], cfg["seq"]
        ),
    )

    # --- BERT batch matrix (BASELINE: "batch 1-128") ------------------------
    batch_detail = {}
    if cfg["batch_sweep"] and not cfg["async_window"]:
        for b in cfg["batch_sweep"]:
            if b == cfg["batch"]:
                continue
            _log(f"run {run_idx + 1}: bert batch {b}")
            payload_b = _payload_factory("bert_base", b, cfg["seq"])
            batch_detail[str(b)] = _shielded(lambda pb=payload_b, bb=b: (
                _measure_depths(
                    model, pb, dispatch, overrides, bb,
                    [cfg["sweep_depth"]], cfg["sweep_secs"], 4, cfg["shm"],
                    cfg["streaming"], False, server, record_aux=False,
                    flops_per_infer=_analytic_fwd_flops(
                        "bert_base", bb, cfg["seq"]
                    ),
                )[cfg["sweep_depth"]]
            ))

    # --- ResNet50 batch sweep (VERDICT r4 #3: batching as a first-class
    # axis for the image path too) -------------------------------------------
    resnet_detail = {}
    if rmodel is not None:
        rm, _, rdispatch, roverrides = rmodel
        rdepth = cfg["resnet_depth"]
        for rb in cfg["resnet_sweep"]:
            _log(f"run {run_idx + 1}: resnet batch {rb}")
            rpayload = _payload_factory("resnet50", rb, cfg["seq"])
            resnet_detail[str(rb)] = _shielded(lambda rp=rpayload, b=rb: (
                _measure_depths(
                    rm, rp, rdispatch, roverrides, b, [rdepth],
                    cfg["resnet_secs"], 5, cfg["shm"], cfg["streaming"],
                    False, server, record_aux=False,
                    write_once=cfg["resnet_write_once"],
                    flops_per_infer=_analytic_fwd_flops("resnet50", b, 0),
                )[rdepth]
            ))

    # --- overload scenario (deadline-aware scheduling gate) -----------------
    overload = {}
    if cfg["overload"]:
        _log(f"run {run_idx + 1}: overload scenario (EDF + admission)")
        overload = _overload_point(server, model.name, payload)
        _log(
            f"run {run_idx + 1}: overload margin "
            f"{overload['overload_margin']} (shed {overload['shed_margin']}"
            f" / hold {overload['hold_margin']})"
        )

    # --- gates --------------------------------------------------------------
    # Gate 1 (throughput): EVERY measured point >= 0.90 of in-process.
    gate_points = {f"c{d}": per_depth[d]["ratio"] for d in per_depth}
    for b, entry in batch_detail.items():
        gate_points[f"b{b}"] = entry["ratio"]
    for b, entry in resnet_detail.items():
        gate_points[f"resnet_b{b}"] = entry["ratio"]
    worst_point = min(gate_points, key=lambda k: gate_points[k])
    worst_ratio = gate_points[worst_point]
    # Gate 2 (tail): serving p99 < 2x in-process p99 at the deepest level.
    deepest = per_depth[max(per_depth)]
    p99_margin = (
        2.0 * deepest["inprocess_p99_latency_ms"]
        / max(deepest["serving_p99_latency_ms"], 1e-9)
    )
    headline = per_depth[max(per_depth)]
    errors = sum(per_depth[d]["errors"] for d in per_depth)
    errors += sum(e["errors"] for e in batch_detail.values())
    errors += sum(e["errors"] for e in resnet_detail.values())
    errors += overload.get("errors", 0)
    # Gate 3 (overload): past-deadline requests 504 in < 5 ms p99 AND
    # in-deadline traffic holds its no-overload p99 within 1.3x, both
    # expressed as margins (>= 1.0 passes) and folded into vs_baseline.
    vs = min(worst_ratio / 0.90, p99_margin)
    if overload:
        vs = min(vs, overload["overload_margin"])
    return {
        "run": run_idx + 1,
        "vs_baseline": round(vs, 4),
        "value": headline["serving_infer_per_sec"],
        "worst_point": worst_point,
        "worst_ratio": worst_ratio,
        "p99_margin": round(p99_margin, 4),
        "errors": errors,
        "sweep": {str(d): per_depth[d] for d in per_depth},
        "batch_sweep": batch_detail,
        "resnet50": resnet_detail,
        "overload": overload,
    }


def main():
    model_name = os.environ.get("BENCH_MODEL", "bert_base")
    n_runs = int(os.environ.get("BENCH_RUNS", "3"))
    multi = n_runs > 1
    cfg = {
        "batch": int(os.environ.get("BENCH_BATCH", "8")),
        "seq": int(os.environ.get("BENCH_SEQ", "128")),
        # Multi-run defaults trade per-run window count for run count:
        # 3 x 10 s samples more of the machine's drift than 1 x 24 s; the
        # headline gates on POOLED pair ratios, with the per-run history
        # and worst run (vs_baseline_min_run) recorded beside it.
        "seconds": float(
            os.environ.get("BENCH_SECONDS", "10" if multi else "24")
        ),
        "n_windows": int(
            os.environ.get("BENCH_WINDOWS", "6" if multi else "8")
        ),
        "depths": [
            int(x)
            for x in os.environ.get(
                "BENCH_CONCURRENCY", os.environ.get("BENCH_SWEEP", "8,16,32")
            ).split(",")
        ],
        "shm": os.environ.get("BENCH_SHM", "tpu"),
        "async_window": os.environ.get("BENCH_ASYNC_WINDOW", "0") == "1",
        "streaming": os.environ.get("BENCH_STREAMING", "1") == "1",
        "batch_sweep": [
            int(x)
            for x in os.environ.get("BENCH_BATCH_SWEEP", "1,32,128").split(",")
            if x
        ],
        "sweep_depth": int(os.environ.get("BENCH_BATCH_SWEEP_DEPTH", "16")),
        "sweep_secs": float(
            os.environ.get("BENCH_BATCH_SWEEP_SECONDS", "7" if multi else "12")
        ),
        "resnet_sweep": [
            int(x)
            for x in os.environ.get("BENCH_RESNET_SWEEP", "1,4,16").split(",")
            if x
        ],
        "resnet_depth": int(os.environ.get("BENCH_RESNET_DEPTH", "8")),
        "resnet_secs": float(
            os.environ.get("BENCH_RESNET_SECONDS", "7" if multi else "18")
        ),
        "resnet_write_once": os.environ.get(
            "BENCH_RESNET_WRITE_ONCE", "1") == "1",
        # Deadline-aware scheduling gate: the seeded overload scenario
        # (BENCH_OVERLOAD=0 disables; bert-only — the point drives the
        # headline model's wire shape).
        "overload": os.environ.get("BENCH_OVERLOAD", "1") == "1",
    }
    if cfg["async_window"] and cfg["shm"] != "tpu":
        print("BENCH_ASYNC_WINDOW=1 requires BENCH_SHM=tpu", file=sys.stderr)
        sys.exit(2)
    if model_name != "bert_base":
        cfg["batch_sweep"] = []
        cfg["resnet_sweep"] = []
        cfg["overload"] = False

    import jax

    from tritonclient_tpu import _compile_cache
    from tritonclient_tpu.server import InferenceServer

    _compile_cache.configure()
    model, payload, dispatch, overrides = _make_model(
        model_name, cfg["batch"], cfg["seq"]
    )
    _log("warmup: bert model + buckets")
    model.warmup()
    _prewarm_buckets(model, dispatch, payload, cfg["batch"])
    # Pre-compile every swept request shape + its batcher buckets once —
    # no measured window (in any run) may pay a compile.
    if cfg["async_window"]:
        cfg["batch_sweep"] = []  # not measured in one-shot mode; don't warm
    for b in cfg["batch_sweep"]:
        if b != cfg["batch"]:
            jax.block_until_ready(dispatch(np.zeros((b, cfg["seq"]), np.int32)))
            _prewarm_buckets(
                model, dispatch, _payload_factory(model_name, b, cfg["seq"]), b
            )
    bert = (model, payload, dispatch, overrides)

    rmodel = None
    models = [model]
    if cfg["resnet_sweep"] and not cfg["async_window"]:
        _log("warmup: resnet50 model + batch shapes")
        rm, _, rdispatch, roverrides = _make_model("resnet50", 1, cfg["seq"])
        rm.warmup()
        for rb in cfg["resnet_sweep"]:
            jax.block_until_ready(
                rdispatch(np.zeros((rb, 224, 224, 3), np.float32))
            )
            _prewarm_buckets(
                rm, rdispatch, _payload_factory("resnet50", rb, cfg["seq"]), rb
            )
        rmodel = (rm, None, rdispatch, roverrides)
        models.append(rm)

    runs = []
    detail_path = os.environ.get(
        "BENCH_DETAIL_PATH",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_DETAIL.json"),
    )
    with InferenceServer(models=models, http=False) as server:
        for run_idx in range(n_runs):
            runs.append(_run_gate_matrix(run_idx, server, bert, rmodel, cfg))
            # Emit after EVERY completed run (same schema, flushed): if
            # an external timeout kills a later run, the last complete
            # line still carries a parseable result for the runs that
            # finished. The final line supersedes the interim ones.
            _emit(runs, cfg, model_name, n_runs, detail_path, jax)


def _emit(runs, cfg, model_name, n_runs, detail_path, jax):
    from statistics import median

    from tritonclient_tpu._sketch import LatencySketch

    # Aggregate gate: POOL each gate point's drift-correlated pairs
    # across all runs (3x the sample of any single run). Two estimators
    # are recorded; the GATE uses the untrimmed pooled median (ADVICE r5
    # bench #4: the trimmed mean plus one-sided outage re-rolls biased
    # the headline upward — the median of the pooled pairs is the
    # honest center), with the trimmed mean kept alongside for
    # comparability with earlier rounds. The per-run history and per-run
    # minimum ship alongside, so "the typical draw" and "every draw" are
    # both visible (VERDICT r4 #1).
    pooled_pairs = {}
    for r in runs:
        for d, e in r["sweep"].items():
            pooled_pairs.setdefault(f"c{d}", []).extend(e["pairs"])
        for b, e in r["batch_sweep"].items():
            pooled_pairs.setdefault(f"b{b}", []).extend(e["pairs"])
        for b, e in r["resnet50"].items():
            pooled_pairs.setdefault(f"resnet_b{b}", []).extend(e["pairs"])
    pooled_gate = {
        k: round(median(v), 4) if v else 0.0
        for k, v in pooled_pairs.items()
    }
    pooled_gate_trimmed = {
        k: round(_trimmed_mean(v, min_trim=len(runs)), 4)
        for k, v in pooled_pairs.items()
    }
    pooled_worst_point = min(pooled_gate, key=lambda k: pooled_gate[k])
    pooled_worst = pooled_gate[pooled_worst_point]
    # Pooled tail gate: p99 over the POOLED latency sample at the deepest
    # level, from merged per-run sketches (exact bucket-wise merge) —
    # min-over-runs of single-run p99s both understates a recurring tail
    # (each run's p99 is a noisy draw) and lets one clean run mask two
    # bad ones. The worst single run stays recorded (p99_margin_min_run)
    # so a per-run blowup remains visible next to the pooled verdict.
    deepest = str(max(int(d) for d in runs[0]["sweep"]))
    serve_pooled = LatencySketch.merged(
        LatencySketch.from_dict(r["sweep"][deepest]["serving_sketch"])
        for r in runs if deepest in r["sweep"]
    )
    inproc_pooled = LatencySketch.merged(
        LatencySketch.from_dict(r["sweep"][deepest]["inprocess_sketch"])
        for r in runs if deepest in r["sweep"]
    )
    serve_p99_us = serve_pooled.quantile(0.99)
    inproc_p99_us = inproc_pooled.quantile(0.99)
    p99_margin_pooled = round(
        2.0 * inproc_p99_us / max(serve_p99_us, 1e-9), 4
    )
    p99_margin_min = min(r["p99_margin"] for r in runs)
    # Overload gate pooled like the others: the median per-run margin is
    # the gate, the worst run stays recorded beside it.
    overload_margins = [
        r["overload"]["overload_margin"] for r in runs if r.get("overload")
    ]
    overload_pooled = (
        round(median(overload_margins), 4) if overload_margins else None
    )
    vs_baseline = round(min(pooled_worst / 0.90, p99_margin_pooled), 4)
    if overload_pooled is not None:
        vs_baseline = round(min(vs_baseline, overload_pooled), 4)
    vs_min = min(r["vs_baseline"] for r in runs)
    worst = min(runs, key=lambda r: r["vs_baseline"])
    detail = {
        "runs": runs,
        "pooled_gate": pooled_gate,
        "pooled_gate_trimmed": pooled_gate_trimmed,
        "pooled_p99": {
            "depth": int(deepest),
            "serving_p99_ms": round(serve_p99_us / 1000, 2),
            "inprocess_p99_ms": round(inproc_p99_us / 1000, 2),
            "serving_samples": serve_pooled.count,
            "inprocess_samples": inproc_pooled.count,
        },
        "config": {
            "n_runs": n_runs,
            "device_kind": jax.devices()[0].device_kind,
            "peak_flops": _peak_flops(),
            "flops_per_infer": _analytic_fwd_flops(
                model_name, cfg["batch"], cfg["seq"]
            ),
            "shared_memory": cfg["shm"],
            "streaming": cfg["streaming"],
            "flash_attention": os.environ.get("BENCH_FLASH", "1") == "1",
            "dynamic_batching": os.environ.get(
                "TPU_SERVER_DYNAMIC_BATCH", "0") == "1",
            "platform": jax.devices()[0].platform,
            "seconds_per_window_pair": cfg["seconds"],
            "depths": cfg["depths"],
        },
    }
    # Atomic replace: an external timeout killing a LATER _emit mid-write
    # must not truncate the previously valid detail file.
    tmp_path = detail_path + ".tmp"
    with open(tmp_path, "w") as f:
        json.dump(detail, f, indent=1)
    os.replace(tmp_path, detail_path)
    # Compact driver-parseable line: the full matrix lives in the detail
    # file, NOT here (round 4's fat line overflowed the tail capture).
    result = {
        "metric": f"{model_name}_b{cfg['batch']}_grpc_stream_tpushm_infer_per_sec",
        "value": round(median(r["value"] for r in runs), 2),
        "unit": "infer/s",
        "vs_baseline": vs_baseline,
        "vs_baseline_min_run": vs_min,
        "runs": [r["vs_baseline"] for r in runs],
        "worst_point": pooled_worst_point,
        "worst_ratio": pooled_worst,
        "worst_run_point": worst["worst_point"],
        # Pooled-sketch tail gate (merged across runs) + the worst single
        # run, recorded side by side: the pooled value is the gate, the
        # min-run value keeps a one-run blowup visible.
        "p99_margin": p99_margin_pooled,
        "p99_margin_min_run": round(p99_margin_min, 4),
        "serving_p99_pooled_ms": round(serve_p99_us / 1000, 2),
        "errors": sum(r["errors"] for r in runs),
        "detail_file": os.path.basename(detail_path),
    }
    if overload_pooled is not None:
        result["overload_margin"] = overload_pooled
        result["overload_margin_min_run"] = round(min(overload_margins), 4)
        result["overload_shed_p99_ms"] = max(
            r["overload"]["shed_p99_ms"] for r in runs if r.get("overload")
        )
    peak = _peak_flops()
    if peak:
        # Absolute MFU headline: achieved FLOPs/s (headline serving
        # throughput x analytic fwd FLOPs per infer) over the device's
        # published peak. Omitted on the CPU backend.
        result["mfu"] = round(
            result["value"]
            * _analytic_fwd_flops(model_name, cfg["batch"], cfg["seq"])
            / peak, 4
        )
    if len(runs) < n_runs:
        result["partial_runs"] = len(runs)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
