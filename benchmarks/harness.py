"""One run of one cell: set-up, the measured window, the output check, and
the one result line. ``benchmarks/run.py`` is its command line."""

import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from benchmarks import spec
from benchmarks.client import RequestLog, open_clients
from benchmarks.costs import peaks_for
from benchmarks.stats import percentile
from benchmarks.traffic import RequestSource

SCRATCH_DIR = ".bench_scratch"     # under the checkout; listed in .gitignore
TRACE_SECONDS = 4.0
POOL_SAMPLE_S = 0.01


class BenchmarkError(RuntimeError):
    """The run cannot give a result; exit non-zero and print none."""


@dataclass
class Observations:
    """Everything a metric reader may look at. A reader that does not find
    what it reads returns None and its metric is left out of the line."""

    cell: spec.Cell
    shape: object                     # the adapter's own (GptShape for GPT)
    chips: int
    peaks: Optional[dict]             # None off the chip: no share of a peak
    setup_s: float
    window: Dict[str, int]            # start_ns, end_ns, drained_ns
    logs: List[RequestLog]            # every request sent in the window
    steps: List[dict] = field(default_factory=list)   # stepscope records
    pool_samples: List[Tuple[int, int, int]] = field(default_factory=list)
    host_lag_ms: float = 0.0          # the sampler thread's latest wake-up
    trace: Optional[dict] = None      # reduce_trace(...) + span on this clock

    @property
    def window_s(self) -> float:
        return (self.window["end_ns"] - self.window["start_ns"]) / 1e9

    def finished(self) -> List[RequestLog]:
        return [log for log in self.logs if log.error is None]

    def failed(self) -> List[RequestLog]:
        return [log for log in self.logs if log.error is not None]

    def first_token_waits_ms(self) -> List[float]:
        """Send to first streamed token, of every request that finished."""
        return [(log.token_ns[0] - log.sent_ns) / 1e6
                for log in self.finished()]

    def token_gaps_ms(self) -> List[float]:
        """Every gap between consecutive tokens of a finished request."""
        return [(b - a) / 1e6 for log in self.finished()
                for a, b in zip(log.token_ns, log.token_ns[1:])]

    def decode_steps(self) -> List[dict]:
        return [r for r in self.steps if r["phase"] == "decode"]


class CompileCount:
    """XLA compile requests and persistent-cache hits while entered, from
    jax.monitoring's own events (after chip_smoke.py:_CompileLog)."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self._lock = threading.Lock()
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0

    def snapshot(self) -> Tuple[int, float, int]:
        with self._lock:
            return self.requests, self.seconds, self.cache_hits

    def _on_duration(self, event, seconds, **_):
        if event == self._COMPILE:
            with self._lock:
                self.requests += 1
                self.seconds += seconds

    def _on_event(self, event, **_):
        if event == self._CACHE_HIT:
            with self._lock:
                self.cache_hits += 1

    def __enter__(self):
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        import jax.monitoring

        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)


def configure_compile_cache() -> str:
    """The program places the cache (``JAX_COMPILATION_CACHE_DIR`` where set,
    else ``<checkout>/.jax_cache``); the thresholds are set here so that the
    sub-second admission, slice and init executables are kept too and a
    second run compiles nothing."""
    import jax

    from tritonclient_tpu import _compile_cache

    directory = _compile_cache.configure()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return directory


def find_devices(chips: int, require_tpu: bool) -> dict:
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise BenchmarkError(
            f"JAX found platform {platform!r}, not 'tpu': the benchmark "
            "measures on the chip and has no fallback")
    if len(devices) < chips:
        raise BenchmarkError(
            f"the cell asks for {chips} chip(s), JAX found {len(devices)}")
    return {"platform": platform, "kind": devices[0].device_kind,
            "count": len(devices)}


def memory_peak_bytes(chips: int) -> int:
    import jax

    peaks = []
    for device in jax.devices()[:chips]:
        stats = device.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def load_module(bench: dict, kind: str, name: str, root: str):
    """``<path>/<kind>/<name>.py`` under any of ``paths``: a metric's
    reader, a configuration's adapter, a mix's loop driver."""
    path = spec.reader_file(bench, kind, name, root)
    module_name = f"_bench_{kind}_{name.replace('.', '_').replace('-', '_')}"
    if module_name not in sys.modules:
        module_spec = importlib.util.spec_from_file_location(module_name, path)
        module = importlib.util.module_from_spec(module_spec)
        sys.modules[module_name] = module
        try:
            module_spec.loader.exec_module(module)
        except BaseException:
            del sys.modules[module_name]
            raise
    return sys.modules[module_name]


def load_reader(bench: dict, kind: str, name: str, root: str) -> Callable:
    return load_module(bench, kind, name, root).read


def read_metrics(bench: dict, kind: str, entries: List[dict], root: str,
                 obs: Observations) -> Dict[str, dict]:
    out = {}
    for entry in entries:
        value = load_reader(bench, kind, entry["name"], root)(obs)
        if value is None:
            continue
        out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def is_correct(check: Dict[str, dict]) -> bool:
    for entry in check.values():
        if "limit" in entry and not entry["value"] <= entry["limit"]:
            return False
        if "at_least" in entry and not entry["value"] >= entry["at_least"]:
            return False
    return True


class _PoolSampler(threading.Thread):
    def __init__(self, serving):
        super().__init__(name="bench-pool-sampler", daemon=True)
        self._serving = serving
        self._stop_event = threading.Event()
        self.samples: List[Tuple[int, int, int]] = []
        self.worst_lag_ms = 0.0

    def run(self):
        # Also a watch on the host: a wake-up that comes late says this
        # process was kept off its cores (PERF.md, the stall).
        due = time.perf_counter_ns() + int(POOL_SAMPLE_S * 1e9)
        while not self._stop_event.wait(POOL_SAMPLE_S):
            now = time.perf_counter_ns()
            self.worst_lag_ms = max(self.worst_lag_ms, (now - due) / 1e6)
            due = now + int(POOL_SAMPLE_S * 1e9)
            used, total = self._serving.pool_usage()
            self.samples.append((now, used, total))

    def stop(self):
        self._stop_event.set()
        self.join(timeout=5)


class _Tracer:
    """A profiler trace of a few seconds inside the window, started and
    stopped by a timer thread; python tracing off (it hooks every call)."""

    def __init__(self, directory: str, start_after_s: float, seconds: float):
        self.directory = directory
        self._start_after = start_after_s
        self._seconds = seconds
        self.span_ns: Optional[Tuple[int, int]] = None      # perf_counter
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, name="bench-tracer",
                                        daemon=True)

    def start(self):
        self._thread.start()

    def join(self):
        self._thread.join(timeout=300)
        if self._thread.is_alive():
            raise BenchmarkError("the profiler did not stop")
        if self.error is not None:
            raise BenchmarkError(f"tracing failed: {self.error!r}")

    def _run(self):
        import jax

        from benchmarks.trace_reduce import CLOCK_SYNC

        try:
            time.sleep(self._start_after)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 2
            jax.profiler.start_trace(self.directory, profiler_options=options)
            begun = time.perf_counter_ns()
            with jax.profiler.TraceAnnotation(CLOCK_SYNC, mono_ns=begun):
                pass
            time.sleep(self._seconds)
            ended = time.perf_counter_ns()
            jax.profiler.stop_trace()
            self.span_ns = (begun, ended)
        except BaseException as e:   # noqa: BLE001 - reported by join()
            self.error = e


def _reduce_trace(tracer: "_Tracer", steps: List[dict], chips: int) -> dict:
    """``steps``: stepscope records with ``start_ns`` on the harness's clock."""
    from benchmarks import trace_reduce

    raw = trace_reduce.read_xplane(tracer.directory)
    begun, ended = tracer.span_ns
    if raw["sync"] is None:
        raise BenchmarkError("the trace holds no clock-sync annotation")
    trace_at, perf_at = raw["sync"]
    to_trace = trace_at - perf_at           # perf_counter ns -> trace ns
    host_spans = []
    for rec in steps:
        start = rec["start_ns"] + to_trace
        host_spans.append((start, start + rec["dispatch_us"] * 1e3,
                           f"engine dispatching {rec['phase']}"))
    reduced = trace_reduce.reduce_trace(
        raw["events"], (begun + to_trace, ended + to_trace), chips,
        host_spans=host_spans)
    reduced["span_ns"] = (begun, ended)
    reduced["layout"] = raw["layout"]
    reduced["file_bytes"] = raw["file_bytes"]
    return reduced


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, benchmark_file: Optional[str] = None,
        process_start: Optional[float] = None,
        out=None, err=None) -> dict:
    """One run of one cell. Returns the result line's object; raises
    ``BenchmarkError`` (or ``spec.SpecError``) where there is no result."""
    process_start = time.perf_counter() if process_start is None else process_start
    out = sys.stdout if out is None else out
    err = sys.stderr if err is None else err
    bench_path = benchmark_file or spec.BENCHMARK_FILE
    root = spec.ROOT
    bench = spec.load_benchmark(bench_path)
    cell = spec.load_cell(workload, bench_path, root)
    mix = cell.traffic
    # Found by name, as the readers are: a new kind of model or of pacing is
    # a new file under `paths`, not an edit here.
    adapter = load_module(bench, "adapters", str(cell.config.get("adapter")),
                          root)
    loop = load_module(bench, "loops", str(mix.get("loop")), root)
    loop.validate(mix)

    def say(*parts):
        print(*parts, file=out, flush=True)

    cache_dir = configure_compile_cache()
    device = find_devices(cell.chips, require_tpu)
    peaks = peaks_for(device["kind"]) if device["platform"] == "tpu" else None

    from tritonclient_tpu import _compile_cache, _stepscope

    shape = adapter.shape_of(cell.config)
    say(f"cell {cell.name}: seed {seed}, {seconds:g} s, trace {int(trace)}; "
        f"device {device}; compile cache {cache_dir} "
        f"({_compile_cache.entry_count(cache_dir)} entries)")

    scratch = os.path.join(spec.ROOT, SCRATCH_DIR)
    trace_dir = os.path.join(scratch, "trace")
    shutil.rmtree(trace_dir, ignore_errors=True)

    stepscope_was = _stepscope.mode()
    ring_was = os.environ.get("TPU_STEPSCOPE_RING")
    serving = None
    with CompileCount() as compiles:
        try:
            weights = adapter.make_weights(seed, shape)
            serving = adapter.Serving(shape, weights, cell.config["engine"],
                                      cell.chips)
            warmed = serving.warm(mix)
            clients = open_clients(serving.address, serving.model_name,
                                   shape.vocab_size, loop.streams(mix))
            source = RequestSource(mix, shape.vocab_size, seed)
            sampler = _PoolSampler(serving)
            tracer = None
            stepscope_offset = time.perf_counter_ns() - time.monotonic_ns()
            if trace:
                # Keep every record of the window, not the last 256.
                os.environ["TPU_STEPSCOPE_RING"] = "1000000"
                _stepscope.configure(_stepscope.MODE_COUNTERS)
                _stepscope.reset()
                span = min(TRACE_SECONDS, seconds / 2)
                tracer = _Tracer(trace_dir, min(2.0, seconds / 4), span)
            setup_compiles = compiles.snapshot()
            setup_s = time.perf_counter() - process_start
            say(f"set-up {setup_s:.3f} s: {setup_compiles[0]} compile "
                f"requests, {setup_compiles[1]:.1f} s compiling, "
                f"{setup_compiles[2]} served from the cache; warmed "
                f"{json.dumps(warmed)}")

            # ---- the measured window -----------------------------------
            sampler.start()
            if trace:
                tracer.start()
            window = loop.run(clients, source, seconds, mix)
            if trace:
                tracer.join()
            sampler.stop()
            # ---- the window has closed ----------------------------------
            in_window = compiles.snapshot()[0] - setup_compiles[0]
            peak = memory_peak_bytes(cell.chips)
            steps = (_stepscope.dump()["records"] if trace else [])
            for c in clients:
                c.close()
        finally:
            _stepscope.configure(stepscope_was)
            if trace:
                if ring_was is None:
                    os.environ.pop("TPU_STEPSCOPE_RING", None)
                else:
                    os.environ["TPU_STEPSCOPE_RING"] = ring_was
            if serving is not None:
                serving.close()

        logs = [log for c in clients for log in c.logs]
        logs.sort(key=lambda g: g.request.index)
        obs = Observations(
            cell=cell, shape=shape, chips=cell.chips, peaks=peaks,
            setup_s=setup_s, window=window, logs=logs,
            steps=[dict(r, start_ns=r["start_ns"] + stepscope_offset)
                   for r in steps if r["model"] == serving.model_name],
            pool_samples=sampler.samples, host_lag_ms=sampler.worst_lag_ms,
        )
        _describe_traffic(obs, in_window, say)

        check = adapter.check_outputs(cell, shape, weights, obs, seed)
        if trace:
            obs.trace = _reduce_trace(tracer, obs.steps, cell.chips)
            shutil.rmtree(trace_dir, ignore_errors=True)
            say("trace layout: " + json.dumps(obs.trace["layout"][:40]))

    kind, entries = (("layer_metrics", cell.per_layer) if trace
                     else ("end_to_end", cell.end_to_end))
    metrics = read_metrics(bench, kind, entries, root, obs)
    device_out = dict(device, memory_peak_bytes=peak)
    result = {
        "correct": is_correct(check),
        "attempted": len(logs),
        "failed": len(obs.failed()),
        "metrics": metrics,
        "device": device_out,
    }
    if trace:
        device_out["busy_s"] = obs.trace["busy_s"]
        device_out["window_s"] = obs.trace["window_s"]
        result["breakdown"] = {"device_ops": obs.trace["device_ops"],
                               "idle_gaps": obs.trace["idle_gaps"]}
    result["check"] = check     # last: each number compared, with its limit
    for name, entry in check.items():
        print(f"check {name}: {json.dumps(entry)}", file=err, flush=True)
    return result


def _describe_traffic(obs: Observations, compiles_in_window: int, say) -> None:
    prompts = [g.request.prompt.shape[1] for g in obs.logs]
    outputs = [g.request.max_tokens for g in obs.logs]
    if prompts:
        say(f"window: {len(obs.logs)} requests sent, {len(obs.failed())} "
            f"failed; prompt tokens min/mean/max {min(prompts)}/"
            f"{sum(prompts) / len(prompts):.1f}/{max(prompts)}, output "
            f"tokens {min(outputs)}/{sum(outputs) / len(outputs):.1f}/"
            f"{max(outputs)}; drained "
            f"{(obs.window['drained_ns'] - obs.window['end_ns']) / 1e9:.3f} s "
            "after the close")
    if "generator_lag_ms" in obs.window:
        say(f"generator lag worst {obs.window['generator_lag_ms']:.1f} ms")
    for log in obs.failed()[:5]:
        say(f"failed request {log.request.index}: {log.error}")
    # index:prompt+output:ms to the first token:ms to the last
    say("requests: " + " ".join(
        f"{g.request.index}:{g.request.prompt.shape[1]}+{len(g.tokens)}:"
        f"{(g.token_ns[0] - g.sent_ns) / 1e6:.1f}:"
        f"{(g.token_ns[-1] - g.sent_ns) / 1e6:.1f}"
        for g in obs.finished()))
    # What a user of one stream sees, in every run, traced or not; judged
    # nowhere (PERF.md section 2 says why).
    waits, gaps = obs.first_token_waits_ms(), obs.token_gaps_ms()
    if waits and gaps:
        say("latency: " + json.dumps({
            "ttft_p50_ms": percentile(waits, 50),
            "ttft_p95_ms": percentile(waits, 95),
            "itl_p50_ms": percentile(gaps, 50),
            "itl_p95_ms": percentile(gaps, 95)}))
    # Output tokens delivered in each second of the window, and the latest
    # wake-up of the 10 ms sampler thread: a stall shows in the first, and
    # in the second too if the host held this process off its cores.
    start = obs.window["start_ns"]
    by_second = [0] * max(int(np.ceil(obs.window_s)), 1)
    for log in obs.finished():
        for t in log.token_ns:
            if start <= t < obs.window["end_ns"]:
                by_second[int((t - start) / 1e9)] += 1
    say(f"delivered by second: {json.dumps(by_second)}; host lag worst "
        f"{obs.host_lag_ms:.1f} ms")
    say(f"compilations inside the window: {compiles_in_window}")
