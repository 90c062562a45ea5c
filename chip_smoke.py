#!/usr/bin/env python3
"""Bring-up check: the serving path, once, on the chip.

One process, no arguments, no JAX_PLATFORMS: it takes whatever accelerator
JAX finds and refuses to run without a TPU. It drives the system through
the entry points a user calls, at the full width of the models the repo
serves (random weights from a seed), and checks each result by the repo's
own means. Phases, each printed with its seconds:

  device     backend must be tpu; versions and the compile-cache directory
  kernels    every pl.pallas_call (flash forward + the two backward
             kernels) compiled, not interpreted, against
             ops.attention.dot_product_attention
  encoder    InferenceServer -> gRPC bidirectional stream -> tpu_shared_memory
             regions -> dynamic batcher -> BERT-base with the flash kernel;
             outputs stay jax.Arrays on the device and match the in-process
             forward; then one short PerfAnalyzer level
  llm        gpt_small through the paged continuous-batching engine over the
             decoupled stream: counts, a fused multi-step dispatch and a
             prefix-cache hit from the engine's own counters, and the
             float32 oracle (engine tokens == gpt.generate_scan)
  multichip  with >= 4 devices: tp=4 engine == tp=1 tokens, shards on four
             devices, sharded BERT through mesh-spanning regions

A failed check raises: the exit code is non-zero and no result line is
printed. A passing run ends with two lines: ``detail: {...}`` (phases with
their seconds, compile count, cache directory) and, last, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``run(cfg, require_tpu=False)`` with ``tiny_config()`` is the same body at
test sizes on the CPU (tests/test_chip_smoke.py); it claims nothing about
the device.
"""

import collections
import dataclasses
import faulthandler
import functools
import importlib.metadata
import json
import queue
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

# The driver stops the script at 1200 s; dump every thread's stack and exit
# non-zero before that, so a hang is a failure with a trace and not a wait.
WATCHDOG_S = 1100
# Deadline on every queue read and future (a first request waits out an
# XLA compile, so it is generous; a hang still ends here).
WAIT_S = 300.0

N_SENDERS = 8
OUTPUT_TOKENS = 16
SHARED_PREFIX = 64


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """Sizes of one run. ``full_config()`` is what the chip runs."""

    bert: object                 # models.bert.BertConfig
    bert_batch: int              # rows per request
    bert_seq: int                # tokens per row (a multiple of 128: flash)
    bert_rounds: int             # requests per sender
    gpt: object                  # models.gpt.GptConfig
    kernel_shapes: tuple         # ((B, L, H, D), causal), ...
    analyzer_seconds: float


def full_config() -> SmokeConfig:
    from tritonclient_tpu.models import bert, gpt

    return SmokeConfig(
        bert=bert.bert_base(), bert_batch=8, bert_seq=128, bert_rounds=4,
        gpt=gpt.gpt_small(),
        # BERT-base's head shape (d=64 relies on Mosaic lane padding), and
        # a full-lane causal head.
        kernel_shapes=(((2, 128, 12, 64), False), ((1, 256, 4, 128), True)),
        analyzer_seconds=2.5,
    )


def tiny_config() -> SmokeConfig:
    from tritonclient_tpu.models import bert, gpt

    return SmokeConfig(
        bert=bert.bert_tiny(seq_len=128), bert_batch=2, bert_seq=128,
        bert_rounds=2,
        gpt=gpt.gpt_tiny(max_len=256),
        kernel_shapes=(((1, 128, 2, 16), False), ((1, 256, 2, 16), True)),
        analyzer_seconds=1.0,
    )


class _CompileLog:
    """Counts XLA compile requests, their seconds and persistent-cache
    hits while entered, from jax.monitoring's own events."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"
    _CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self._lock = threading.Lock()  # batcher and engine threads compile
        self.requests = 0
        self.seconds = 0.0
        self.cache_hits = 0
        self.by_function = collections.Counter()  # jit name -> seconds

    def _on_duration(self, event, seconds, fun_name="?", **_):
        if event == self._COMPILE:
            with self._lock:
                self.requests += 1
                self.seconds += seconds
                self.by_function[fun_name] += seconds

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


def _check(ok: bool, message: str):
    if not ok:
        raise AssertionError(message)


def _max_err(got, want) -> float:
    return float(np.max(np.abs(
        np.asarray(got, np.float32) - np.asarray(want, np.float32)
    )))


# --------------------------------------------------------------------------- #
# device                                                                      #
# --------------------------------------------------------------------------- #


def _package_version(name: str) -> str:
    try:
        return importlib.metadata.version(name)
    except importlib.metadata.PackageNotFoundError:
        return "absent"


def _device_phase(require_tpu: bool) -> dict:
    import jax

    backend = jax.default_backend()
    if require_tpu and backend != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX backend is '{backend}', not 'tpu' — no "
            "accelerator found, nothing was run"
        )
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(f"  platform={device['platform']} device_kind={device['kind']} "
          f"devices={device['count']}")
    print("  " + " ".join(
        f"{name}={_package_version(name)}"
        for name in ("jax", "jaxlib", "libtpu")
    ))
    return device


# --------------------------------------------------------------------------- #
# kernels                                                                     #
# --------------------------------------------------------------------------- #


def _mosaic_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def _kernels_phase(cfg: SmokeConfig, interpret) -> None:
    """Forward and both backward pallas_calls against the reference. On the
    chip ``interpret`` is False — never the interpreter, never the silent
    reference fallback — and the compiled HLO must hold the Mosaic calls."""
    import jax
    import jax.numpy as jnp

    from tritonclient_tpu.ops import (
        dot_product_attention,
        flash_attention,
        flash_attention_path,
    )

    for shape, causal in cfg.kernel_shapes:
        path = flash_attention_path(shape, shape, causal=causal,
                                    interpret=interpret)
        _check(path != "reference",
               f"flash {shape} causal={causal} resolved to the reference")
        q, k, v, w = (
            jax.random.normal(key, shape, jnp.float32)
            for key in jax.random.split(jax.random.PRNGKey(0), 4)
        )
        flash = functools.partial(flash_attention, causal=causal,
                                  interpret=interpret)
        ref = functools.partial(dot_product_attention, causal=causal)

        def grads(attention):
            return jax.grad(
                lambda q, k, v: (attention(q, k, v) * w).sum(),
                argnums=(0, 1, 2),
            )

        fwd = jax.jit(flash).lower(q, k, v).compile()
        bwd = jax.jit(grads(flash)).lower(q, k, v).compile()
        if path == "mosaic":
            # grad runs the forward kernel again, then dq and dk/dv.
            _check(_mosaic_calls(fwd) >= 1 and _mosaic_calls(bwd) >= 3,
                   f"expected 1 + 3 Mosaic custom calls, compiled HLO has "
                   f"{_mosaic_calls(fwd)} + {_mosaic_calls(bwd)}")
        got = fwd(q, k, v)
        got_grads = bwd(q, k, v)
        # The reference at full f32 precision, so the error below is the
        # kernel's (TPU f32 matmuls otherwise run bf16 passes on both sides).
        with jax.default_matmul_precision("highest"):
            want = ref(q, k, v)
            want_grads = grads(ref)(q, k, v)
        err = _max_err(got, want)
        _check(err <= 2e-2, f"flash forward {shape}: max |err| {err:.2e}")
        grad_errs = [_max_err(g, r) for g, r in zip(got_grads, want_grads)]
        _check(max(grad_errs) <= 5e-2,
               f"flash backward {shape}: max |err| dq/dk/dv {grad_errs}")
        print(f"  flash {shape} causal={causal} [{path}]: fwd err {err:.1e}, "
              f"dq/dk/dv err " + "/".join(f"{e:.1e}" for e in grad_errs))


# --------------------------------------------------------------------------- #
# streams                                                                     #
# --------------------------------------------------------------------------- #


class _Stream:
    """One gRPC client with its bidirectional stream open; responses are
    read with a deadline."""

    def __init__(self, address: str):
        import tritonclient_tpu.grpc as grpcclient

        self.client = grpcclient.InferenceServerClient(address)
        self._responses: "queue.Queue" = queue.Queue()
        self.client.start_stream(
            callback=lambda result, error: self._responses.put((result, error))
        )

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.client.stop_stream()
        self.client.close()

    def get(self):
        result, error = self._responses.get(timeout=WAIT_S)
        if error is not None:
            raise error
        return result


def _join(futures) -> list:
    """Every future's result, each with a deadline (a sender's exception
    surfaces here)."""
    return [f.result(timeout=4 * WAIT_S) for f in futures]


# --------------------------------------------------------------------------- #
# encoder                                                                     #
# --------------------------------------------------------------------------- #


def _encoder_sender(address: str, wid: int, payloads, d_model: int,
                    barrier: threading.Barrier):
    """One closed-loop sender: its own stream and its own input/output TPU
    regions. Returns [(tokens, output jax.Array as read from the region)]."""
    import jax.numpy as jnp

    import tritonclient_tpu.grpc as grpcclient
    import tritonclient_tpu.utils.tpu_shared_memory as tpushm

    batch = payloads[0].shape[0]
    in_bytes = payloads[0].nbytes
    out_bytes = batch * d_model * 4
    names = (f"smoke_in_{wid}", f"smoke_out_{wid}")
    in_region = tpushm.create_shared_memory_region(names[0], in_bytes, 0)
    out_region = tpushm.create_shared_memory_region(names[1], out_bytes, 0)
    results = []
    try:
        with _Stream(address) as stream:
            client = stream.client
            client.register_tpu_shared_memory(
                names[0], tpushm.get_raw_handle(in_region), 0, in_bytes)
            client.register_tpu_shared_memory(
                names[1], tpushm.get_raw_handle(out_region), 0, out_bytes)
            try:
                for tokens in payloads:
                    tpushm.set_shared_memory_region_from_dlpack(
                        in_region, [jnp.asarray(tokens)])
                    inp = grpcclient.InferInput(
                        "INPUT_IDS", list(tokens.shape), "INT32")
                    inp.set_shared_memory(names[0], in_bytes)
                    out = grpcclient.InferRequestedOutput("POOLED_OUTPUT")
                    out.set_shared_memory(names[1], out_bytes)
                    # Senders release each round together, so requests
                    # are in the batcher's queue at the same time.
                    barrier.wait(timeout=WAIT_S)
                    client.async_stream_infer(
                        "bert_base", [inp], outputs=[out])
                    stream.get()
                    results.append((tokens, tpushm.as_shared_memory_tensor(
                        out_region, "FP32", [batch, d_model])))
            finally:
                for name in names:
                    client.unregister_tpu_shared_memory(name)
    except BaseException:
        barrier.abort()  # the other senders fail now, not at their deadline
        raise
    finally:
        tpushm.destroy_shared_memory_region(in_region)
        tpushm.destroy_shared_memory_region(out_region)
    return results


def _float32_forward(model):
    """tokens -> pooled output of ``model``'s weights in float32, matmuls
    at full precision, attention by ops.attention.dot_product_attention."""
    import jax
    import jax.numpy as jnp

    from tritonclient_tpu.models import bert

    params = jax.tree_util.tree_map(
        lambda p: p.astype(jnp.float32), model._params)

    @jax.jit
    def forward(params, tokens):
        with jax.default_matmul_precision("highest"):
            return bert.pooled_output(
                params, bert.encode(params, tokens, model.cfg))

    return lambda tokens: forward(params, jnp.asarray(tokens))


def _encoder_phase(cfg: SmokeConfig, compiles: "_CompileLog") -> None:
    import jax
    import jax.numpy as jnp

    from tritonclient_tpu.models.bert import BertBaseModel
    from tritonclient_tpu.perf_analyzer import PerfAnalyzer
    from tritonclient_tpu.server import InferenceServer

    on_tpu = jax.default_backend() == "tpu"
    model = BertBaseModel(cfg=cfg.bert, use_flash_attention=True)
    # bench.py's batcher setting: hold a forming batch up to 2 ms, only
    # under arrival pressure.
    model.max_queue_delay_us = 2000
    path = model.attention_path(cfg.bert_seq)
    print(f"  bert attention at L={cfg.bert_seq}: {path}")
    _check(path == ("flash-mosaic" if on_tpu else "flash-interpret"),
           f"served attention resolved to {path}")

    rng = np.random.default_rng(0)
    shape = (cfg.bert_batch, cfg.bert_seq)
    payloads = [
        [rng.integers(0, cfg.bert.vocab_size, shape).astype(np.int32)
         for _ in range(cfg.bert_rounds)]
        for _ in range(N_SENDERS)
    ]
    barrier = threading.Barrier(N_SENDERS)
    with InferenceServer(models=[model], http=False) as server:
        with ThreadPoolExecutor(N_SENDERS) as pool:
            per_sender = _join([
                pool.submit(_encoder_sender, server.grpc_address, wid,
                            payloads[wid], cfg.bert.d_model, barrier)
                for wid in range(N_SENDERS)
            ])
        stats = server.core.model_statistics(model.name)[0]
        n_requests = N_SENDERS * cfg.bert_rounds
        _check(stats["inference_count"] == n_requests,
               f"server counted {stats['inference_count']} of {n_requests}")
        avg_batch = stats["inference_count"] / stats["execution_count"]
        _check(avg_batch > 1.0,
               "the batcher never formed a batch > 1 "
               f"({stats['execution_count']} executions)")

        # A batched request ran in a power-of-two padded executable of
        # another shape than model._fwd at the request's own shape, so the
        # two agree to bfloat16 rounding, not to the bit. How much rounding
        # moves this model is measured, not guessed: the distance of
        # model._fwd from the same weights in float32 at full precision
        # with the reference attention.
        truth_fn = _float32_forward(model)
        worst = rounding = 0.0
        device = jax.devices()[0]
        for tokens, got in (r for results in per_sender for r in results):
            _check(isinstance(got, jax.Array) and got.devices() == {device},
                   f"region output is {type(got).__name__}, not a jax.Array "
                   f"on {device}: host staging on the output path")
            want = model._fwd(model._params, jnp.asarray(tokens))
            worst = max(worst, _max_err(got, want))
            rounding = max(rounding, _max_err(want, truth_fn(tokens)))
        _check(worst <= max(3 * rounding, 1e-5),
               f"served output differs from model._fwd by {worst:.2e}; "
               f"model._fwd is {rounding:.2e} from its float32 reference")
        print(f"  {n_requests} requests over {N_SENDERS} streams, avg batch "
              f"{avg_batch:.2f}, outputs on {device}, max |err| vs "
              f"model._fwd {worst:.1e} (model._fwd vs float32 reference "
              f"{rounding:.1e})")

        if on_tpu:
            # The executable the server ran holds the Mosaic kernel: not
            # the reference flash_attention substitutes for shapes that do
            # not tile, and not the interpreter.
            served = model._fwd.lower(
                model._params, jax.ShapeDtypeStruct(shape, jnp.int32)
            ).compile()
            _check(_mosaic_calls(served) >= 1,
                   "compiled HLO of the served forward has no Mosaic "
                   "custom call")
            print(f"  served forward HLO: {_mosaic_calls(served)} Mosaic "
                  "custom call(s)")

        # Compile what the level below will run, as bench.py does before
        # its windows, so it shows serving and not XLA. A lone request's
        # input is uploaded from its region's host mirror and arrives
        # committed to the device — a second executable for the same shape
        # as the uncommitted arrays parked above. A formed batch is
        # concatenated (uncommitted) and padded to a power of two of rows.
        warm = [jax.device_put(np.zeros(shape, np.int32), device)]
        rows = 2 * cfg.bert_batch
        while rows <= min(model.max_batch_size, N_SENDERS * cfg.bert_batch):
            warm.append(jnp.zeros((rows, cfg.bert_seq), jnp.int32))
            rows *= 2
        for tokens in warm:
            jax.block_until_ready(model._fwd(model._params, tokens))
        analyzer = PerfAnalyzer(
            server.grpc_address, model.name, batch_size=cfg.bert_batch,
            shared_memory="tpu", streaming=True, read_outputs=True,
            measurement_interval_s=cfg.analyzer_seconds, warmup_s=0.5,
            shape_overrides={"INPUT_IDS": cfg.bert_seq},
        )
        compiles_before = compiles.requests
        summary = analyzer.measure(N_SENDERS).summary()
        _check(summary["errors"] == 0,
               f"PerfAnalyzer level had {summary['errors']} errors")
        _check(summary["count"] > 0, "PerfAnalyzer level completed nothing")
        print(f"  PerfAnalyzer c{N_SENDERS}, {cfg.analyzer_seconds}s: "
              f"{summary['count']} requests, errors 0, avg batch "
              f"{summary['server_request_count'] / max(summary['server_exec_count'], 1):.2f}, "
              f"latency p50/p99 {summary['latency_p50_us'] / 1e3:.1f}/"
              f"{summary['latency_p99_us'] / 1e3:.1f} ms; server per "
              f"request: queue {summary['server_queue_us']} us, compute "
              f"input/infer/output {summary['server_compute_input_us']}/"
              f"{summary['server_compute_infer_us']}/"
              f"{summary['server_compute_output_us']} us; "
              f"{compiles.requests - compiles_before} XLA compile(s) during "
              "the level; smoke_not_a_measurement_infer_per_sec="
              f"{summary['throughput_infer_per_sec']:.1f}")


# --------------------------------------------------------------------------- #
# llm                                                                         #
# --------------------------------------------------------------------------- #


def _llm_requests(vocab: int) -> list:
    """Eight seeded requests, 40-160 prompt tokens (two to five prefill
    chunks of 32). Requests 2 and 4 share their first 64 tokens; the last
    one samples, with a seed wider than 32 bits (SEED is INT64 on the
    wire)."""
    rng = np.random.default_rng(1)
    prefix = rng.integers(1, vocab, SHARED_PREFIX)
    requests = []
    for i, length in enumerate((40, 40, 72, 72, 104, 104, 160, 160)):
        prompt = rng.integers(1, vocab, length)
        if i in (2, 4):
            prompt[:SHARED_PREFIX] = prefix
        requests.append({
            "prompt": prompt.astype(np.int32)[None],
            "temperature": 0.0, "top_k": 0, "seed": 0,
        })
    requests[-1].update(temperature=0.7, top_k=50, seed=(1 << 40) + 7)
    return requests


def _stream_generation(address: str, request: dict,
                       start_after: threading.Event,
                       first_token: threading.Event) -> list:
    """One request over its own decoupled stream, the way
    examples/gpt_token_stream_client.py does; returns its tokens."""
    import tritonclient_tpu.grpc as grpcclient

    def tensor(name, value, datatype):
        inp = grpcclient.InferInput(name, list(value.shape), datatype)
        inp.set_data_from_numpy(value)
        return inp

    inputs = [
        tensor("INPUT_IDS", request["prompt"], "INT32"),
        tensor("MAX_TOKENS", np.array([OUTPUT_TOKENS], np.int32), "INT32"),
    ]
    if request["temperature"] > 0:
        inputs += [
            tensor("TEMPERATURE",
                   np.array([request["temperature"]], np.float32), "FP32"),
            tensor("TOP_K", np.array([request["top_k"]], np.int32), "INT32"),
            tensor("SEED", np.array([request["seed"]], np.int64), "INT64"),
        ]
    _check(start_after.wait(timeout=WAIT_S), "start signal never came")
    tokens = []
    with _Stream(address) as stream:
        stream.client.async_stream_infer(
            "gpt_engine", inputs, enable_empty_final_response=True)
        while True:
            result = stream.get()
            out = result.as_numpy("OUTPUT_IDS")
            if out is not None and out.size:
                tokens.append(int(out[0]))
                first_token.set()
            final = result.get_response().parameters.get(
                "triton_final_response")
            if final is not None and final.bool_param:
                return tokens


def _engine_generation(engine, requests: list) -> list:
    """All requests submitted to the engine at once; tokens per request."""
    submitted = [
        engine.submit(r["prompt"], OUTPUT_TOKENS,
                      temperature=r["temperature"], top_k=r["top_k"],
                      seed=r["seed"])
        for r in requests
    ]
    streams = []
    for req in submitted:
        tokens = []
        while True:
            token = req.out.get(timeout=WAIT_S)
            if token is None:
                break
            if isinstance(token, BaseException):
                raise token
            tokens.append(int(token[0]))
        streams.append(tokens)
    return streams


def _float32_oracle(cfg: SmokeConfig, requests: list) -> list:
    """The engine at the same widths in float32 must give generate_scan's
    tokens exactly. (Two bfloat16 paths on random weights cannot be held
    to token equality: logits are nearly flat and argmax flips on
    rounding.) Returns the tokens, which the multichip phase compares
    tp=4 against."""
    import jax
    import jax.numpy as jnp

    from tritonclient_tpu.models import gpt
    from tritonclient_tpu.models.gpt_engine import GptEngineModel
    from tritonclient_tpu.parallel.validate import full_matmul_precision

    cfg32 = dataclasses.replace(cfg.gpt, dtype=jnp.float32)
    with full_matmul_precision():
        model = GptEngineModel(cfg=cfg32)
        try:
            got = _engine_generation(model.engine, requests)
        finally:
            model.engine.shutdown()

        @functools.lru_cache(maxsize=None)
        def scan_fn(temperature, top_k, seed):
            # One jit per sampling setting; it retraces per prompt length.
            return jax.jit(functools.partial(
                gpt.generate_scan, max_new=OUTPUT_TOKENS, cfg=cfg32,
                temperature=temperature, top_k=top_k, seed=seed,
            ))

        for i, (request, tokens) in enumerate(zip(requests, got)):
            want = scan_fn(
                request["temperature"], request["top_k"], request["seed"]
            )(model.engine.params, jnp.asarray(request["prompt"]))
            want = [int(t) for t in np.asarray(want)[0]]
            _check(tokens == want,
                   f"float32 oracle, request {i}: engine {tokens} != "
                   f"generate_scan {want}")
    print(f"  float32 oracle: engine == generate_scan on {len(requests)} "
          f"requests x {OUTPUT_TOKENS} tokens")
    return got


def _llm_phase(cfg: SmokeConfig) -> list:
    from tritonclient_tpu import _stepscope
    from tritonclient_tpu.models.gpt_engine import GptEngineModel
    from tritonclient_tpu.protocol._literals import PREFIX_EVENT_HIT
    from tritonclient_tpu.server import InferenceServer

    requests = _llm_requests(cfg.gpt.vocab_size)
    model = GptEngineModel(cfg=cfg.gpt)
    engine = model.engine
    # The engine's step records (micro-steps per dispatch) are its own
    # account of whether the fused path ran.
    previous_mode = _stepscope.mode()
    _stepscope.configure(_stepscope.MODE_COUNTERS)
    _stepscope.reset()
    try:
        with InferenceServer(models=[model], http=False) as server:
            go = threading.Event()
            go.set()
            firsts = [threading.Event() for _ in requests]
            with ThreadPoolExecutor(len(requests)) as pool:
                # Request 4 starts when request 2 has its first token: by
                # then the shared prefix's pages are in the prefix cache,
                # and the rest are still decoding.
                streams = _join([
                    pool.submit(_stream_generation, server.grpc_address,
                                request, firsts[2] if i == 4 else go,
                                firsts[i])
                    for i, request in enumerate(requests)
                ])
            for i, tokens in enumerate(streams):
                _check(len(tokens) == OUTPUT_TOKENS,
                       f"request {i} streamed {len(tokens)} tokens")
                _check(all(0 <= t < cfg.gpt.vocab_size for t in tokens),
                       f"request {i} has a token out of range: {tokens}")
            hits = engine._prefix.snapshot_events().get(PREFIX_EVENT_HIT, 0)
            _check(hits >= SHARED_PREFIX // engine.block_size,
                   f"prefix cache counted {hits} hit pages; the shared "
                   f"{SHARED_PREFIX}-token prefix is "
                   f"{SHARED_PREFIX // engine.block_size}")
            decode = [r for r in _stepscope.dump()["records"]
                      if r["model"] == model.name and r["phase"] == "decode"]
            fused = [r["micro_steps"] for r in decode if r["micro_steps"] > 1]
            _check(bool(fused), "no fused multi-step decode dispatch in "
                   f"{len(decode)} decode records")
            print(f"  {len(streams)} streams x {OUTPUT_TOKENS} tokens; "
                  f"{len(decode)} decode dispatches, {len(fused)} fused "
                  f"(widths {sorted(set(fused))}); prefix-cache hit pages "
                  f"{hits}")

            # The sampled request again, twice, alone: both runs find the
            # whole prompt in the prefix cache and take the same schedule,
            # so the same seed must give the same tokens.
            repeats = [
                _stream_generation(server.grpc_address, requests[-1], go, go)
                for _ in range(2)
            ]
            _check(repeats[0] == repeats[1] and
                   len(repeats[0]) == OUTPUT_TOKENS,
                   f"same seeded request, two answers: {repeats}")
            print("  seeded sampled request repeated: identical tokens")
    finally:
        _stepscope.configure(previous_mode)
        _stepscope.reset()
        engine.shutdown()
    return _float32_oracle(cfg, requests)


# --------------------------------------------------------------------------- #
# multichip                                                                   #
# --------------------------------------------------------------------------- #


def _check_quarter_shards(name: str, array) -> None:
    shards = array.addressable_shards
    devices = {s.device for s in shards}
    _check(len(shards) == 4 and len(devices) == 4,
           f"{name}: {len(shards)} shards on {len(devices)} devices")
    _check(all(s.data.nbytes * 4 == array.nbytes for s in shards),
           f"{name}: shard bytes {[s.data.nbytes for s in shards]} of "
           f"{array.nbytes}")


def _multichip_phase(cfg: SmokeConfig, oracle_tokens: list) -> str:
    import jax
    import jax.numpy as jnp

    from tritonclient_tpu.models.gpt_engine import GptEngineModel
    from tritonclient_tpu.parallel import build_mesh
    from tritonclient_tpu.parallel.validate import (
        full_matmul_precision,
        serve_sharded_bert_roundtrip,
    )

    devices = jax.devices()
    if len(devices) < 4:
        print(f"multichip: not run, {len(devices)} device(s)")
        return "not run"
    devices = devices[:4]
    cfg32 = dataclasses.replace(cfg.gpt, dtype=jnp.float32)
    # float32 across shardings: full precision, as for the oracle.
    with full_matmul_precision():
        model = GptEngineModel(cfg=cfg32, mesh=build_mesh({"tp": 4}, devices))
        try:
            # Code that has only seen a virtual mesh may put everything on
            # device 0.
            _check_quarter_shards("KV pool", model.engine._k)
            _check_quarter_shards(
                "wqkv", model.engine.params["layers"]["wqkv"])
            got = _engine_generation(
                model.engine, _llm_requests(cfg.gpt.vocab_size))
        finally:
            model.engine.shutdown()
    _check(got == oracle_tokens,
           f"tp=4 tokens differ from tp=1: {got} != {oracle_tokens}")
    print("  tp=4 engine: tokens identical to tp=1; KV pool and wqkv in "
          "quarters on 4 devices")
    # Ring attention with impl="reference": Mosaic does not lower inside
    # ring_attention's partial-manual shard_map (its docstring).
    serve_sharded_bert_roundtrip(
        build_mesh({"dp": 1, "sp": 2, "tp": 2}, devices), prefix="smoke_msv")
    print("  sharded BERT (sp=2 ring, tp=2) through mesh-spanning regions: "
          "matches single-device")
    return "pass"


# --------------------------------------------------------------------------- #
# run                                                                         #
# --------------------------------------------------------------------------- #


def run(cfg: SmokeConfig, require_tpu: bool = True) -> dict:
    """Every phase in order; raises at the first failed check. Returns the
    detail record; ``result_line`` cuts it to the last line's object."""
    import jax

    from tritonclient_tpu import _compile_cache

    phases = {}

    def phase(name, fn, *args):
        print(f"[{name}]", flush=True)
        t0 = time.perf_counter()
        out = fn(*args)
        seconds = round(time.perf_counter() - t0, 1)
        # A phase that may not apply names its own status.
        status = out if isinstance(out, str) else "pass"
        phases[name] = {"status": status, "seconds": seconds}
        print(f"[{name}] {status} in {seconds}s", flush=True)
        return out

    cache_dir = jax.config.jax_compilation_cache_dir
    entries_before = _compile_cache.entry_count(cache_dir)
    with _CompileLog() as compiles:
        device = phase("device", _device_phase, require_tpu)
        print(f"  compile cache: {cache_dir} ({entries_before} entries)")
        phase("kernels", _kernels_phase, cfg, False if require_tpu else None)
        phase("encoder", _encoder_phase, cfg, compiles)
        oracle_tokens = phase("llm", _llm_phase, cfg)
        phase("multichip", _multichip_phase, cfg, oracle_tokens)
    entries_after = _compile_cache.entry_count(cache_dir)
    print(f"compilations: {compiles.requests} requests, "
          f"{compiles.seconds:.1f}s, {compiles.cache_hits} served from "
          f"{cache_dir} ({entries_before} -> {entries_after} entries); "
          "most seconds: " + ", ".join(
              f"{name} {seconds:.1f}"
              for name, seconds in compiles.by_function.most_common(6)))
    return {
        "ok": True,
        "device": device,
        "phases": phases,
        "compilations": compiles.requests,
        "compile_seconds": round(compiles.seconds, 1),
        "cache_hits": compiles.cache_hits,
        "cache_dir": cache_dir,
        "cache_entries": [entries_before, entries_after],
    }


def result_line(result: dict) -> str:
    """The last line of standard output: exactly ``ok`` and ``device``
    (``platform``, ``kind``, ``count`` as JAX reports them). The driver
    that reads it accepts no other key; the rest of ``result`` is printed
    on the ``detail:`` line before it."""
    return json.dumps({"ok": result["ok"], "device": result["device"]})


def main() -> int:
    faulthandler.dump_traceback_later(WATCHDOG_S, exit=True)
    from tritonclient_tpu import _compile_cache

    _compile_cache.configure()
    result = run(full_config())
    print("detail: " + json.dumps(result))
    print(result_line(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
