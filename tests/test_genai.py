"""GPT serving + genai-perf instrument tests (the LLM streaming plane)."""

import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tritonclient_tpu.models import gpt


@pytest.fixture(scope="module")
def gpt_server():
    from tritonclient_tpu.server import InferenceServer

    model = gpt.GptModel(cfg=gpt.gpt_tiny(max_len=64))
    model.warmup()
    with InferenceServer(models=[model], http=False) as s:
        yield s


def test_gpt_cache_decode_matches_full_forward():
    cfg = gpt.gpt_tiny(max_len=32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.array([[1, 5, 9, 2, 7, 3, 11, 4],
                       [2, 4, 6, 8, 10, 12, 14, 16]], np.int32)
    stream = np.stack(
        list(gpt.generate_tokens(params, prompt, 6, cfg)), axis=1
    )
    scan = np.asarray(gpt.generate_scan(params, jnp.asarray(prompt), 6, cfg))
    np.testing.assert_array_equal(stream, scan)
    # Naive reference: re-run the full forward per step (no cache).
    cur = prompt.copy()
    for step in range(6):
        logits = gpt.forward(params, jnp.asarray(cur), cfg)
        tok = np.asarray(jnp.argmax(logits[:, -1], -1)).astype(np.int32)
        np.testing.assert_array_equal(stream[:, step], tok)
        cur = np.concatenate([cur, tok[:, None]], axis=1)


def test_gpt_generation_respects_max_len():
    cfg = gpt.gpt_tiny(max_len=16)
    params = gpt.init_params(jax.random.PRNGKey(1), cfg)
    prompt = np.zeros((1, 12), np.int32)
    toks = list(gpt.generate_tokens(params, prompt, 100, cfg))
    assert len(toks) == 4  # clamped to max_len - prompt_len


def test_gpt_streaming_over_grpc(gpt_server):
    import queue

    import tritonclient_tpu.grpc as grpcclient

    client = grpcclient.InferenceServerClient(gpt_server.grpc_address)
    try:
        results: "queue.Queue" = queue.Queue()
        client.start_stream(
            callback=lambda result, error: results.put((result, error))
        )
        prompt = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)
        inp = grpcclient.InferInput("INPUT_IDS", [1, 8], "INT32")
        inp.set_data_from_numpy(prompt)
        mt = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        mt.set_data_from_numpy(np.array([5], np.int32))
        client.async_stream_infer(
            "gpt", [inp, mt], enable_empty_final_response=True
        )
        received = []
        while True:
            result, error = results.get(timeout=60)
            assert error is None, error
            response = result.get_response()
            p = response.parameters.get("triton_final_response")
            final = bool(p and p.bool_param)
            out = result.as_numpy("OUTPUT_IDS")
            if out is not None and out.size:
                received.append(int(out[0]))
            if final:
                break
        client.stop_stream()
        assert len(received) == 5
        # Streamed tokens equal the model's own greedy generation.
        model = gpt_server.core._repository["gpt"]
        expected = [
            int(t[0]) for t in gpt.generate_tokens(
                model._params, prompt, 5, model.cfg,
                prefill_fn=model._prefill, decode_fn=model._decode,
            )
        ]
        assert received == expected
    finally:
        client.close()


def test_genai_perf_measures_streaming(gpt_server):
    from tritonclient_tpu.genai_perf import GenAIPerf

    analyzer = GenAIPerf(
        gpt_server.grpc_address,
        "gpt",
        input_tokens=8,
        output_tokens=4,
        vocab_size=128,
        measurement_interval_s=2.0,
        warmup_s=0.5,
    )
    summary = analyzer.measure(2)
    assert summary["errors"] == 0
    assert summary["requests"] > 0
    assert summary["output_tokens"] == 4 * summary["requests"]
    assert summary["time_to_first_token"]["p50_ms"] > 0
    assert summary["inter_token_latency"]["p50_ms"] > 0
    assert summary["output_token_throughput_per_sec"] > 0


def test_genai_perf_cli(gpt_server):
    proc = subprocess.run(
        [
            sys.executable, "-m", "tritonclient_tpu.genai_perf",
            "-m", "gpt", "-u", gpt_server.grpc_address,
            "--concurrency-range", "1:1",
            "--input-tokens", "8", "--output-tokens", "3",
            "--vocab-size", "128",
            "--measurement-interval", "1500", "--warmup-interval", "300",
            "--json",
        ],
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout.strip().splitlines()[-1])
    assert doc["model"] == "gpt"
    assert doc["results"][0]["errors"] == 0
    assert doc["results"][0]["output_tokens"] > 0


def test_gpt_flash_prefill_matches_reference():
    # Flash-prefill GPT must stream identical tokens to the reference-
    # attention model on the same weights (L=128 prompt: real kernel path
    # in interpret mode, not the fallback).
    cfg = gpt.gpt_tiny(max_len=192)
    plain = gpt.GptModel(cfg=cfg, seed=3)
    flash = gpt.GptModel(cfg=cfg, seed=3, use_flash_attention=True)
    prompt = (np.arange(2 * 128, dtype=np.int32).reshape(2, 128)
              % cfg.vocab_size)
    out_plain = [t.copy() for t in gpt.generate_tokens(
        plain._params, prompt, 4, cfg,
        prefill_fn=plain._prefill, decode_fn=plain._decode)]
    out_flash = [t.copy() for t in gpt.generate_tokens(
        flash._params, prompt, 4, cfg,
        prefill_fn=flash._prefill, decode_fn=flash._decode)]
    np.testing.assert_array_equal(np.stack(out_plain), np.stack(out_flash))


def test_gpt_overlong_prompt_fails_cleanly(gpt_server):
    """A full-length prompt must produce a per-request error response, not
    tear down the stream (round-3 review findings)."""
    import queue

    import tritonclient_tpu.grpc as grpcclient

    client = grpcclient.InferenceServerClient(gpt_server.grpc_address)
    try:
        results: "queue.Queue" = queue.Queue()
        client.start_stream(
            callback=lambda result, error: results.put((result, error))
        )
        bad = np.zeros((1, 64), np.int32)  # == max_len of the fixture model
        inp = grpcclient.InferInput("INPUT_IDS", [1, 64], "INT32")
        inp.set_data_from_numpy(bad)
        client.async_stream_infer("gpt", [inp])
        result, error = results.get(timeout=60)
        assert error is not None and "max_len" in str(error)
        # The STREAM survives: a well-formed request right after succeeds.
        good = np.array([[1, 2, 3, 4]], np.int32)
        inp2 = grpcclient.InferInput("INPUT_IDS", [1, 4], "INT32")
        inp2.set_data_from_numpy(good)
        mt = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        mt.set_data_from_numpy(np.array([2], np.int32))
        client.async_stream_infer(
            "gpt", [inp2, mt], enable_empty_final_response=True
        )
        tokens = 0
        while True:
            result, error = results.get(timeout=60)
            assert error is None, error
            response = result.get_response()
            p = response.parameters.get("triton_final_response")
            out = result.as_numpy("OUTPUT_IDS")
            if out is not None and out.size:
                tokens += 1
            if p and p.bool_param:
                break
        assert tokens == 2
        client.stop_stream()
    finally:
        client.close()


class TestContinuousBatching:
    """gpt_engine: concurrent generations share batched decode steps
    (continuous batching) — scheduling changes, results must not."""

    def test_engine_matches_single_request_path(self):
        import threading
        import time as _time

        from tritonclient_tpu.models.gpt_engine import GenerationEngine

        cfg = gpt.gpt_tiny(max_len=64)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        engine = GenerationEngine(cfg, params, max_slots=4)
        prompts = [
            np.array([[1, 5, 9, 2, 7, 3, 11, 4]], np.int32),
            np.array([[2, 4, 6]], np.int32),
            np.array([[9, 8, 7, 6, 5, 4, 3, 2, 1, 0, 1, 2]], np.int32),
            np.array([[42]], np.int32),
            np.array([[13, 21, 34]], np.int32),  # 5 requests > 4 slots
        ]
        max_news = [6, 4, 8, 3, 5]
        refs = [
            [int(t[0]) for t in gpt.generate_tokens(params, p, m, cfg)]
            for p, m in zip(prompts, max_news)
        ]
        results = [None] * len(prompts)

        def consume(i):
            q = engine.submit(prompts[i], max_news[i]).out
            toks = []
            while True:
                t = q.get(timeout=120)
                if t is None:
                    break
                toks.append(int(t[0]))
            results[i] = toks

        threads = [threading.Thread(target=consume, args=(i,))
                   for i in range(len(prompts))]
        for t in threads[:3]:
            t.start()
        _time.sleep(0.3)  # staggered joins mid-generation
        for t in threads[3:]:
            t.start()
        for t in threads:
            t.join()
        assert results == refs

    def test_cancel_terminates_in_delivery_order(self):
        """A cancelled request's None terminator is routed through the
        delivery queue: it must arrive AFTER every token already in the
        pipe, exactly once, and the freed slot must serve a new request
        with correct tokens (no cross-talk from the cancelled one)."""
        import time as _time

        from tritonclient_tpu.models.gpt_engine import GenerationEngine

        cfg = gpt.gpt_tiny(max_len=64)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        engine = GenerationEngine(cfg, params, max_slots=2)
        try:
            prompt = np.array([[1, 5, 9, 2]], np.int32)
            req = engine.submit(prompt, 40)
            got = [req.out.get(timeout=120) for _ in range(3)]
            assert all(t is not None for t in got)
            req.cancelled = True
            # Drain to the terminator; tokens may still flow first (the
            # pipeline drains in order), then exactly one None.
            tail = []
            while True:
                t = req.out.get(timeout=120)
                if t is None:
                    break
                assert not isinstance(t, BaseException), t
                tail.append(t)
            _time.sleep(0.2)
            assert req.out.empty(), "tokens delivered after the terminator"
            # Freed capacity serves a fresh request token-identically.
            p2 = np.array([[2, 4, 6]], np.int32)
            ref = [int(t[0]) for t in gpt.generate_tokens(params, p2, 5, cfg)]
            q2 = engine.submit(p2, 5).out
            toks = []
            while True:
                t = q2.get(timeout=120)
                if t is None:
                    break
                toks.append(int(t[0]))
            assert toks == ref
        finally:
            engine.shutdown()

    def test_engine_served_over_grpc_with_genai_perf(self):
        from tritonclient_tpu.genai_perf import GenAIPerf
        from tritonclient_tpu.models.gpt_engine import GptEngineModel
        from tritonclient_tpu.server import InferenceServer

        model = GptEngineModel(cfg=gpt.gpt_tiny(max_len=64), max_slots=4)
        model.warmup()
        with InferenceServer(models=[model], http=False) as s:
            # The window has to outlast the two programs the run compiles
            # inside it (a 4-lane chunk, the fused decode): about a second
            # on an idle CPU since the attention kernel is interpreted
            # here, three under tier-1's six workers.
            analyzer = GenAIPerf(
                s.grpc_address, "gpt_engine", input_tokens=8,
                output_tokens=4, vocab_size=128,
                measurement_interval_s=4.0, warmup_s=0.5,
            )
            summary = analyzer.measure(4)
        assert summary["errors"] == 0
        assert summary["requests"] > 0
        assert summary["output_tokens"] == 4 * summary["requests"]

    def test_engine_rejects_overlong_and_multirow(self):
        from tritonclient_tpu.models.gpt_engine import GptEngineModel

        model = GptEngineModel(cfg=gpt.gpt_tiny(max_len=16), max_slots=2)
        with pytest.raises(ValueError, match="max_len"):
            model.infer({"INPUT_IDS": np.zeros((1, 16), np.int32)})
        with pytest.raises(ValueError, match="one"):
            model.infer({"INPUT_IDS": np.zeros((2, 4), np.int32)})
        with pytest.raises(ValueError, match="one"):
            # 3-D input must be rejected, not silently flattened.
            model.infer({"INPUT_IDS": np.zeros((2, 3, 4), np.int32)})

    def test_engine_shutdown_terminates_queued_requests(self):
        from tritonclient_tpu.models.gpt_engine import GenerationEngine

        cfg = gpt.gpt_tiny(max_len=32)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        engine = GenerationEngine(cfg, params, max_slots=1)
        qs = [engine.submit(np.array([[1, 2]], np.int32), 4).out
              for _ in range(3)]
        engine.shutdown()
        # Every stream ends (tokens then None) within the join budget;
        # nobody hangs on an undrained admission queue.
        for q in qs:
            while True:
                t = q.get(timeout=30)
                if t is None:
                    break
        with pytest.raises(RuntimeError, match="shut down"):
            engine.submit(np.array([[1]], np.int32), 1)


class TestSampling:
    """temperature/top-k/seed sampling on the shared (seed, step) key
    schedule: single-path, one-jit scan, and the continuous-batching
    engine must produce bit-identical sampled streams."""

    def test_greedy_default_unchanged(self):
        cfg = gpt.gpt_tiny(max_len=32)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        logits = jax.random.normal(jax.random.PRNGKey(1), (2, cfg.vocab_size))
        tok = gpt.sample_token(logits, gpt.sampling_key(0, 0), 0.0, 0)
        np.testing.assert_array_equal(
            np.asarray(tok), np.asarray(jnp.argmax(logits, -1))
        )
        # top_k=1 is argmax at any temperature.
        tok1 = gpt.sample_token(logits, gpt.sampling_key(7, 3), 2.0, 1)
        np.testing.assert_array_equal(
            np.asarray(tok1), np.asarray(jnp.argmax(logits, -1))
        )

    def test_seeded_sampling_deterministic_and_varied(self):
        cfg = gpt.gpt_tiny(max_len=48)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        prompt = np.array([[3, 1, 4, 1, 5]], np.int32)
        kw = dict(temperature=1.0, top_k=20, seed=123)
        a = [int(t[0]) for t in gpt.generate_tokens(
            params, prompt, 8, cfg, **kw)]
        b = [int(t[0]) for t in gpt.generate_tokens(
            params, prompt, 8, cfg, **kw)]
        assert a == b  # same seed -> identical stream
        c = [int(t[0]) for t in gpt.generate_tokens(
            params, prompt, 8, cfg, temperature=1.0, top_k=20, seed=124)]
        assert a != c  # different seed -> (overwhelmingly) different
        scan = np.asarray(gpt.generate_scan(
            params, jnp.asarray(prompt), 8, cfg, **kw))[0].tolist()
        assert a == scan  # loop and one-jit scan share the key schedule

    def test_engine_sampled_matches_single_path(self):
        from tritonclient_tpu.models.gpt_engine import GenerationEngine

        cfg = gpt.gpt_tiny(max_len=48)
        params = gpt.init_params(jax.random.PRNGKey(0), cfg)
        engine = GenerationEngine(cfg, params, max_slots=3)
        jobs = [
            (np.array([[3, 1, 4, 1, 5]], np.int32), 6, 1.0, 10, 11),
            (np.array([[2, 7, 2]], np.int32), 5, 0.7, 0, 22),
            (np.array([[9, 9]], np.int32), 4, 0.0, 0, 0),  # greedy mixed in
        ]
        refs = [
            [int(t[0]) for t in gpt.generate_tokens(
                params, p, m, cfg, temperature=temp, top_k=tk, seed=sd)]
            for p, m, temp, tk, sd in jobs
        ]
        qs = [engine.submit(p, m, temperature=temp, top_k=tk, seed=sd).out
              for p, m, temp, tk, sd in jobs]
        got = []
        for q in qs:
            toks = []
            while True:
                t = q.get(timeout=120)
                if t is None:
                    break
                toks.append(int(t[0]))
            got.append(toks)
        assert got == refs

    def test_sampling_over_the_wire(self, gpt_server):
        import queue

        import tritonclient_tpu.grpc as grpcclient

        client = grpcclient.InferenceServerClient(gpt_server.grpc_address)
        try:
            results: "queue.Queue" = queue.Queue()
            client.start_stream(
                callback=lambda result, error: results.put((result, error))
            )

            def run_once():
                prompt = np.array([[3, 1, 4, 1, 5, 9, 2, 6]], np.int32)
                inp = grpcclient.InferInput("INPUT_IDS", [1, 8], "INT32")
                inp.set_data_from_numpy(prompt)
                mt = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
                mt.set_data_from_numpy(np.array([5], np.int32))
                tp = grpcclient.InferInput("TEMPERATURE", [1], "FP32")
                tp.set_data_from_numpy(np.array([0.8], np.float32))
                tk = grpcclient.InferInput("TOP_K", [1], "INT32")
                tk.set_data_from_numpy(np.array([16], np.int32))
                sd = grpcclient.InferInput("SEED", [1], "INT64")
                sd.set_data_from_numpy(np.array([99], np.int64))
                client.async_stream_infer(
                    "gpt", [inp, mt, tp, tk, sd],
                    enable_empty_final_response=True,
                )
                toks = []
                while True:
                    result, error = results.get(timeout=60)
                    assert error is None, error
                    response = result.get_response()
                    p = response.parameters.get("triton_final_response")
                    out = result.as_numpy("OUTPUT_IDS")
                    if out is not None and out.size:
                        toks.append(int(out[0]))
                    if p and p.bool_param:
                        return toks

            assert run_once() == run_once()  # same SEED -> same stream
            client.stop_stream()
        finally:
            client.close()


def test_int64_and_negative_seeds_consistent_across_paths():
    """Any int64 wire seed (incl. negative / >= 2**31) canonicalizes to
    the same 31-bit key on every path — no engine overflow, identical
    streams (round-3 review findings)."""
    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    cfg = gpt.gpt_tiny(max_len=32)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.array([[3, 1, 4]], np.int32)
    for seed in (2**31, -1, 2**62 + 17):
        ref = [int(t[0]) for t in gpt.generate_tokens(
            params, prompt, 5, cfg, temperature=1.0, top_k=8, seed=seed)]
        engine = GenerationEngine(cfg, params, max_slots=2)
        q = engine.submit(prompt, 5, temperature=1.0, top_k=8, seed=seed).out
        got = []
        while True:
            t = q.get(timeout=60)
            if t is None:
                break
            assert not isinstance(t, BaseException), t
            got.append(int(t[0]))
        engine.shutdown()
        assert got == ref, f"seed {seed}"


def test_sampled_requests_without_seed_vary():
    """TEMPERATURE without SEED must not return the same 'random' stream
    every time (server draws entropy; explicit SEED stays reproducible)."""
    from tritonclient_tpu.models.gpt import sampling_inputs

    seen = {
        sampling_inputs({"TEMPERATURE": np.array([0.8], np.float32)})[2]
        for _ in range(8)
    }
    assert len(seen) > 1
    # greedy default keeps the stable seed 0
    assert sampling_inputs({})[2] == 0


class TestEngineCancellation:
    def test_consumer_close_releases_slot(self):
        """Closing the decoupled generator mid-generation (client
        disconnect) marks the request cancelled so the engine frees the
        slot instead of generating dead tokens to max_new."""
        from tritonclient_tpu.models.gpt_engine import GptEngineModel

        model = GptEngineModel(cfg=gpt.gpt_tiny(max_len=64), max_slots=2)
        gen = model.infer(
            {"INPUT_IDS": np.array([[3, 1, 4]], np.int32),
             "MAX_TOKENS": np.array([40], np.int32)}
        )
        first = next(gen)
        assert first["OUTPUT_IDS"].shape == (1,)
        req = model.engine._slot_req[
            next(i for i, r in enumerate(model.engine._slot_req)
                 if r is not None)
        ]
        gen.close()  # transport went away
        assert req.cancelled
        # The slot frees promptly (well before 40 tokens' worth of work):
        # a fresh 2-slot engine admits two new requests immediately.
        import time as _time

        deadline = _time.time() + 30
        while _time.time() < deadline:
            if all(r is None or r.cancelled
                   for r in model.engine._slot_req):
                break
            _time.sleep(0.05)
        outs = [model.engine.submit(np.array([[7, 7]], np.int32), 2).out
                for _ in range(2)]
        for q in outs:
            toks = []
            while True:
                t = q.get(timeout=60)
                if t is None:
                    break
                assert not isinstance(t, BaseException)
                toks.append(t)
            assert len(toks) == 2
        model.engine.shutdown()


class TestAioBlockingStream:
    """Blocking decoupled models over the grpc.aio front-end: tokens must
    drain through the executor (one slow stream cannot stall the loop),
    and a client cancel mid-generation must release the engine slot."""

    @pytest.fixture()
    def aio_server(self, monkeypatch):
        from tritonclient_tpu.models.gpt_engine import GptEngineModel
        from tritonclient_tpu.server import InferenceServer

        monkeypatch.setenv("TPU_SERVER_GRPC_AIO", "1")
        model = GptEngineModel(cfg=gpt.gpt_tiny(max_len=256), max_slots=2)
        try:
            with InferenceServer(models=[model], http=False) as s:
                yield s, model
        finally:
            model.engine.shutdown()

    def test_stream_and_cancel(self, aio_server):
        import queue
        import time as _time

        import tritonclient_tpu.grpc as grpcclient

        server, model = aio_server
        ref = [
            int(t[0]) for t in gpt.generate_tokens(
                model.engine.params, np.array([[5, 9, 2]], np.int32), 6,
                model.cfg,
            )
        ]

        # Full stream: tokens arrive and match the single-request path.
        c = grpcclient.InferenceServerClient(server.grpc_address)
        done: "queue.Queue" = queue.Queue()
        c.start_stream(callback=lambda result, error: done.put((result, error)))
        inp = grpcclient.InferInput("INPUT_IDS", [1, 3], "INT32")
        inp.set_data_from_numpy(np.array([[5, 9, 2]], np.int32))
        mt = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        mt.set_data_from_numpy(np.array([6], np.int32))
        c.async_stream_infer(
            "gpt_engine", [inp, mt], enable_empty_final_response=True
        )
        got = []
        while True:
            r, e = done.get(timeout=120)
            assert e is None, e
            p = r.get_response().parameters.get("triton_final_response")
            if p and p.bool_param:
                break
            got.append(int(r.as_numpy("OUTPUT_IDS")[0]))
        assert got == ref
        c.stop_stream()

        # Cancel mid-generation: the drain must stop and free the slot.
        c2 = grpcclient.InferenceServerClient(server.grpc_address)
        done2: "queue.Queue" = queue.Queue()
        c2.start_stream(callback=lambda result, error: done2.put((result, error)))
        mt_long = grpcclient.InferInput("MAX_TOKENS", [1], "INT32")
        # ~250 decode steps: long enough that the RPC cancel always lands
        # mid-generation (a short run could complete first and pass this
        # test vacuously).
        mt_long.set_data_from_numpy(np.array([250], np.int32))
        c2.async_stream_infer("gpt_engine", [inp, mt_long])
        r, e = done2.get(timeout=120)  # at least one token flowing
        assert e is None
        live = [req for req in model.engine._slot_req if req is not None]
        assert live, "request should occupy a slot mid-generation"
        target = live[0]
        c2.stop_stream(cancel_requests=True)
        c2.close()
        deadline = _time.time() + 30
        while _time.time() < deadline and not target.cancelled:
            _time.sleep(0.1)
        # The cancel must actually propagate (not vacuous completion).
        assert target.cancelled, (
            "cancelled stream did not mark the engine request cancelled"
        )
        c.close()


def test_warm_admission_requires_an_idle_engine():
    """ADVICE r5 #1: warm_admission rewrites live slot state; with a
    request in flight it must raise instead of silently corrupting the
    generation, and it must work again once the engine drains."""
    import time as _time

    from tritonclient_tpu.models.gpt_engine import GenerationEngine

    cfg = gpt.gpt_tiny(max_len=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    engine = GenerationEngine(cfg, params, max_slots=2)
    try:
        engine.warm_admission()  # idle engine: allowed
        req = engine.submit(np.array([[1, 2, 3]], np.int32), 30)
        assert req.out.get(timeout=120) is not None  # slot occupied
        with pytest.raises(RuntimeError, match="idle engine"):
            engine.warm_admission()
        req.cancelled = True
        while req.out.get(timeout=120) is not None:
            pass
        # The freed slot is applied at the engine's next loop top; the
        # guard must flip back to allowed once it lands.
        deadline = _time.time() + 30
        while True:
            try:
                engine.warm_admission()
                break
            except RuntimeError:
                if _time.time() > deadline:
                    raise
                _time.sleep(0.05)  # tpulint: disable=TPU001 - poll loop
    finally:
        engine.shutdown()
