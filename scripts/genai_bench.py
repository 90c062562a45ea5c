"""LLM serving-plane benchmark artifact (VERDICT r3 #6; paged-KV round).

Drives the paged-KV continuous-batching engine (models/gpt_engine.py)
through the full gRPC streaming stack with the genai_perf instrument and
writes GENAI_r{N}.json at the repo root:

  * TTFT/ITL percentiles and token throughput at concurrency
    {1, 4, 8, 16}, each window extended until it holds >= 150 requests;
  * a mixed prompt-length point (--prompt-len-dist short:8,long:1) with
    per-bucket TTFT rows;
  * the prefix-caching pair: a cold window (unique prompts) vs a
    shared-prefix window (identical first tokens across requests), with
    the measured hit rate from the engine's own event counters and the
    TTFT win recorded;
  * the paged-vs-contiguous no-regression point: the engine at the
    SAME workload (input 32 / output 16 / c8 / same window) as the
    contiguous-bank baseline captured on this host before the rework;
  * the single-loop GptModel comparator at c=8 (the engine's throughput
    claim, recorded instead of asserted).

Run:  python scripts/genai_bench.py [round_number]
"""

import json
import os
import sys
import time

sys.path.append(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.setswitchinterval(0.0002)

MIN_REQUESTS = 150


def _drain(req):
    while True:
        tok = req.out.get(timeout=300)
        if tok is None:
            return
        if isinstance(tok, BaseException):
            raise tok  # surface warmup compile/engine errors immediately


def _wait_idle(engine, timeout=60.0):
    """The warm request's slot-free travels through the delivery thread;
    warm_admission requires the engine to have PROCESSED it, not just
    the terminator to have been consumed."""
    deadline = time.time() + timeout
    while time.time() < deadline:
        if all(r is None for r in engine._slot_req):
            return
        time.sleep(0.05)  # tpulint: disable=TPU001 (sync bench poll)
    raise RuntimeError(f"engine not idle after warmup: {engine._slot_req}")


def _measure_min_requests(perf, c, initial_s, min_req=MIN_REQUESTS,
                          max_s=1800.0):
    """One window, re-measured once with a scaled interval if the first
    held too few requests (CPU hosts are slow enough that a fixed window
    cannot satisfy a request-count floor at every concurrency)."""
    perf.measurement_interval_s = min(initial_s, max_s)
    summary = perf.measure(c)
    if 0 < summary["requests"] < min_req:
        scale = min_req / summary["requests"] * 1.15
        perf.measurement_interval_s = min(
            perf.measurement_interval_s * scale, max_s
        )
        print(f"  c{c}: {summary['requests']} requests < {min_req}; "
              f"re-measuring over {perf.measurement_interval_s:.0f}s",
              file=sys.stderr)
        summary = perf.measure(c)
    return summary


def main():
    rnd = sys.argv[1] if len(sys.argv) > 1 else os.environ.get("ROUND", "06")
    out_tokens = int(os.environ.get("GENAI_OUTPUT_TOKENS", "8"))

    import jax

    from tritonclient_tpu import _compile_cache, _memscope, _stepscope
    from tritonclient_tpu.genai_perf import GenAIPerf
    from tritonclient_tpu.models.gpt import GptModel
    from tritonclient_tpu.models.gpt_engine import GptEngineModel
    from tritonclient_tpu.server import InferenceServer

    import numpy as np

    _compile_cache.configure()
    engine_model = GptEngineModel()
    loop_model = GptModel()
    engine_model.warmup()
    loop_model.warmup()
    engine = engine_model.engine
    # Warm the chunked-prefill and decode shapes at the measured prompt
    # lengths (32 / 128 / 160): first-use compiles must not land inside
    # a window.
    for warm_len in (32, 128, 160):
        _drain(engine.submit(np.ones((1, warm_len), np.int32), 2))
    _wait_idle(engine)
    # Load the slot-state update (one executable whatever a burst
    # carries; warm_prefill makes it for each lane bucket's result), so
    # no first-use compile lands inside a measured window.
    engine.warm_admission()
    # ... and the batched chunk-prefill family: every lane bucket ×
    # the context buckets the measured prompt lengths pass through
    # (chunks of a 160-token prompt traverse ceil(end/bs) = 2..10 →
    # buckets {2,4,8,16}). A synchronized churn burst otherwise hits
    # its first k>1 lane shape mid-window, paying a multi-second XLA
    # compile inside the measurement.
    bs = engine.block_size
    ctx = set()
    for warm_len in (32, 128, 160):
        end = 0
        while end < warm_len:
            end = min(end + engine.prefill_chunk, warm_len)
            ctx.add(-(-end // bs))
    engine.warm_prefill(ctx_blocks=sorted(ctx))
    for tok in loop_model.infer(
        {"INPUT_IDS": np.ones((1, 32), np.int32),
         "MAX_TOKENS": np.array([2], np.int32)}
    ):
        pass

    # Contiguous-bank baseline captured on this host BEFORE the paged
    # rework (same model, same workload knobs): the no-regression
    # denominator. Absent file -> the comparison is skipped, not faked.
    contig = None
    for path in (
        os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "CONTIG_BASELINE_c8.json"),
        "/tmp/contig_baseline_c8.json",
    ):
        if os.path.exists(path):
            with open(path) as f:
                contig = json.load(f)
            break

    result = {
        "round": rnd,
        "platform": jax.devices()[0].platform,
        "output_tokens": out_tokens,
        "kv": {
            "block_size": engine.block_size,
            "n_blocks": engine._pool.n_blocks,
            "prefill_chunk": engine.prefill_chunk,
        },
        "engine": {},   # gpt_engine: continuous batching over the block pool
        "single_loop_c8": None,  # GptModel: one generation loop per request
    }
    with InferenceServer(models=[engine_model, loop_model],
                         http=False) as server:
        perf = GenAIPerf(
            server.grpc_address,
            model_name="gpt_engine",
            input_tokens=32,
            output_tokens=out_tokens,
            vocab_size=engine_model.cfg.vocab_size,
            warmup_s=2.0,
        )
        # -- main sweep: c{1,4,8,16}, >= 150 requests per level ------------
        per_worker_rps = None
        for c in (1, 4, 8, 16):
            if per_worker_rps:
                # Seed the window from the previous level's request rate
                # (batching efficiency only improves it).
                initial = min(max(MIN_REQUESTS / (per_worker_rps * c)
                                  * 1.25, 45.0), 1800.0)
            else:
                initial = 60.0
            summary = _measure_min_requests(perf, c, initial)
            per_worker_rps = (summary["requests"]
                              / summary["duration_s"] / c) or None
            result["engine"][f"c{c}"] = {
                "concurrency": c,
                "requests": summary["requests"],
                "errors": summary["errors"],
                "duration_s": summary["duration_s"],
                "output_token_throughput_per_sec": summary[
                    "output_token_throughput_per_sec"],
                "request_throughput_per_sec": summary[
                    "request_throughput_per_sec"],
                "ttft_ms": summary["time_to_first_token"],
                "itl_ms": summary["inter_token_latency"],
            }
            if _memscope.enabled():
                # Peak KV/device bytes at this concurrency so memory
                # growth across the sweep is visible next to throughput.
                result["engine"][f"c{c}"].update(
                    _memscope.peaks("gpt_engine"))
            print(f"gpt_engine c{c}: {summary['requests']} req, "
                  f"{summary['output_token_throughput_per_sec']} tok/s, "
                  f"ttft p99 "
                  f"{summary['time_to_first_token']['p99_ms']} ms",
                  file=sys.stderr)

        # -- mixed prompt lengths (short:8,long:1 at c8) -------------------
        mixed = GenAIPerf(
            server.grpc_address,
            model_name="gpt_engine",
            input_tokens=32,
            output_tokens=out_tokens,
            vocab_size=engine_model.cfg.vocab_size,
            warmup_s=2.0,
            prompt_len_dist="short:8,long:1",  # short=32, long=128
        )
        summary = _measure_min_requests(
            mixed, 8, initial_s=MIN_REQUESTS / (per_worker_rps * 8) * 1.6
        )
        result["mixed_prompt_len_c8"] = {
            "prompt_len_dist": "short:8,long:1",
            "requests": summary["requests"],
            "errors": summary["errors"],
            "output_token_throughput_per_sec": summary[
                "output_token_throughput_per_sec"],
            "ttft_ms": summary["time_to_first_token"],
            "ttft_by_prompt_len": summary["ttft_by_prompt_len"],
            "itl_ms": summary["inter_token_latency"],
        }
        print(f"mixed-length c8: {summary['requests']} req, per-bucket "
              f"ttft {summary['ttft_by_prompt_len']}", file=sys.stderr)

        # -- prefix caching: cold vs shared-prefix TTFT --------------------
        # Same prompt length (160 = 10 blocks) both windows; the shared
        # window's prompts agree on their first 144 tokens (9 full
        # blocks), so admissions after the first resolve 9 of 10 pages
        # from cache. Cold first: its unique prompts never hit.
        prefix_kw = dict(
            url=server.grpc_address, model_name="gpt_engine",
            input_tokens=160, output_tokens=out_tokens,
            vocab_size=engine_model.cfg.vocab_size, warmup_s=2.0,
        )
        cold = GenAIPerf(**prefix_kw)
        cold_summary = _measure_min_requests(
            cold, 4, initial_s=60.0, min_req=100
        )
        ev0 = engine._prefix.snapshot_events()
        shared = GenAIPerf(**prefix_kw, shared_prefix_tokens=144)
        shared_summary = _measure_min_requests(
            shared, 4, initial_s=60.0, min_req=100
        )
        ev1 = engine._prefix.snapshot_events()
        hits = ev1["hit"] - ev0["hit"]
        misses = ev1["miss"] - ev0["miss"]
        hit_rate = round(hits / (hits + misses), 4) if hits + misses else 0.0
        cold_ttft = cold_summary["time_to_first_token"]
        shared_ttft = shared_summary["time_to_first_token"]
        result["prefix_cache_c4"] = {
            "prompt_tokens": 160,
            "shared_prefix_tokens": 144,
            "cold": {
                "requests": cold_summary["requests"],
                "ttft_ms": cold_ttft,
                "output_token_throughput_per_sec": cold_summary[
                    "output_token_throughput_per_sec"],
            },
            "shared": {
                "requests": shared_summary["requests"],
                "ttft_ms": shared_ttft,
                "output_token_throughput_per_sec": shared_summary[
                    "output_token_throughput_per_sec"],
            },
            "prefix_hit_rate": hit_rate,
            "prefix_events_delta": {"hit": hits, "miss": misses,
                                    "evict": ev1["evict"] - ev0["evict"]},
            "ttft_p50_win": round(
                cold_ttft["p50_ms"] / shared_ttft["p50_ms"], 3
            ) if shared_ttft["p50_ms"] else None,
        }
        print(f"prefix cache: hit rate {hit_rate}, ttft p50 "
              f"{cold_ttft['p50_ms']} -> {shared_ttft['p50_ms']} ms "
              f"(win {result['prefix_cache_c4']['ttft_p50_win']}x)",
              file=sys.stderr)

        # -- paged vs contiguous, same workload ----------------------------
        # Mirror the pre-rework baseline exactly: input 32 / output 16 /
        # c8 / 45 s window on this host. stepscope counters run through
        # this window to attribute per-phase overhead (PERF.md).
        _stepscope.configure(_stepscope.MODE_COUNTERS)
        _stepscope.reset()
        regress = GenAIPerf(
            server.grpc_address, model_name="gpt_engine",
            input_tokens=32, output_tokens=16,
            vocab_size=engine_model.cfg.vocab_size,
            measurement_interval_s=float(
                (contig or {}).get("interval_s", 45.0)),
            warmup_s=2.0,
        )
        reg_summary = regress.measure(8)
        phase_us = {}
        for rec in _stepscope.dump()["records"]:
            if rec["phase"] in _stepscope.LOOP_STATES:
                continue  # dispatches only: what the baseline's figures are
            phase_us.setdefault(rec["phase"], []).append(rec["total_us"])
        _stepscope.configure(_stepscope.MODE_OFF)
        result["stepscope_per_phase_us"] = {
            phase: {
                "n": len(vals),
                "p50_us": sorted(vals)[len(vals) // 2],
                "mean_us": round(sum(vals) / len(vals), 1),
            }
            for phase, vals in sorted(phase_us.items())
        }
        result["paged_c8_contig_workload"] = {
            "input_tokens": 32, "output_tokens": 16,
            "requests": reg_summary["requests"],
            "errors": reg_summary["errors"],
            "output_token_throughput_per_sec": reg_summary[
                "output_token_throughput_per_sec"],
            "ttft_ms": reg_summary["time_to_first_token"],
            "itl_ms": reg_summary["inter_token_latency"],
        }
        if contig:
            result["contiguous_baseline_c8"] = contig
            base = contig["output_token_throughput_per_sec"]
            result["paged_vs_contiguous_c8"] = round(
                reg_summary["output_token_throughput_per_sec"] / base, 4
            )
            print(f"paged vs contiguous c8: "
                  f"{reg_summary['output_token_throughput_per_sec']} vs "
                  f"{base} tok/s "
                  f"({result['paged_vs_contiguous_c8']}x)", file=sys.stderr)

        # -- single-loop comparator ----------------------------------------
        loop_perf = GenAIPerf(
            server.grpc_address, model_name="gpt",
            input_tokens=32, output_tokens=out_tokens,
            vocab_size=engine_model.cfg.vocab_size,
            measurement_interval_s=90.0, warmup_s=2.0,
        )
        summary = loop_perf.measure(8)
        result["single_loop_c8"] = {
            "concurrency": 8,
            "requests": summary["requests"],
            "errors": summary["errors"],
            "output_token_throughput_per_sec": summary[
                "output_token_throughput_per_sec"],
            "ttft_ms": summary["time_to_first_token"],
            "itl_ms": summary["inter_token_latency"],
        }
        print(f"gpt (single loop) c8: "
              f"{summary['output_token_throughput_per_sec']} tok/s",
              file=sys.stderr)

    eng8 = result["engine"].get("c8", {})
    eng1 = result["engine"].get("c1", {})
    single = result["single_loop_c8"] or {}
    if single.get("output_token_throughput_per_sec"):
        result["engine_speedup_c8"] = round(
            eng8.get("output_token_throughput_per_sec", 0)
            / single["output_token_throughput_per_sec"], 2
        )
    # Gate (VERDICT r4 #4, extended for the paged round): the engine must
    # buy throughput WITHOUT selling TTFT — >= 1.3x single-loop token
    # throughput at c8 AND TTFT p99 at c8 <= 2.5x its own c1 value — and
    # the paged pool must hold >= 0.95x of the contiguous bank on the
    # same workload. genai_vs_baseline >= 1.0 means all hold; the min
    # names the binding constraint.
    ttft8 = (eng8.get("ttft_ms") or {}).get("p99_ms", 0)
    ttft1 = (eng1.get("ttft_ms") or {}).get("p99_ms", 0)
    if ttft1 and ttft8 and result.get("engine_speedup_c8"):
        result["ttft_p99_c8_over_c1"] = round(ttft8 / ttft1, 2)
        terms = [
            result["engine_speedup_c8"] / 1.3,
            2.5 / result["ttft_p99_c8_over_c1"],
        ]
        if result.get("paged_vs_contiguous_c8"):
            terms.append(result["paged_vs_contiguous_c8"] / 0.95)
        result["genai_vs_baseline"] = round(min(terms), 4)
    else:
        # A degenerate run (empty window, failed comparator) must read
        # as a FAILED gate, not an absent one.
        result["genai_vs_baseline"] = 0.0
        result["gate_inputs_missing"] = True
    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        f"GENAI_r{rnd}.json",
    )
    with open(path, "w") as f:
        json.dump(result, f, indent=1)
    # Compact driver/judge-parseable line; the full detail is in the file.
    print(json.dumps({
        "metric": "gpt_engine_c8_token_throughput",
        "value": eng8.get("output_token_throughput_per_sec"),
        "unit": "tok/s",
        "engine_speedup_c8": result.get("engine_speedup_c8"),
        "ttft_p99_c8_over_c1": result.get("ttft_p99_c8_over_c1"),
        "paged_vs_contiguous_c8": result.get("paged_vs_contiguous_c8"),
        "prefix_hit_rate": result.get("prefix_cache_c4", {}).get(
            "prefix_hit_rate"),
        "genai_vs_baseline": result.get("genai_vs_baseline"),
        "detail_file": os.path.basename(path),
    }))


if __name__ == "__main__":
    main()
