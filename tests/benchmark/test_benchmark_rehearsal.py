"""CPU rehearsal of a whole benchmark run at the test-only tiny cell, which
is added as files and entries only: the last line's exact keys, both kinds
of run, and the timed path broken underneath coming out as not correct. It
says nothing about the device: every number here is from the CPU backend."""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

from _bench_tiny import CELL, OPEN_CELL, REPO, tiny_benchmark_file
from benchmarks import harness


@pytest.fixture(autouse=True)
def _no_shared_compile_cache(monkeypatch):
    # A test process keeps JAX's configuration to itself.
    monkeypatch.setattr(harness, "configure_compile_cache", lambda: "(none)")


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return tiny_benchmark_file(tmp_path_factory.mktemp("bench"))


def _run(bench_file, trace, seed=2**31 + 77, seconds=1.5):
    return harness.run(CELL, seed, seconds, trace, require_tpu=False,
                       benchmark_file=bench_file)


CHECK_KEYS = {"served_logit_gap_max", "failed_requests", "checked_tokens"}
# (the tiny configuration sets no limit on the 99th-percentile gap: a number
# without a limit is not compared and not printed)


def test_untraced_run_prints_exactly_the_contracts_keys(bench_file, capsys):
    result = _run(bench_file, trace=False)
    # `check` is the key of its own that comes last; the rest is the contract's.
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8
    assert set(result["metrics"]) == {"output_tokens_per_s", "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert result["device"] == {
        "platform": "cpu", "kind": jax.devices()[0].device_kind,
        "count": len(jax.devices()), "memory_peak_bytes": 0}
    assert set(result["check"]) == CHECK_KEYS
    assert result["check"]["checked_tokens"]["value"] >= 8
    assert json.loads(json.dumps(result)) == result
    out, err = capsys.readouterr()
    assert "compilations inside the window: 0" in out
    assert "prompt tokens min/mean/max" in out
    # what one stream's user sees is printed in every run, traced or not
    latency = json.loads(next(line for line in out.splitlines()
                              if line.startswith("latency: "))[9:])
    assert set(latency) == {"ttft_p50_ms", "ttft_p95_ms", "itl_p50_ms",
                            "itl_p95_ms"}
    assert 0 < latency["ttft_p50_ms"] <= latency["ttft_p95_ms"]
    delivered = next(line for line in out.splitlines()
                     if line.startswith("delivered by second: "))
    assert "host lag worst" in delivered
    # each number compared, beside its limit, ends standard error
    assert [line.split(":")[0] for line in err.strip().splitlines()[-3:]] == [
        "check served_logit_gap_max", "check failed_requests",
        "check checked_tokens"]


def test_an_open_loop_mix_is_added_as_a_data_file(bench_file, capsys):
    """`tiny-open` sets `loop: open`, a rate, bursts of 2 and a shared prefix;
    nothing but its data file and its entry was added for it."""
    result = harness.run(OPEN_CELL, 2**31 + 78, 2.0, False, require_tpu=False,
                         benchmark_file=bench_file)
    assert result["correct"] is True and result["failed"] == 0
    # 6 requests/s for 2 s in bursts of 2: about 12, whatever the service time
    assert 8 <= result["attempted"] <= 16 and result["attempted"] % 2 == 0
    assert "generator lag worst" in capsys.readouterr().out


def test_a_four_chip_cell_hands_its_chips_to_the_adapter(tmp_path):
    """`chips: 4` in the cell's entry is all a tensor-parallel cell adds: the
    adapter builds the tp mesh over that many devices (virtual ones here)."""
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 (virtual) devices")
    path = tiny_benchmark_file(tmp_path)
    with open(path) as f:
        bench = json.load(f)
    bench["workloads"][0]["chips"] = 4
    with open(path, "w") as f:
        json.dump(bench, f)
    from tritonclient_tpu.models import gpt_engine

    meshes = []
    init = gpt_engine.GenerationEngine.__init__

    def spy(self, *args, **kwargs):
        meshes.append(kwargs.get("mesh"))
        return init(self, *args, **kwargs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gpt_engine.GenerationEngine, "__init__", spy)
        result = harness.run(CELL, 2**31 + 79, 1.5, False, require_tpu=False,
                             benchmark_file=path)
    assert dict(meshes[0].shape) == {"tp": 4}
    assert result["correct"] is True and result["failed"] == 0


def test_open_loop_gaps_are_the_same_set_for_every_seed():
    from benchmarks.loops import open as open_loop

    mix = {"set_size": 16, "rate": 4.0, "burst": 2}
    a, b = (open_loop.arrival_gaps(mix, seed, 0) for seed in (5, 2**31 + 6))
    assert sorted(a) == sorted(b) and list(a) != list(b)
    assert abs(a.mean() - 0.5) < 1e-9      # bursts of 2 at 4 requests/s
    assert list(open_loop.arrival_gaps(mix, 5, 0)) == list(a)
    with pytest.raises(ValueError):
        open_loop.validate(dict(mix, rate=None, clients=4))


def test_traced_run_reports_the_layers_and_the_device_times(bench_file):
    result = _run(bench_file, trace=True)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "check"]
    assert result["correct"] is True
    # Shares of a peak or a roofline are left out off the chip, never 0.
    assert set(result["metrics"]) == {
        "ttft_p50_ms", "ttft_p95_ms", "itl_p95_ms",
        "decode_slots_occupied", "fused_step_share", "kv_pages_peak_share"}
    assert 0 < result["metrics"]["decode_slots_occupied"]["value"] <= 100
    assert 0 < result["metrics"]["kv_pages_peak_share"]["value"] <= 100
    device = result["device"]
    assert 0 < device["busy_s"] < device["window_s"]
    breakdown = result["breakdown"]
    assert 0 < len(breakdown["device_ops"]) <= 10
    assert 0 < len(breakdown["idle_gaps"]) <= 10
    assert not os.path.exists(os.path.join(REPO, harness.SCRATCH_DIR, "trace"))


def _alter_a_token(monkeypatch):
    """Every 7th position of every slot: the token one up from the one the
    step produced, altered where it is produced (inside the decode step)."""
    from tritonclient_tpu.models import gpt_engine

    produce = gpt_engine._decode_step_paged

    def broken(params, k_pool, v_pool, btabs, tokens, pos, *rest, **kw):
        nxt, k_pool, v_pool = produce(params, k_pool, v_pool, btabs, tokens,
                                      pos, *rest, **kw)
        vocab = params["embed"]["tok"].shape[0]
        return (jax.numpy.where(pos % 7 == 0, (nxt + 1) % vocab, nxt),
                k_pool, v_pool)

    monkeypatch.setattr(gpt_engine, "_decode_step_paged", broken)


def _lose_a_token(monkeypatch):
    """The stream of every request that asks for 11 tokens or more (the window's only) ends one
    token short."""
    from tritonclient_tpu.models.gpt_engine import GptEngineModel

    infer = GptEngineModel.infer

    def short(self, inputs, parameters=None):
        asked = int(inputs["MAX_TOKENS"].reshape(-1)[0])
        responses = infer(self, inputs, parameters)
        if asked < 11:
            return responses
        return (r for i, r in enumerate(responses) if i != 3)

    monkeypatch.setattr(GptEngineModel, "infer", short)


def _stale_page(monkeypatch):
    """Prefill writes its keys one page off: decode reads a page the
    prompt never reached."""
    from tritonclient_tpu.models import gpt_engine

    prefill = gpt_engine._prefill_chunk_paged

    def broken(params, k_pool, v_pool, chunks, btabs, *rest, **kw):
        shifted = jax.numpy.roll(btabs, 1, axis=1)
        return prefill(params, k_pool, v_pool, chunks, shifted, *rest, **kw)

    monkeypatch.setattr(gpt_engine, "_prefill_chunk_paged", broken)


@pytest.mark.parametrize("fault,failing", [
    (_alter_a_token, "served_logit_gap_max"),
    (_stale_page, "served_logit_gap_max"),
    (_lose_a_token, "failed_requests"),
], ids=["token_altered", "kv_page_misplaced", "stream_cut_short"])
def test_a_broken_timed_path_comes_out_as_not_correct(bench_file, monkeypatch,
                                                      fault, failing):
    fault(monkeypatch)
    result = _run(bench_file, trace=False)
    assert result["correct"] is False
    entry = result["check"][failing]
    assert entry["value"] > entry["limit"]
    if failing == "failed_requests":
        assert result["failed"] == entry["value"] > 0


def test_without_a_tpu_there_is_no_result(bench_file):
    with pytest.raises(harness.BenchmarkError, match="not 'tpu'"):
        harness.run(CELL, 1, 1.0, False, benchmark_file=bench_file)


def _command_line(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--workload",
         "gpt2-xl.chat", "--seed", "3000000001", "--seconds", "1",
         "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_line_refuses_to_run_without_a_tpu():
    proc = _command_line(REPO)
    assert proc.returncode != 0
    assert "not 'tpu'" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_command_line_refuses_a_directory_without_the_program(tmp_path):
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for base in ("benchmarks", os.path.join("tests", "benchmark")):
        shutil.copytree(os.path.join(REPO, base), tmp_path / base,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command_line(str(tmp_path))
    assert proc.returncode != 0
    assert "tritonclient_tpu" in proc.stderr
    assert proc.stdout.strip() == ""
