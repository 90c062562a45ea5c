"""The readers of the program's own spans: a traced tiny run on the CPU
reports all eight, joins every finished request to its timeline and sees
the five terms of a first-token wait add up; a key that two requests share
makes every joined metric None; the two counters on hand-made records. It
says nothing about the device: every number here is from the CPU backend."""

import importlib.util
import math
import os
import time
import types

import numpy as np
import pytest

from _bench_tiny import CELL, REPO, tiny_benchmark_file
from benchmarks import harness, request_spans
from benchmarks.client import RequestLog
from benchmarks.traffic import Request

SPAN_METRICS = ("wire_ingress_p95_ms", "core_ingress_p95_ms", "egress_p95_ms",
                "admission_wait_p95_ms", "prefill_span_p50_ms",
                "engine_itl_p95_ms")
NEW_METRICS = SPAN_METRICS + ("prefill_run_mean", "engine_ticket_wait_share")


def _reader(name):
    path = os.path.join(REPO, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("_spans_test_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the tiny cell; the Observations the readers saw
    and the join as it stood right after the run (the ring is process-wide,
    so later tests that reset stepscope do not disturb it)."""
    seen = {}
    read_metrics = harness.read_metrics

    def keep(bench, kind, entries, root, obs):
        seen["obs"] = obs
        seen["pairs"] = request_spans.joined(obs)
        return read_metrics(bench, kind, entries, root, obs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "configure_compile_cache", lambda: "(none)")
        patch.setattr(harness, "read_metrics", keep)
        # A trace directory of this file's own: the rehearsal's traced run
        # may be under way in another test worker, and each run clears its.
        patch.setattr(harness, "SCRATCH_DIR",
                      os.path.join(harness.SCRATCH_DIR, "spans_test"))
        seen["result"] = harness.run(
            CELL, 2**31 + 91, 1.5, True, require_tpu=False,
            benchmark_file=tiny_benchmark_file(
                tmp_path_factory.mktemp("bench")))
    return seen


@pytest.mark.parametrize("name", NEW_METRICS)
def test_a_traced_run_reports_the_metric_finite_and_non_negative(traced, name):
    metric = traced["result"]["metrics"][name]
    assert math.isfinite(metric["value"]) and metric["value"] >= 0
    assert metric["unit"] in ("ms", "%", "dispatches")


def test_the_traced_line_holds_exactly_the_old_and_the_new_metrics(traced):
    """What the rehearsal's exact-set test would say had this PR been free
    to edit it (see conftest.py): nothing left the line, eight names joined
    it. Shares of a peak or a roofline are left out off the chip, never 0."""
    result = traced["result"]
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "check"]
    assert result["correct"] is True
    assert set(result["metrics"]) == {
        "ttft_p50_ms", "ttft_p95_ms", "itl_p95_ms",
        "decode_slots_occupied", "fused_step_share", "kv_pages_peak_share",
        *NEW_METRICS}
    assert 0 < result["metrics"]["decode_slots_occupied"]["value"] <= 100
    assert 0 < result["metrics"]["kv_pages_peak_share"]["value"] <= 100
    device = result["device"]
    assert 0 < device["busy_s"] < device["window_s"]
    breakdown = result["breakdown"]
    assert 0 < len(breakdown["device_ops"]) <= 10
    assert 0 < len(breakdown["idle_gaps"]) <= 10
    assert not os.path.exists(os.path.join(
        REPO, harness.SCRATCH_DIR, "spans_test", "trace"))


def test_every_finished_request_is_joined_and_its_parts_add_up(traced):
    """wire ingress + core ingress + admission wait + prefill span + the
    first token's egress is the client's own first-token wait, request by
    request: client and server share a process and CLOCK_MONOTONIC."""
    obs, pairs = traced["obs"], traced["pairs"]
    assert traced["result"]["correct"] and traced["result"]["failed"] == 0
    assert pairs is not None and len(pairs) == len(obs.finished()) > 8
    for log, r in pairs:
        terms = [r["recv_ns"] - log.sent_ns, r["submit_ns"] - r["recv_ns"],
                 r["admitted_ns"] - r["submit_ns"],
                 r["out_ns"][0] - r["admitted_ns"],
                 log.token_ns[0] - r["out_ns"][0]]
        assert all(t >= 0 for t in terms), terms
        assert abs(sum(terms) - (log.token_ns[0] - log.sent_ns)) <= 1e6
        assert r["recv_ns"] <= r["core_ns"] <= r["submit_ns"]
        assert r["admitted_ns"] <= r["first_chunk_ns"] <= r["last_chunk_ns"] \
            <= r["first_ready_ns"] <= r["out_ns"][0]
        # every token reaches the client after the engine handed it over
        assert len(r["out_ns"]) == len(log.token_ns) == log.request.max_tokens
        assert all(out <= at for out, at in zip(r["out_ns"], log.token_ns))
        assert r["chunks"] == -(-log.request.prompt.shape[1] // 32)
    # the whole-request reading and the sum of its parts agree in the tail
    waits = sorted((log.token_ns[0] - log.sent_ns) / 1e6 for log, _ in pairs)
    assert traced["result"]["metrics"]["ttft_p95_ms"]["value"] <= waits[-1]


def test_the_idle_gaps_name_a_loop_state(traced):
    labels = {label for label, _ in
              traced["result"]["breakdown"]["idle_gaps"]}
    assert labels & {"engine dispatching admit", "engine dispatching join",
                     "engine dispatching ticket_wait",
                     "engine dispatching idle_wait"}
    states = {r["phase"] for r in traced["obs"].steps}
    assert "admit" in states and {"decode", "prefill_chunk"} <= states


def _log(index, prompt, max_tokens, sent_ns):
    log = RequestLog(Request(index, np.asarray([prompt], np.int32),
                             max_tokens), 0, sent_ns)
    log.token_ns = [sent_ns + 5_000_000 + 1_000_000 * i
                    for i in range(max_tokens)]
    log.tokens = [1] * max_tokens
    return log


def _record(log, shift_ns=0):
    """The program's side of ``log`` on the program's clock."""
    at = log.sent_ns - (time.perf_counter_ns() - time.monotonic_ns())
    at += shift_ns
    return {
        "model": "m", "key": list(request_spans.key_of(log.request)),
        "recv_ns": at + 100_000, "core_ns": at + 200_000,
        "submit_ns": at + 300_000, "admitted_ns": at + 1_300_000,
        "waited_for_pages": False, "first_chunk_ns": at + 2_000_000,
        "last_chunk_ns": at + 3_000_000, "chunks": 2,
        "first_ready_ns": at + 4_000_000,
        "out_ns": [at + 4_500_000 + 1_000_000 * i
                   for i in range(log.request.max_tokens)],
        "end_ns": at + 9_000_000, "outcome": "finished",
    }


def _obs(logs):
    return types.SimpleNamespace(
        logs=logs, finished=lambda: [g for g in logs if g.error is None])


def test_the_join_is_by_key_and_moves_the_record_onto_the_clients_clock(
        monkeypatch):
    now = time.perf_counter_ns()
    logs = [_log(0, [1, 2, 3], 3, now), _log(1, [1, 2, 4], 2, now + 50)]
    stray = _record(_log(9, [7, 7], 1, now))       # a request of no log
    monkeypatch.setattr(request_spans, "ring", lambda: [
        _record(logs[1]), stray, _record(logs[0])])
    obs = _obs(logs)
    pairs = request_spans.joined(obs)
    assert [log.request.index for log, _ in pairs] == [0, 1]
    for log, r in pairs:
        assert abs((r["recv_ns"] - log.sent_ns) - 100_000) < 50_000
    expected = {"wire_ingress_p95_ms": 0.1, "core_ingress_p95_ms": 0.2,
                "egress_p95_ms": 0.5, "admission_wait_p95_ms": 1.0,
                "prefill_span_p50_ms": 3.2, "engine_itl_p95_ms": 1.0}
    for name, value in expected.items():
        assert _reader(name)(obs) == pytest.approx(value, abs=0.05), name


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_two_requests_with_one_key_give_none(monkeypatch, name):
    now = time.perf_counter_ns()
    twins = [_log(0, [5, 6, 7], 2, now), _log(1, [5, 6, 7], 2, now + 10)]
    other = _log(2, [8, 9], 2, now)
    monkeypatch.setattr(request_spans, "ring", lambda: [
        _record(twins[0]), _record(twins[1]), _record(other)])
    assert _reader(name)(_obs(twins + [other])) is None
    assert _reader(name)(_obs([other])) is not None


def test_a_join_that_cannot_be_sound_gives_none(monkeypatch):
    now = time.perf_counter_ns()
    log = _log(0, [1, 2, 3], 3, now)
    obs = _obs([log])
    cases = {
        "no ring in the program": None,
        "no record of the request": [],
        "a record from before the send": [_record(log, -10_000_000)],
        "another token count": [dict(_record(log), out_ns=[1, 2])],
        "not finished": [dict(_record(log), outcome="cancelled")],
    }
    for why, ring in cases.items():
        monkeypatch.setattr(request_spans, "ring", lambda ring=ring: ring)
        assert request_spans.joined(obs) is None, why
        assert _reader("egress_p95_ms")(obs) is None, why
    # A record without the core's stamps (no TraceContext): the spans that
    # need them report nothing, the others still do.
    monkeypatch.setattr(request_spans, "ring", lambda: [
        dict(_record(log), recv_ns=None, core_ns=None)])
    assert _reader("wire_ingress_p95_ms")(obs) is None
    assert _reader("core_ingress_p95_ms")(obs) is None
    assert _reader("admission_wait_p95_ms")(obs) == pytest.approx(1.0)


def _step(phase, start_ns, dispatch_us=100, batch_size=1, **more):
    return dict({"model": "m", "phase": phase, "start_ns": start_ns,
                 "dispatch_us": dispatch_us, "batch_size": batch_size,
                 "slots": 4, "micro_steps": 1, "lanes": 4}, **more)


def test_prefill_run_mean_counts_chunks_between_decode_dispatches():
    read = _reader("prefill_run_mean")
    order = "d ccc d d c d cc"          # runs of 3 and 1; the tail is open
    steps = [_step("decode" if ch == "d" else "prefill_chunk", 1000 * i)
             for i, ch in enumerate(order.replace(" ", ""))]
    steps.append(_step("ticket_wait", 500, batch_size=0))    # not a dispatch
    obs = types.SimpleNamespace(steps=list(reversed(steps)))  # any ring order
    assert read(obs) == pytest.approx(2.0)
    interleaved = [_step("decode" if i % 2 == 0 else "prefill_chunk", i)
                   for i in range(9)]
    assert read(types.SimpleNamespace(steps=interleaved)) == 1.0
    assert read(types.SimpleNamespace(
        steps=[_step("decode", i) for i in range(3)])) == 0.0
    assert read(types.SimpleNamespace(steps=[_step("decode", 0)])) is None
    assert read(types.SimpleNamespace(steps=[])) is None


def test_ticket_wait_share_is_cut_to_the_window():
    read = _reader("engine_ticket_wait_share")
    window = {"start_ns": 1_000_000, "end_ns": 11_000_000}
    steps = [
        _step("decode", 1_000_000),
        _step("ticket_wait", 500_000, dispatch_us=1000, batch_size=0),   # half in
        _step("ticket_wait", 4_000_000, dispatch_us=2000, batch_size=0),
        _step("admit", 7_000_000, dispatch_us=3000, batch_size=0),       # other
        _step("ticket_wait", 10_500_000, dispatch_us=2000, batch_size=0),
    ]
    obs = types.SimpleNamespace(steps=steps, window=window, window_s=0.01)
    assert read(obs) == pytest.approx(100 * (0.5 + 2.0 + 0.5) / 10)
    obs.steps = [_step("decode", 1_000_000)]
    assert read(obs) == 0.0
    # records of a program from before it had loop states: nothing to read
    obs.steps = [{k: v for k, v in _step("decode", 1_000_000).items()
                  if k != "lanes"}]
    assert read(obs) is None
