"""The readers of the host's share of a token: a token's egress split at the
stream handler's two hand-overs, the engine loop's time off the CPU and the
collector's pauses. A traced tiny run of the GPT cell and of each routed
family's reports all five beside every metric it reported before (the
enlarged sets of the four exact-set tests that `tests/conftest.py` hands
over, and of four tests that held a count); every finished request has one
stamp of each kind a token and the two parts add up to the egress, token by
token; and the three readers of counters on records written out here. It
says nothing about the device: every number here is from the CPU backend."""

import importlib.util
import json
import math
import os
import time
import types

import pytest

import _bench_tiny
import _bench_tiny_mhc_mla_moe
import _bench_tiny_mla_moe
import _bench_tiny_swa_moe
import test_benchmark_spans as spans_test
from _bench_tiny import REPO
from benchmarks import harness, host_spans, request_spans, spec

EGRESS_PARTS = ("egress_wake_p95_ms", "egress_handler_p95_ms",
                "egress_wire_p95_ms")
HOST_METRICS = EGRESS_PARTS + ("engine_loop_blocked_share", "gc_pause_share")
CLOCK_SLACK_US = 100     # thread CPU time against the wall, see its test
# What the families share, as the four stale tests knew it ...
SHARED_BEFORE = {
    "ttft_p50_ms", "ttft_p95_ms", "itl_p95_ms", "decode_slots_occupied",
    "fused_step_share", "kv_pages_peak_share", "device_idle_share",
    "wire_ingress_p95_ms", "core_ingress_p95_ms", "egress_p95_ms",
    "admission_wait_p95_ms", "prefill_span_p50_ms", "engine_itl_p95_ms",
    "prefill_run_mean", "engine_ticket_wait_share"}
# ... and each family's own readers, those that read off the chip first.
FAMILIES = {
    "mla_moe": (_bench_tiny_mla_moe,
                ["moe_experts_hit_share", "moe_load_imbalance",
                 "mla_moe_step_mfu", "mla_moe_step_roofline_share"], 2),
    "swa_moe": (_bench_tiny_swa_moe,
                ["swa_moe_experts_hit_share", "kv_window_held_share",
                 "swa_moe_step_mfu", "swa_moe_step_roofline_share",
                 "swa_paged_attention_roofline_share"], 2),
    "mhc_mla_moe": (_bench_tiny_mhc_mla_moe,
                    ["mhc_moe_experts_hit_share", "mhc_mla_moe_step_mfu",
                     "mhc_mla_moe_step_roofline_share"], 1),
}


def _per_layer():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)["per_layer"]


def _reader(name):
    path = os.path.join(REPO, "benchmarks", "layer_metrics", name + ".py")
    module_spec = importlib.util.spec_from_file_location(
        "_host_time_test_" + name, path)
    module = importlib.util.module_from_spec(module_spec)
    module_spec.loader.exec_module(module)
    return module.read


def _traced_run(tiny, scratch, seconds, keep=None):
    """One traced run of a tiny cell, its trace under a directory of this
    file's own; ``keep`` receives what the readers saw."""
    read_metrics = harness.read_metrics

    def watched(bench, kind, entries, root, obs):
        if keep is not None:
            keep["obs"] = obs
            keep["pairs"] = host_spans.joined(obs)
        return read_metrics(bench, kind, entries, root, obs)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "configure_compile_cache", lambda: "(none)")
        patch.setattr(harness, "read_metrics", watched)
        patch.setattr(harness, "SCRATCH_DIR", str(scratch / "scratch"))
        return harness.run(
            tiny.CELL, 2**31 + 36, seconds, True, require_tpu=False,
            benchmark_file=tiny.tiny_benchmark_file(scratch))


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One traced run of the tiny GPT cell: the line, the Observations the
    readers saw and the join as it stood right then."""
    seen = {}
    seen["result"] = _traced_run(
        _bench_tiny, tmp_path_factory.mktemp("host_time"), 1.5, seen)
    return seen


# --------------------------------------------------------------------------- #
# the traced lines                                                            #
# --------------------------------------------------------------------------- #


@pytest.mark.parametrize("name", HOST_METRICS)
def test_a_traced_run_reports_the_metric_finite_and_non_negative(traced, name):
    metric = traced["result"]["metrics"][name]
    assert math.isfinite(metric["value"]) and metric["value"] >= 0
    assert metric["unit"] == ("ms" if name.endswith("_ms") else "%")


def test_the_traced_line_holds_exactly_the_old_and_the_new_metrics(traced):
    """`test_benchmark_spans.py`'s test of this name with this PR's five
    names joined to its set: nothing left the line. Shares of a peak or a
    roofline are left out off the chip, never 0."""
    result = traced["result"]
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "breakdown", "check"]
    assert result["correct"] is True
    assert set(result["metrics"]) == (
        SHARED_BEFORE - {"device_idle_share"}) | set(HOST_METRICS)
    assert 0 < result["metrics"]["decode_slots_occupied"]["value"] <= 100
    assert 0 < result["metrics"]["kv_pages_peak_share"]["value"] <= 100
    device = result["device"]
    assert 0 < device["busy_s"] < device["window_s"]
    breakdown = result["breakdown"]
    assert 0 < len(breakdown["device_ops"]) <= 10
    assert 0 < len(breakdown["idle_gaps"]) <= 10


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_routed_cells_traced_line_holds_the_shared_layers_and_its_own(
        family, tmp_path):
    """What each routed family's `test_traced_run_reports_the_shared_layers_
    and_...` holds, with the families' shared readers grown from fifteen to
    twenty: every one of them reads the cell unchanged, the family's
    counters are there, the shares of a peak are left out off the chip and
    no other family's readers are this cell's."""
    tiny, own, off_chip_own = FAMILIES[family]
    result = _traced_run(tiny, tmp_path, 2.5 if family == "swa_moe" else 2.0)
    assert result["correct"] is True
    entries = _per_layer()
    shared = {m["name"] for m in entries if "workloads" not in m}
    assert shared == SHARED_BEFORE | set(HOST_METRICS) and len(shared) == 20
    assert {m["name"] for m in entries
            if m.get("workloads") == [tiny.REAL_CELL]} == set(own)
    assert set(result["metrics"]) == (
        shared - {"device_idle_share"}) | set(own[:off_chip_own])
    for name in HOST_METRICS:
        value = result["metrics"][name]["value"]
        assert math.isfinite(value) and value >= 0, name
    assert (result["metrics"]["egress_wake_p95_ms"]["value"]
            <= result["metrics"]["egress_p95_ms"]["value"])
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]


# What each family's `test_the_cell_resolves_and_its_longest_request_fits`
# holds of its cell beside the count: the configuration, its positions
# served, the ends of the mix's length set, the longest request a seed can
# make, the limits that decide `correct`, and the other families' readers
# that are not this cell's.
CELL_FACTS = {
    "mla_moe": ("joyai-llm-flash", 4096, (200, 1294, 10, 415), 1709,
                ("max", "p99"), {"step_mfu", "step_roofline_share"}),
    "swa_moe": ("k-exaone-236b-a23b", 16384, (357, 13312, 8, 354), 13666,
                ("max", "p99", "mean"),
                {"step_mfu", "step_roofline_share", "mla_moe_step_mfu",
                 "moe_experts_hit_share"}),
    "mhc_mla_moe": ("xing4.0-29b-a4b", 8192, (344, 6534, 2, 130), 6664,
                    ("max", "p99", "mean"),
                    {"step_mfu", "mla_moe_step_mfu", "moe_experts_hit_share",
                     "swa_moe_step_mfu"}),
}


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_routed_cell_resolves_fits_and_lists_the_shared_readers_and_its_own(
        family):
    """Each family's `test_the_cell_resolves_and_its_longest_request_fits`
    with its count (`15 + its own`) as it stands now: a strict xfail hides
    whatever else such a test would catch, so what it held is held here."""
    from benchmarks import traffic

    tiny, own, _ = FAMILIES[family]
    name, positions, ends, longest, limit_names, not_its_own = CELL_FACTS[
        family]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"] if c["name"] == name)
    with open(os.path.join(REPO, entry["file"])) as f:
        config = json.load(f)
    cell = spec.load_cell(tiny.REAL_CELL)
    assert cell.chips == 1 and cell.config["reduced"] == entry["reduced"]
    assert cell.config["source"] == entry["source"] == config["source"]
    assert all(cell.config[key] == value for key, value in config.items())
    mix = cell.traffic
    assert (mix["loop"], mix["clients"]) == ("closed", 8)
    assert mix["clients"] == config["engine"]["max_slots"]
    prompts, outputs = traffic.length_set(mix)
    assert (prompts[0], prompts[-1], outputs[0], outputs[-1]) == ends
    assert (traffic.longest_request(mix) == longest == ends[1] + ends[3]
            <= config["max_position_embeddings"] == positions)
    assert positions % config["engine"]["block_size"] == 0
    limits = [config["check"][f"served_logit_gap_{k}_limit"]
              for k in limit_names]
    assert any(v is not None for v in limits)
    assert all(v is None or v > 0 for v in limits)
    reported = {m["name"] for m in cell.per_layer}
    assert reported == SHARED_BEFORE | set(HOST_METRICS) | set(own)
    assert len(reported) == 15 + 5 + len(own)
    assert not not_its_own & reported


def test_benchmark_json_gained_five_shared_metrics_at_the_end():
    """`test_benchmark_mhc_mla_moe.py`'s `..._gained_one_configuration_one_
    cell_three_metrics` held the list's last three entries to the `mhc_*`
    names; they are now the three before this PR's five, which list no cells
    (every cell reports them, a later one too). Its other assertions are
    held here too: a strict xfail hides them there."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]][-1] == "xing4.0-29b-a4b"
    assert bench["workloads"][-1] == {
        "name": _bench_tiny_mhc_mla_moe.REAL_CELL,
        "config": "xing4.0-29b-a4b", "traffic": "code", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert len(bench["configs"]) == len(bench["workloads"]) == 5
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200
    entries = bench["per_layer"]
    assert [m["name"] for m in entries][-8:] == [
        "mhc_mla_moe_step_mfu", "mhc_mla_moe_step_roofline_share",
        "mhc_moe_experts_hit_share", *HOST_METRICS]
    for m in entries[-8:-5]:
        assert m["workloads"] == [_bench_tiny_mhc_mla_moe.REAL_CELL]
        assert m["moves"] == "output_tokens_per_s"
    layers = {m["layer"] for m in entries[:-5]}
    for m in entries[-5:]:
        assert "workloads" not in m and m["better"] == "lower"
        assert m["moves"] == "output_tokens_per_s"
        assert m["source"] == ("program_span" if m["name"] in EGRESS_PARTS
                               else "program_counter")
        assert m["layer"] in layers | {"host process (interpreter)"}
    assert {m["layer"] for m in entries if m["name"] in EGRESS_PARTS} == {
        m["layer"] for m in entries if m["name"] == "egress_p95_ms"}


# --------------------------------------------------------------------------- #
# the stamps of a traced run                                                  #
# --------------------------------------------------------------------------- #


def test_every_token_changes_hands_in_order_and_the_parts_add_up(traced):
    """out (the delivery thread's put) <= taken (the handler holds it) <=
    resumed (the transport asks for the next), one of each a token, and
    wake + wire is the token's egress to the nanosecond: the two spans meet
    at one stamp."""
    obs, pairs = traced["obs"], traced["pairs"]
    assert pairs is not None and len(pairs) == len(obs.finished()) > 8
    assert [log for log, _ in pairs] == obs.finished()
    for log, r in pairs:
        assert len(r["taken_ns"]) == len(r["resumed_ns"]) == len(
            r["out_ns"]) == len(log.token_ns) == log.request.max_tokens
        for out, taken, resumed, at in zip(r["out_ns"], r["taken_ns"],
                                           r["resumed_ns"], log.token_ns):
            assert out <= taken <= resumed
            assert (taken - out) + (at - taken) == at - out
            assert taken <= at
        # the handler takes token i + 1 after it was resumed behind token i
        assert all(resumed <= taken for resumed, taken in
                   zip(r["resumed_ns"], r["taken_ns"][1:]))
    metrics = traced["result"]["metrics"]
    assert (metrics["egress_wake_p95_ms"]["value"]
            <= metrics["egress_p95_ms"]["value"])
    assert (metrics["egress_wire_p95_ms"]["value"]
            <= metrics["egress_p95_ms"]["value"])


def test_the_loops_time_off_the_cpu_is_inside_its_working_stretches(traced):
    """blocked <= the share of the window the loop spent in `admit`, `join`
    and the dispatch brackets; and no stretch was on the CPU
    for longer than it lasted, to the clocks' resolution (the kernel
    advances a thread's CPU clock in steps: on this sandbox it runs up to
    21 us ahead of the wall over a busy stretch of any length)."""
    obs = traced["obs"]
    metrics = traced["result"]["metrics"]
    lo, hi = obs.window["start_ns"], obs.window["end_ns"]
    working = 0.0
    for r in obs.steps:
        assert 0 <= r["cpu_us"] <= r["dispatch_us"] + CLOCK_SLACK_US, r
        assert r.get("runq_us", 0) >= 0, r
        if r["phase"] in host_spans.WORKING_PHASES:
            start, end = r["start_ns"], r["start_ns"] + r["dispatch_us"] * 1e3
            working += max(min(end, hi) - max(start, lo), 0.0)
    # (the run-queue delay beside it is kept by the scheduler on a CPU's
    # own clock: on this sandbox single readings run 100 us and more past
    # their stretch, so no record is held to it; no metric reads it)
    blocked = metrics["engine_loop_blocked_share"]["value"]
    assert blocked <= 100.0 * working / (hi - lo) + 1e-6
    assert 0 <= blocked <= 100.0
    assert {r["phase"] for r in obs.steps} >= {"admit", "join", "decode",
                                              "prefill_chunk"}


# --------------------------------------------------------------------------- #
# the readers, on records written out here                                    #
# --------------------------------------------------------------------------- #


def _obs(steps, lo=1_000_000_000, hi=2_000_000_000):
    return types.SimpleNamespace(
        steps=steps, window={"start_ns": lo, "end_ns": hi},
        window_s=(hi - lo) / 1e9)


def _stretch(phase, start_ns, wall_us, cpu_us, runq_us=None):
    record = {"phase": phase, "start_ns": start_ns, "dispatch_us": wall_us,
              "cpu_us": cpu_us}
    if runq_us is not None:
        record["runq_us"] = runq_us
    return record


def test_the_loop_reader_sums_the_working_stretches_cut_to_the_window():
    steps = [
        _stretch("admit", 1_100_000_000, 10_000, 4_000, 1_000),
        # a clock that ticks: 10 ms of CPU read on a 2 ms stretch and none
        # on the next; only the sums are right
        _stretch("decode", 1_200_000_000, 2_000, 10_000, 0),
        _stretch("decode", 1_210_000_000, 9_000, 0, 0),
        _stretch("join", 1_300_000_000, 5_000, 5_000, 0),
        # half inside the window: half of its 8 ms blocked, 2 ms on a queue
        _stretch("prefill_chunk", 1_995_000_000, 10_000, 2_000, 2_000),
        # waits the loop means to make count for nothing
        _stretch("ticket_wait", 1_400_000_000, 50_000, 100, 40_000),
        _stretch("idle_wait", 1_500_000_000, 90_000, 10, 10),
        _stretch("admit", 2_500_000_000, 10_000, 0, 10_000),   # outside
    ]
    obs = _obs(steps)
    assert _reader("engine_loop_blocked_share")(obs) == pytest.approx(
        100 * (6_000 - 8_000 + 9_000 + 4_000) * 1e3 / 1e9)
    # all CPU and a tick over: never under nothing
    assert _reader("engine_loop_blocked_share")(_obs(steps[1:2])) == 0.0


def test_a_loop_record_without_the_clock_gives_none():
    """A program from before the stamps (no `cpu_us`) reports no share; one
    whose thread could not read its `schedstat` (no `runq_us`: the chip's
    host) reports it all the same."""
    older = [{"phase": "admit", "start_ns": 1_100_000_000,
              "dispatch_us": 10_000}]
    assert _reader("engine_loop_blocked_share")(_obs(older)) is None
    unreadable = [_stretch("admit", 1_100_000_000, 10_000, 4_000)]
    assert _reader("engine_loop_blocked_share")(_obs(unreadable)) == (
        pytest.approx(0.6))
    # nothing to read at all: no working stretch in the records
    assert _reader("engine_loop_blocked_share")(_obs([])) is None


def test_the_collectors_reader_cuts_pauses_to_the_window(monkeypatch):
    from tritonclient_tpu import _stepscope

    to_program = time.monotonic_ns() - time.perf_counter_ns()
    lo = time.perf_counter_ns()
    hi = lo + 1_000_000_000
    pauses = [
        {"start_ns": lo + to_program + 100_000_000, "duration_ns": 3_000_000,
         "generation": 2, "thread_ident": 1, "thread_name": "a"},
        {"start_ns": lo + to_program - 4_000_000, "duration_ns": 6_000_000,
         "generation": 0, "thread_ident": 2, "thread_name": "b"},
        {"start_ns": hi + to_program + 5, "duration_ns": 9_000_000,
         "generation": 1, "thread_ident": 1, "thread_name": "a"},
    ]
    monkeypatch.setattr(_stepscope, "dump", lambda: {"gc": pauses})
    read = _reader("gc_pause_share")
    assert read(_obs([], lo, hi)) == pytest.approx(0.5, abs=0.01)
    monkeypatch.setattr(_stepscope, "dump", lambda: {"gc": []})
    assert read(_obs([], lo, hi)) == 0.0
    monkeypatch.setattr(_stepscope, "dump", lambda: {"requests": []})
    assert read(_obs([], lo, hi)) is None      # a program without the ring


def _record(log, stamps=True):
    """`test_benchmark_spans.py`'s record of ``log`` on the program's clock
    (a token is out at 4.5 ms + i, at the client 0.5 ms later) with the
    handler's stamps: taken 0.2 ms after it was out, resumed 0.1 ms on."""
    record = spans_test._record(log)
    if stamps:
        record["taken_ns"] = [t + 200_000 for t in record["out_ns"]]
        record["resumed_ns"] = [t + 300_000 for t in record["out_ns"]]
    return record


_log, _requests = spans_test._log, spans_test._obs


def test_the_egress_readers_on_hand_made_timelines(monkeypatch):
    now = time.perf_counter_ns()
    logs = [_log(0, [1, 2, 3], 3, now), _log(1, [1, 2, 4], 2, now + 50)]
    monkeypatch.setattr(request_spans, "ring",
                        lambda: [_record(log) for log in logs])
    obs = _requests(logs)
    expected = {"egress_wake_p95_ms": 0.2, "egress_handler_p95_ms": 0.1,
                "egress_wire_p95_ms": 0.3, "egress_p95_ms": 0.5}
    for name, value in expected.items():
        assert _reader(name)(obs) == pytest.approx(value, abs=0.05), name


@pytest.mark.parametrize("name", EGRESS_PARTS)
def test_a_timeline_without_the_handlers_stamps_gives_none(monkeypatch, name):
    """A program from before the stamps, a stream that was cut before its
    last token's second stamp, and a join that is not one-to-one: the
    readers report nothing, while the one span that needs none still does."""
    now = time.perf_counter_ns()
    log = _log(0, [1, 2, 3], 3, now)
    obs = _requests([log])
    short = _record(log)
    short["resumed_ns"] = short["resumed_ns"][:-1]
    for ring in ([_record(log, stamps=False)], [short],
                 [_record(log), _record(log)]):
        monkeypatch.setattr(request_spans, "ring", lambda ring=ring: ring)
        assert _reader(name)(obs) is None
    monkeypatch.setattr(request_spans, "ring",
                        lambda: [_record(log, stamps=False)])
    assert _reader("egress_p95_ms")(obs) is not None
