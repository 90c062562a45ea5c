"""BENCHMARK.json and its data files, held to the contract's letter; the
seeded traffic generator."""

import json
import os
import re

import numpy as np
import pytest

from benchmarks import spec, traffic

REPO = spec.ROOT
with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
LINE = re.compile(r"^[^\t\n]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    # 2 + 14 runs a cell, each run_seconds + 60, 180 s a cell to compile,
    # 1200 s spare: the full 24 cells have to fit 43200 s.
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= len(BENCH["paths"]) <= 16
    assert len(BENCH["command"]) <= 32
    for word in BENCH["command"]:
        assert LINE.match(word) and not word.startswith("/") and ".." not in word
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])


@pytest.mark.parametrize("entry", METRICS + BENCH["configs"] + BENCH["workloads"],
                         ids=lambda e: e["name"])
def test_every_name_is_within_the_allowed_characters(entry):
    assert NAME.match(entry["name"])
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])
    for key in ("why", "layer", "source"):
        if key in entry and entry not in METRICS:
            assert LINE.match(entry[key])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entries(metric):
    per_layer = metric in BENCH["per_layer"]
    allowed = {"name", "unit", "better", "source", "workloads"}
    allowed |= {"layer", "moves"} if per_layer else {"bound"}
    assert set(metric) <= allowed and allowed - {"workloads"} <= set(metric)
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", cells)) <= cells
    if per_layer:
        assert LINE.match(metric["layer"])
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        kind = "layer_metrics"
    else:
        assert metric["source"] in ("host_clock", "device_trace")
        assert 0.01 <= metric["bound"] <= 0.1
        kind = "end_to_end"
    # One reader, one file, found by the metric's name.
    assert os.path.exists(spec.reader_file(BENCH, kind, metric["name"], REPO))


def test_setup_s_and_a_whole_step_mfu_are_there():
    assert "setup_s" in {m["name"] for m in BENCH["end_to_end"]}
    rooflines = [m for m in BENCH["per_layer"] if "roofline" in m["name"]]
    mfus = [m for m in BENCH["per_layer"] if "mfu" in m["name"].split("_")]
    for roofline in rooflines:
        assert roofline["unit"] == "%"
        assert any(m["moves"] == roofline["moves"] for m in mfus)


@pytest.mark.parametrize("config", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_files(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in BENCH["paths"])
    assert len({c["file"] for c in BENCH["configs"]}) == len(BENCH["configs"])
    with open(os.path.join(REPO, config["file"])) as f:
        body = json.load(f)
    assert body["source"] == config["source"]
    assert body["reduced"] == config["reduced"] == []
    for key in ("n_layer", "n_embd", "n_head", "n_positions", "vocab_size",
                "layer_norm_epsilon", "activation_function"):
        assert key in body
    assert body["n_embd"] % body["n_head"] == 0
    limits = [body["check"][k] for k in ("served_logit_gap_max_limit",
                                         "served_logit_gap_p99_limit")]
    assert any(v is not None for v in limits)
    assert all(v is None or v > 0 for v in limits)
    assert any(w["config"] == config["name"] for w in BENCH["workloads"])


PUBLISHED = {
    "gpt2-xl": dict(n_layer=48, n_embd=1600, n_head=25, n_inner=None,
                    n_positions=1024, vocab_size=50257,
                    activation_function="gelu_new", layer_norm_epsilon=1e-5),
    "cerebras-gpt-1.3b": dict(n_layer=24, n_embd=2048, n_head=16,
                              n_inner=8192, n_positions=2048,
                              vocab_size=50257, activation_function="gelu",
                              layer_norm_epsilon=1e-5),
}


@pytest.mark.parametrize("name", sorted(PUBLISHED))
def test_published_values_are_kept(name):
    with open(os.path.join(REPO, "benchmarks", "configs", name + ".json")) as f:
        body = json.load(f)
    for key, value in PUBLISHED[name].items():
        assert body[key] == value, key
    # The engine runs at the program's defaults.
    assert body["engine"] == {"max_slots": 8, "block_size": 16,
                              "prefill_chunk": 32, "n_blocks": None}


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_cells_resolve_to_their_files(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert cell["chips"] in (1, 4)
    loaded = spec.load_cell(cell["name"])
    assert loaded.config["name"] == cell["config"]
    assert loaded.traffic["name"] == cell["traffic"]
    reported = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert loaded.per_layer
    traffic.validate(loaded.traffic)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 4)


def test_files_under_paths_are_named_from_the_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base in BENCH["paths"]:
        for directory, dirs, files in os.walk(os.path.join(REPO, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for name in files:
                if name.endswith(".pyc"):
                    continue
                rel = os.path.relpath(os.path.join(directory, name), REPO)
                assert allowed.match(rel), rel


def test_an_unknown_cell_or_reader_is_an_error():
    with pytest.raises(spec.SpecError):
        spec.load_cell("no-such.cell")
    with pytest.raises(spec.SpecError):
        spec.reader_file(BENCH, "layer_metrics", "no_such_metric", REPO)


# -- the seeded mix ---------------------------------------------------------- #

with open(os.path.join(REPO, "benchmarks", "traffic", "chat.json")) as _f:
    CHAT = json.load(_f)


def _first(seed, n=130, stream="window"):
    source = traffic.RequestSource(CHAT, 50257, seed, stream=stream)
    return [source.take() for _ in range(n)]


def test_the_mix_repeats_for_one_seed_and_differs_for_another():
    a, b, c = _first(2**31 + 5), _first(2**31 + 5), _first(2**31 + 6)
    for x, y in zip(a, b):
        assert x.max_tokens == y.max_tokens
        assert np.array_equal(x.prompt, y.prompt)
    assert any(x.prompt.shape != z.prompt.shape
               or not np.array_equal(x.prompt, z.prompt) for x, z in zip(a, c))
    warm = _first(2**31 + 5, stream="warm")
    assert not np.array_equal(a[0].prompt[0, :8], warm[0].prompt[0, :8])


def _lengths(seed, n):
    return [(r.prompt.shape[1], r.max_tokens) for r in _first(seed, n)]


def test_every_seed_sends_the_same_lengths_in_another_order_and_pairing():
    n = CHAT["set_size"]
    prompts, outputs = traffic.length_set(CHAT)
    assert len(prompts) == len(outputs) == n
    runs = [_lengths(s, 2 * n) for s in (1, 2, 2**31 + 9)]
    for run in runs:
        for lap in (run[:n], run[n:]):      # each lap: the whole set, once
            assert sorted(p for p, _ in lap) == sorted(prompts)
            assert sorted(o for _, o in lap) == sorted(outputs)
        assert run[:n] != run[n:]
    assert runs[0] != runs[1] != runs[2]
    # the pairing is the seed's too, not only the order
    assert sorted(runs[0][:n]) != sorted(runs[1][:n])


def test_lengths_follow_the_stated_trace_at_the_stated_scale():
    prompts, outputs = traffic.length_set(CHAT)
    scale = CHAT["length_scale"]
    assert prompts == sorted(prompts) and outputs == sorted(outputs)
    # the middle of the set sits at the trace's medians, scaled
    assert abs(np.median(prompts) - scale * 1020) < 8
    assert abs(np.median(outputs) - scale * 129) < 2
    # heavy-tailed: the mean lies above the median, as the trace's does
    assert np.mean(prompts) > np.median(prompts) * 1.08
    assert np.mean(outputs) > np.median(outputs) * 1.4
    assert CHAT["prompt_tokens"]["min"] <= prompts[0]
    assert prompts[-1] <= CHAT["prompt_tokens"]["max"]
    assert CHAT["output_tokens"]["min"] <= outputs[0]
    assert outputs[-1] <= CHAT["output_tokens"]["max"]
    for r in _first(7, 40):
        assert r.prompt.dtype == np.int32 and r.prompt.shape[0] == 1
        assert 0 <= r.prompt.min() and r.prompt.max() < 50257


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_the_longest_request_fits_the_configuration(cell):
    loaded = spec.load_cell(cell["name"])
    assert (traffic.longest_request(loaded.traffic)
            <= loaded.config["n_positions"])


def test_a_uniform_mix_takes_the_middles_of_equal_slices():
    mix = dict(CHAT, set_size=4, prompt_tokens={"min": 10, "max": 49},
               output_tokens={"min": 1, "max": 8})
    assert traffic.length_set(mix) == ([15, 25, 35, 45], [2, 4, 6, 8])


def test_a_shared_prefix_heads_every_prompt():
    mix = dict(CHAT, shared_prefix_tokens=48)
    source = traffic.RequestSource(mix, 50257, 11)
    other = traffic.RequestSource(mix, 50257, 12)
    requests = [source.take() for _ in range(6)]
    head = requests[0].prompt[0, :48]
    for r in requests:
        assert np.array_equal(r.prompt[0, :48], head)
    assert not np.array_equal(requests[0].prompt[0, 48:80],
                              requests[1].prompt[0, 48:80])
    assert not np.array_equal(other.take().prompt[0, :48], head)


@pytest.mark.parametrize("change", [
    {"sessions": 8}, {"sampling": "top_k"}, {"token_ids": "zipf"},
    {"prompt_tokens": {"min": 0, "max": 4}}, {"set_size": 0},
    {"shared_prefix_tokens": -1},
    {"output_tokens": {"distribution": "pareto", "min": 1, "max": 4}},
], ids=lambda c: next(iter(c)))
def test_what_the_generator_does_not_implement_is_an_error(change):
    with pytest.raises(traffic.TrafficError):
        traffic.validate(dict(CHAT, **change))


@pytest.mark.parametrize("change", [{"rate": 3.5}, {"burst": 4},
                                    {"clients": 0}],
                         ids=lambda c: next(iter(c)))
def test_a_closed_loop_refuses_what_only_another_loop_has(change):
    from benchmarks.loops import closed

    closed.validate(CHAT)
    with pytest.raises(traffic.TrafficError):
        closed.validate(dict(CHAT, **change))
