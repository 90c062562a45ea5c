"""The window/global grouped-query routed family in the benchmark, on the CPU
at a test-only tiny cell added as files and entries: a whole run of both
kinds, the timed path broken underneath coming out as not correct by this
family's reference, the cost functions against hand counts, the
configuration against its published row, and the new readers on records
written out here. It says nothing about the device: every number here is
from the CPU backend or from the arithmetic."""

import json
import os
from types import SimpleNamespace

import jax
import pytest

from _bench_tiny import REPO
from _bench_tiny_swa_moe import CELL, tiny_benchmark_file
from benchmarks import costs_swa_moe as costs
from benchmarks import harness

REAL_CELL = "k-exaone-236b-a23b.long_mixed"
NEW_METRICS = {"swa_moe_step_mfu", "swa_moe_step_roofline_share",
               "swa_paged_attention_roofline_share",
               "swa_moe_experts_hit_share", "kv_window_held_share"}


@pytest.fixture(autouse=True)
def _no_shared_compile_cache(monkeypatch, tmp_path):
    # A test process keeps JAX's configuration to itself, and its profiler
    # trace too: the harness empties `.bench_scratch/trace` under the
    # checkout before and after a traced run, and the files of this
    # directory run side by side (an absolute path stands for itself in the
    # harness's `os.path.join`).
    monkeypatch.setattr(harness, "configure_compile_cache", lambda: "(none)")
    monkeypatch.setattr(harness, "SCRATCH_DIR", str(tmp_path / "scratch"))


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return tiny_benchmark_file(tmp_path_factory.mktemp("bench"))


def _run(bench_file, trace, seed=2**31 + 77, seconds=2.5):
    return harness.run(CELL, seed, seconds, trace, require_tpu=False,
                       benchmark_file=bench_file)


def test_untraced_run_is_correct_and_prints_the_contracts_keys(bench_file):
    result = _run(bench_file, trace=False)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["correct"] is True and result["failed"] == 0
    # No count of requests is judged: eight interpreted layers a step on a
    # CPU shared with five other workers finish 4 to 30 in the window.
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert set(result["check"]) == {"served_logit_gap_max", "failed_requests",
                                    "checked_tokens"}
    assert result["check"]["checked_tokens"]["value"] >= 8
    assert json.loads(json.dumps(result)) == result


def test_traced_run_reports_the_shared_layers_and_the_new_counters(
        bench_file):
    """The fifteen readers the families share read this cell unchanged, the
    two new counters are there (the held experts that got a token; what the
    window layers hold against what global layers would), and the three
    shares of a peak are left out off the chip, never 0; no other family's
    readers are this cell's."""
    result = _run(bench_file, trace=True)
    assert result["correct"] is True
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = json.load(f)["per_layer"]
    shared = {m["name"] for m in entries if "workloads" not in m}
    assert len(shared) == 15
    assert {m["name"] for m in entries
            if m.get("workloads") == [REAL_CELL]} == NEW_METRICS
    off_chip = shared - {"device_idle_share"}
    assert set(result["metrics"]) == off_chip | {
        "swa_moe_experts_hit_share", "kv_window_held_share"}
    assert 0 < result["metrics"]["swa_moe_experts_hit_share"]["value"] <= 100
    # prompts of 20-70 and outputs to 12 against a ring of 48 positions:
    # the longest requests hold less in a window layer than in a global one
    assert 0 < result["metrics"]["kv_window_held_share"]["value"] < 100
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]


def _alter_a_token(monkeypatch):
    """Every 7th position of every slot: the token one up from the one the
    step produced, altered where it is produced (inside the decode step)."""
    from tritonclient_tpu.models import swa_moe

    produce = swa_moe._decode_step_kinds

    def broken(params, k_pool, v_pool, btabs, tokens, pos, *rest, **kw):
        nxt, k_pool, v_pool, counts = produce(
            params, k_pool, v_pool, btabs, tokens, pos, *rest, **kw)
        vocab = params["embed"]["tok"].shape[0]
        return (jax.numpy.where(pos % 7 == 0, (nxt + 1) % vocab, nxt),
                k_pool, v_pool, counts)

    monkeypatch.setattr(swa_moe, "_decode_step_kinds", broken)


def _ring_misplaced(monkeypatch):
    """A prefill chunk writes and reads its window layers' keys one ring
    entry off: decode then reads ring pages the prompt never reached, while
    the global layers' pages are right."""
    from tritonclient_tpu.models import swa_moe

    prefill = swa_moe._prefill_chunk_kinds

    def broken(params, k_pool, v_pool, chunks, btabs, *rest, pages, **kw):
        ring = jax.numpy.roll(btabs[:, :pages.ring], 1, axis=1)
        shifted = jax.numpy.concatenate([ring, btabs[:, pages.ring:]], axis=1)
        return prefill(params, k_pool, v_pool, chunks, shifted, *rest,
                       pages=pages, **kw)

    monkeypatch.setattr(swa_moe, "_prefill_chunk_kinds", broken)


@pytest.mark.parametrize("fault", [_alter_a_token, _ring_misplaced],
                         ids=["token_altered", "ring_page_misplaced"])
def test_a_broken_timed_path_comes_out_as_not_correct(bench_file, monkeypatch,
                                                      fault):
    fault(monkeypatch)
    result = _run(bench_file, trace=False)
    assert result["correct"] is False
    entry = result["check"]["served_logit_gap_max"]
    assert entry["value"] > entry["limit"]
    assert result["failed"] == 0


def test_the_parent_of_this_family_fails_at_once_without_the_program(
        tmp_path, monkeypatch):
    """A checkout that has the benchmark's files and not the program's
    module (the parent commit, laid over with this cell) fails while the
    adapter is loaded, before any weight is made."""
    import sys

    import tritonclient_tpu.models
    from benchmarks import weights_swa_moe

    monkeypatch.setitem(sys.modules, "tritonclient_tpu.models.swa_moe", None)
    monkeypatch.delattr(tritonclient_tpu.models, "swa_moe", raising=False)
    monkeypatch.delitem(sys.modules, "_bench_adapters_swa_moe_paged_engine",
                        raising=False)
    monkeypatch.setattr(weights_swa_moe, "make_weights", lambda *a: 1 / 0)
    with pytest.raises(ImportError):
        harness.run(CELL, 1, 1.0, False, require_tpu=False,
                    benchmark_file=tiny_benchmark_file(tmp_path))


# --------------------------------------------------------------------------- #
# costs_swa_moe.py against hand counts                                        #
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "k-exaone-236b-a23b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def published(config):
    return costs.swa_moe_shape(config)


def test_parameter_counts_are_the_hand_counts(published):
    s = published
    # W_q and W_o 6144 x 8192 each, W_k and W_v 6144 x 1024 each: 113.2 M
    assert costs.attention_params(s) == 2 * 6144 * 8192 + 2 * 6144 * 1024
    assert costs.attention_params(s) == 113_246_208
    assert costs.expert_params(s) == 3 * 6144 * 2048 == 37_748_736
    assert costs.dense_ffn_params(s) == 3 * 6144 * 18432 == 339_738_624
    # the shared expert and the router's 128 outputs
    assert costs.shared_params(s) == 37_748_736 + 6144 * 128
    # 0.304 GB a layer outside the routed experts, 1.511 GB a sparse layer
    # of this chip, 0.906 GB the dense one, 0.472 GB embedding and head
    outside = 113_246_208 + 37_748_736 + 786_432
    assert round(2 * outside / 1e9, 3) == 0.304
    assert round(2 * (outside + 16 * 37_748_736) / 1e9, 3) == 1.512
    assert round(2 * (113_246_208 + 339_738_624) / 1e9, 3) == 0.906
    assert round(2 * 2 * 19200 * 6144 / 1e9, 3) == 0.472
    # 1 dense + 7 sparse layers of 16 experts + embedding and head: 11.96 GB
    held = (8 * 113_246_208 + 339_738_624
            + 7 * (16 * 37_748_736 + 38_535_168) + 2 * 19200 * 6144)
    assert costs.param_count(s) == held == 5_979_242_496
    assert round(2 * held / 1e9, 2) == 11.96
    assert costs.fixed_weight_bytes(s) == 2 * (
        8 * 113_246_208 + 339_738_624 + 7 * 38_535_168 + 19200 * 6144)
    # the held experts are 8.5 of the 12 GB a step can read
    assert round(2 * 7 * 16 * 37_748_736 / 1e9, 1) == 8.5


def test_cache_bytes_by_kind_are_the_hand_counts(published):
    s = published
    assert s.layer_kinds == ("window",) * 3 + ("global",) + (
        "window",) * 3 + ("global",)
    # keys and values of 8 heads of 128 in bfloat16: 4096 B a position a layer
    assert costs.kv_bytes_per_position(s) == 2 * 8 * 128 * 2 == 4096
    assert costs.page_bytes_by_kind(s, 16) == (2 * 65536, 6 * 65536)
    # 8 slots of 16384 positions in the 2 global layers: 1.07 GB
    assert round(8 * 16384 * 2 * 4096 / 1e9, 2) == 1.07


def test_the_programs_cache_is_what_the_costs_say(config, published):
    """The program's own reckoning (pool shapes, bytes by kind, the ring)
    at the published sizes, without allocating anything."""
    import jax.numpy as jnp

    from benchmarks.adapters import swa_moe_paged_engine as adapter
    from tritonclient_tpu.models import swa_moe

    engine = config["engine"]
    chunk, bs = engine["prefill_chunk"], engine["block_size"]
    model = swa_moe.SwaMoePaged(adapter.program_config(published), 8, chunk)
    ring = model.ring_pages(bs)
    assert ring == (128 + chunk) // 16 + 1
    assert model.kind_bytes(bs) == costs.page_bytes_by_kind(published, bs)
    n_blocks = 1 + 8 * 1024
    k_pool, v_pool = jax.eval_shape(lambda: model.pool_arrays(n_blocks, bs))
    assert k_pool.shape == v_pool.shape == (
        1, 2 * n_blocks + 6 * (1 + 8 * ring), 16, 1024)
    assert k_pool.dtype == jnp.bfloat16
    # the window layers' rings: under 0.2 GB beside the global layers' 1.07
    rings = 6 * (1 + 8 * ring) * 65536
    assert rings < 0.2e9 < 2 * n_blocks * 65536
    # a 13,312-token prompt's last chunk: the context in a global layer, the
    # window and the chunk in a window layer
    assert model.pages_read(13312, chunk, bs) == (
        832, 832 - (13312 - chunk + 1 - 128) // 16)
    assert model.pages_read(13312, 1, bs) == (832, 8)


def test_token_operations_count_the_pairs_this_chip_holds(published):
    s = published
    assert costs.expected_pairs_held(s) == 8 * 16 / 128 == 1.0
    outside = 8 * 113_246_208 + 339_738_624 + 7 * 38_535_168
    assert costs.token_flops(s, with_head=False) == 2.0 * (
        outside + 7 * 37_748_736)
    assert costs.token_flops(s, pairs_held=0.0) == 2.0 * (
        outside + 6144 * 19200)
    # q . k and p . v over 128 for 64 heads: 2 global layers attend the
    # context, 6 window layers the window
    assert costs.attend_flops(s, 1000, 128) == 4.0 * 64 * 128 * (
        2 * 1000 + 6 * 128)


def test_dispatch_work_reads_the_record_by_kind(published):
    s = published
    fixed, expert = costs.fixed_weight_bytes(s), 2 * 37_748_736
    decode = {"phase": "decode", "batch_size": 8, "micro_steps": 2,
              "tokens": 16, "ctx_tokens": 24000, "experts_hit": 90,
              "experts_held": 224, "expert_load_mean": 0.5,
              "ctx_pages_global": 3008, "ctx_pages_window": 144}
    work = costs.dispatch_work(s, decode, 16)
    assert work["bytes"] == (2 * fixed + 90 * expert
                             + 3008 * 2 * 65536 + 144 * 6 * 65536)
    keys = 24000 + 24008            # the second micro-step holds 8 more
    assert work["flops"] == (
        16 * costs.token_flops(s, with_head=False, pairs_held=0.0)
        + 2.0 * 112 * 37_748_736 + 16 * 2.0 * 6144 * 19200
        + costs.attend_flops(s, keys, 16 * 128))
    chunk = {"phase": "prefill_chunk", "batch_size": 2, "micro_steps": 1,
             "tokens": 512, "ctx_tokens": 9000, "experts_hit": 112,
             "experts_held": 112, "expert_load_mean": 4.0,
             "ctx_pages_global": 564, "ctx_pages_window": 50}
    work = costs.dispatch_work(s, chunk, 16)
    assert work["bytes"] == (fixed + 112 * expert + 564 * 2 * 65536
                             + 50 * 6 * 65536)
    # two lanes of 256 rows ending at 4500: a row attends 4500 - 127.5 keys
    assert work["flops"] == (
        512 * costs.token_flops(s, with_head=False, pairs_held=0.0)
        + 2.0 * 448 * 37_748_736 + 2 * 2.0 * 6144 * 19200
        + costs.attend_flops(s, 512 * (4500 - 127.5), 512 * 128))
    # another phase, a record the delivery thread has not reached, and a
    # family whose records are not split by kind
    assert costs.dispatch_work(s, dict(decode, phase="join"), 16) is None
    assert costs.dispatch_work(
        s, {k: v for k, v in decode.items() if k != "experts_hit"},
        16) is None
    assert costs.dispatch_work(
        s, {k: v for k, v in decode.items() if k != "ctx_pages_window"},
        16) is None
    # decode at these sizes is bound by bytes, not by operations
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    work = costs.dispatch_work(s, decode, 16)
    assert costs.roofline_seconds(work, peaks) == work["bytes"] / 819e9


# --------------------------------------------------------------------------- #
# the configuration and the cell                                              #
# --------------------------------------------------------------------------- #


def test_the_configuration_keeps_every_published_number(config):
    """Against the catalog row this configuration was drawn from, written
    out here: every key under its own name and value but the five the file
    lists under ``reduced``; the lists a layer are kept whole."""
    kinds = ["sliding_attention"] * 3 + ["full_attention"]
    published_config = {
        "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 6144, "intermediate_size": 18432,
        "layer_types": kinds * 12, "max_position_embeddings": 262144,
        "mlp_layer_types": ["dense"] + ["sparse"] * 47,
        "model_type": "exaone_moe", "moe_intermediate_size": 2048,
        "mtp_layer_types": ["full_attention"], "mtp_sliding_windows": [0],
        "n_group": 1, "norm_topk_prob": True, "num_attention_heads": 64,
        "num_experts": 128, "num_experts_per_tok": 8,
        "num_hidden_layers": 48, "num_key_value_heads": 8,
        "num_nextn_predict_layers": 1, "num_shared_experts": 1,
        "rms_norm_eps": 1e-05,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
        "sliding_window": 128, "sliding_window_pattern": "LLLG",
        "sliding_windows": [128, 128, 128, 0] * 12,
        "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}
    differs = sorted(k for k, v in published_config.items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == [
        "max_position_embeddings", "num_experts", "num_hidden_layers",
        "num_nextn_predict_layers", "vocab_size"]
    assert config["published"] == {k: published_config[k] for k in differs}
    assert [config[k] for k in differs] == [16384, 16, 8, 0, 19200]
    # no width, head count, router width, top-k, window or theta is cut
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "head_dim", "num_attention_heads", "num_key_value_heads",
                "num_experts_per_tok", "sliding_window",
                "rope_parameters"} & set(config["reduced"])
    assert config["expert_share"] == {
        "router_outputs": 128, "experts_held": 16, "first_expert": 0,
        "chips_a_layer": 8}
    for key in ("norm_placement", "qk_norm", "rope_in_both_kinds",
                "e_score_correction_bias", *config["reduced"]):
        assert len(config["assumed"][key]) > 40, key
    assert "8 chips" in config["deployment"]
    assert "stage 0 of a 6-stage" in config["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "k-exaone-236b-a23b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/"
        "config.json")


def test_the_shape_is_read_from_the_published_keys(config, published):
    s = published
    assert (s.n_layer, s.n_dense_layer, s.n_moe_layer) == (8, 1, 7)
    assert (s.d_model, s.n_head, s.n_kv_head, s.head_dim) == (
        6144, 64, 8, 128)
    assert (s.n_experts, s.experts_held, s.first_expert,
            s.experts_per_token) == (128, 16, 0, 8)
    assert (s.window, s.rope_theta, s.rms_norm_eps) == (128, 1e6, 1e-5)
    assert (s.layers_of("window"), s.layers_of("global")) == (6, 2)
    for key, value in (("num_nextn_predict_layers", 1),
                       ("scoring_func", "softmax"), ("n_group", 2),
                       ("rope_parameters", {"rope_theta": 1e6,
                                            "rope_type": "yarn"}),
                       ("mlp_layer_types", ["sparse"] * 48),
                       ("sliding_windows", [128] * 48),
                       ("num_experts", 32)):
        with pytest.raises(ValueError):
            costs.swa_moe_shape(dict(config, **{key: value}))


def test_the_cell_resolves_and_its_longest_request_fits(config):
    """What `test_benchmark_spec.py` holds a GPT-keyed configuration to,
    under this family's key names (tests/conftest.py says why)."""
    from benchmarks import spec, traffic

    cell = spec.load_cell(REAL_CELL)
    assert cell.chips == 1 and cell.config == config
    mix = cell.traffic
    assert (mix["loop"], mix["clients"]) == ("closed", 8)
    assert mix["clients"] == config["engine"]["max_slots"]
    prompts, outputs = traffic.length_set(mix)
    assert (prompts[0], prompts[-1], len(prompts)) == (357, 13312, 16)
    assert 55_000 < sum(prompts) < 57_000           # a lap's prompt tokens
    assert 2100 < (prompts[7] + prompts[8]) / 2 < 2500
    assert (outputs[0], outputs[-1]) == (8, 354)
    # the longest pair a seed can make, and the most the mix's cuts allow
    assert traffic.longest_request(mix) == 13666
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            == 13312 + 512 <= config["max_position_embeddings"] == 16384)
    shape = costs.swa_moe_shape(cell.config)
    assert shape.n_positions % config["engine"]["block_size"] == 0
    limits = [config["check"][k] for k in (
        "served_logit_gap_max_limit", "served_logit_gap_p99_limit",
        "served_logit_gap_mean_limit")]
    assert any(v is not None for v in limits)
    assert all(v is None or v > 0 for v in limits)
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= reported and len(reported) == 15 + 5
    assert not {"step_mfu", "step_roofline_share", "mla_moe_step_mfu",
                "moe_experts_hit_share"} & reported


# --------------------------------------------------------------------------- #
# the new readers, on records written out here                                #
# --------------------------------------------------------------------------- #


def _reader(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return harness.load_reader(bench, "layer_metrics", name, REPO)


def _obs(shape, steps, device_ops=(), **kw):
    cell = SimpleNamespace(config={"engine": {"block_size": 16,
                                              "prefill_chunk": 512}})
    return SimpleNamespace(
        shape=shape, cell=cell, chips=1, steps=list(steps),
        peaks={"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        trace={"device_ops": [list(op) for op in device_ops],
               "span_ns": (0, 10**9), "busy_s": 0.9},
        decode_steps=lambda: [r for r in steps if r["phase"] == "decode"],
        **kw)


def test_kv_window_held_share_reads_the_peak_record(published):
    read = _reader("kv_window_held_share")
    g, w = 2 * 65536, 6 * 65536
    steps = [{"phase": "decode", "kv_held_global_bytes": 100 * g,
              "kv_held_window_bytes": 60 * w},
             {"phase": "decode", "kv_held_global_bytes": 900 * g,
              "kv_held_window_bytes": 8 * 25 * w}]
    # at the peak 900 pages a global layer, 200 a window layer
    assert read(_obs(published, steps)) == pytest.approx(100 * 200 / 900)
    assert read(_obs(published, [{"phase": "decode"}])) is None
    assert read(_obs(object(), steps)) is None


def test_the_kernels_share_counts_the_shapes_whose_events_were_kept(
        published):
    read = _reader("swa_paged_attention_roofline_share")
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    decode = {"phase": "decode", "start_ns": 5, "lanes": 8, "batch_size": 8,
              "tokens": 8, "micro_steps": 1, "ctx_tokens": 16000,
              "ctx_pages_global": 1000, "ctx_pages_window": 64}
    chunk = {"phase": "prefill_chunk", "start_ns": 6, "lanes": 2,
             "batch_size": 2, "tokens": 1024, "micro_steps": 1,
             "ctx_tokens": 12000, "ctx_pages_global": 750,
             "ctx_pages_window": 80}
    other = dict(chunk, lanes=4, batch_size=3)
    # decode's one shape; the two-lane bucket's (16 tables of 512 rows: 8
    # tiles of 64 positions x 8 heads a lane); the ledger's form of a name
    ops = [("paged_attention.17 f32[8,8,1024]", 0.002),
           ("ragged-dot-none.2 f32[4096,6144]", 0.5),
           ("paged_attention.11_f32_16_512_1024_", 0.004)]
    least = {name: costs.roofline_seconds(
        costs.attention_work(published, r, 16), peaks)
        for name, r in (("decode", decode), ("chunk", chunk))}
    # decode is bound by its bytes, the chunk by its operations
    assert least["decode"] == (1000 * 2 + 64 * 6) * 65536 / 819e9
    assert least["chunk"] == costs.attention_work(
        published, chunk, 16)["flops"] / 197e12
    assert read(_obs(published, [decode, chunk], ops)) == pytest.approx(
        100 * (least["decode"] + least["chunk"]) / 0.006)
    # a four-lane bucket ran whose kernel is not among the kept: its
    # dispatches are left out, on both sides
    assert read(_obs(published, [decode, chunk, other], ops)) == (
        pytest.approx(100 * (least["decode"] + least["chunk"]) / 0.006))
    # only decode's event kept: decode alone
    assert read(_obs(published, [decode, chunk], ops[:2])) == pytest.approx(
        100 * least["decode"] / 0.002)
    # no kernel event among the kept, another family, off the chip
    assert read(_obs(published, [decode], ops[1:2])) is None
    assert read(_obs(object(), [decode], ops)) is None
    obs = _obs(published, [decode], ops)
    obs.peaks = None
    assert read(obs) is None


def test_step_mfu_counts_a_window_layers_keys_up_to_the_window(published):
    from benchmarks.layer_metrics import swa_moe_step_mfu as reader

    s = published
    # rows 0..299: 1 + 2 + ... + 128 on the ramp, then 172 rows of 128
    assert reader._window_keys(s, 0, 300) == 128 * 129 / 2 + 172 * 128
    assert reader._window_keys(s, 0, 100) == 100 * 101 / 2
    assert reader._window_keys(s, 200, 300) == 100 * 128
