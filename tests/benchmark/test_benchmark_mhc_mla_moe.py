"""The MLA / routed-expert family with a multi-stream residual path (mHC) and
YaRN-scaled positions in the benchmark, on the CPU at a test-only tiny cell
added as files and entries: a whole run of both kinds, the timed path broken
underneath coming out as not correct by this configuration's reference, the
parameters and bytes of the published configuration counted by hand, the
configuration against its catalog row, and the three new readers on dispatch
records written out here. It says nothing about the device: every number
here is from the CPU backend or from the arithmetic."""

import json
import os
from types import SimpleNamespace

import jax
import pytest

from _bench_tiny import REPO
from _bench_tiny_mhc_mla_moe import CELL, REAL_CELL, tiny_benchmark_file
from benchmarks import costs_mhc_mla_moe as costs
from benchmarks import harness

NEW_METRICS = {"mhc_mla_moe_step_mfu", "mhc_mla_moe_step_roofline_share",
               "mhc_moe_experts_hit_share"}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(autouse=True)
def _no_shared_compile_cache(monkeypatch, tmp_path):
    # A test process keeps JAX's configuration to itself, and its profiler
    # trace too (the files of this directory run side by side).
    monkeypatch.setattr(harness, "configure_compile_cache", lambda: "(none)")
    monkeypatch.setattr(harness, "SCRATCH_DIR", str(tmp_path / "scratch"))


@pytest.fixture(scope="module")
def bench_file(tmp_path_factory):
    return tiny_benchmark_file(tmp_path_factory.mktemp("bench"))


def _run(bench_file, trace, seed=2**31 + 77, seconds=2.0):
    return harness.run(CELL, seed, seconds, trace, require_tpu=False,
                       benchmark_file=bench_file)


def test_untraced_run_is_correct_and_prints_the_contracts_keys(bench_file):
    result = _run(bench_file, trace=False)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert set(result["check"]) == {"served_logit_gap_max", "failed_requests",
                                    "checked_tokens"}
    assert result["check"]["checked_tokens"]["value"] >= 8
    assert json.loads(json.dumps(result)) == result


def test_traced_run_reports_the_shared_layers_and_the_new_counter(bench_file):
    """The fifteen readers the families share read this cell unchanged, the
    experts' counter is there, and the two shares of a peak are left out
    off the chip, never 0; no other family's readers are this cell's."""
    result = _run(bench_file, trace=True)
    assert result["correct"] is True
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = json.load(f)["per_layer"]
    shared = {m["name"] for m in entries if "workloads" not in m}
    assert len(shared) == 15
    assert {m["name"] for m in entries
            if m.get("workloads") == [REAL_CELL]} == NEW_METRICS
    off_chip = shared - {"device_idle_share"}
    assert set(result["metrics"]) == off_chip | {"mhc_moe_experts_hit_share"}
    assert 0 < result["metrics"]["mhc_moe_experts_hit_share"]["value"] <= 100
    assert 0 < result["device"]["busy_s"] < result["device"]["window_s"]


def _alter_a_token(monkeypatch):
    """Every 7th position of every slot: the token one up from the one the
    step produced, altered where it is produced (inside the decode step)."""
    from tritonclient_tpu.models import mla_moe

    produce = mla_moe._decode_step_latent

    def broken(params, pool, btabs, tokens, pos, *rest, **kw):
        nxt, pool, counts = produce(params, pool, btabs, tokens, pos, *rest,
                                    **kw)
        vocab = params["embed"]["tok"].shape[0]
        return (jax.numpy.where(pos % 7 == 0, (nxt + 1) % vocab, nxt), pool,
                counts)

    monkeypatch.setattr(mla_moe, "_decode_step_latent", broken)


def _streams_unmixed(monkeypatch):
    """The program mixes its streams by the identity where the model says a
    Sinkhorn-normalised matrix a token: every other part is right."""
    import jax.numpy as jnp

    from tritonclient_tpu.models import mhc

    def identity(m, iters, eps):
        one, zero = jnp.ones_like(m[0][0]), jnp.zeros_like(m[0][0])
        return [[one if i == j else zero for j in range(len(m))]
                for i in range(len(m))]

    monkeypatch.setattr(mhc, "sinkhorn", identity)


@pytest.mark.parametrize("fault", [_alter_a_token, _streams_unmixed],
                         ids=["token_altered", "streams_unmixed"])
def test_a_broken_timed_path_comes_out_as_not_correct(bench_file, monkeypatch,
                                                      fault):
    fault(monkeypatch)
    result = _run(bench_file, trace=False)
    assert result["correct"] is False
    entry = result["check"]["served_logit_gap_max"]
    assert entry["value"] > entry["limit"]
    assert result["failed"] == 0


def test_the_parent_of_this_configuration_fails_at_once_without_the_maps(
        tmp_path, monkeypatch):
    """A checkout that has the benchmark's files and not the program's part
    (the parent commit, laid over with this cell: ``models/mla_moe.py`` is
    there, ``models/mhc.py`` is not) fails while the adapter is loaded,
    before any weight is made."""
    import sys

    import tritonclient_tpu.models
    from benchmarks import weights_mla_moe

    monkeypatch.setitem(sys.modules, "tritonclient_tpu.models.mhc", None)
    monkeypatch.delattr(tritonclient_tpu.models, "mhc", raising=False)
    monkeypatch.delitem(sys.modules,
                        "_bench_adapters_mhc_mla_moe_paged_engine",
                        raising=False)
    monkeypatch.setattr(weights_mla_moe, "make_weights", lambda *a: 1 / 0)
    with pytest.raises(ImportError):
        harness.run(CELL, 1, 1.0, False, require_tpu=False,
                    benchmark_file=tiny_benchmark_file(tmp_path))


# --------------------------------------------------------------------------- #
# the published configuration, counted by hand                                #
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def published(config):
    return costs.mhc_mla_moe_shape(config)


def test_parameter_counts_are_the_hand_counts(published):
    s = published
    # W_qa 3584 x 768, W_qb 768 x 32 x 192, W_kva 3584 x 576,
    # W_kvb 512 x 32 x 256, W_o 4096 x 3584: 28.41 M
    assert costs.attention_params(s) == (
        3584 * 768 + 768 * 6144 + 3584 * 576 + 512 * 8192 + 4096 * 3584)
    assert costs.attention_params(s) == 28_409_856
    assert costs.dense_ffn_params(s) == 3 * 3584 * 9216 == 99_090_432
    assert costs.expert_params(s) == 3 * 3584 * 1024 == 11_010_048
    # the shared expert and the router's 64 outputs: 11.01 + 0.23 M
    assert costs.shared_params(s) == 11_010_048 + 3584 * 64
    # two sets of maps a layer: phi [4 x 3584, 2 x 4 + 16] each: 0.69 M
    assert (s.stream_width, s.hc_coefficients) == (14336, 24)
    assert costs.maps_params(s) == 2 * 14336 * 24 == 688_128
    # the catalog's "about 40 M a layer beside its experts"
    beside = 28_409_856 + 11_010_048 + 3584 * 64 + 688_128
    assert round(beside / 1e6, 2) == 40.34
    dense_layer = 28_409_856 + 99_090_432 + 688_128
    expert_layer = beside + 64 * 11_010_048
    assert round(dense_layer / 1e6, 1) == 128.2
    assert round(expert_layer / 1e6, 1) == 745.0
    assert round(2 * expert_layer / 1e9, 2) == 1.49
    head = 2 * 131072 * 3584
    assert round(head / 1e6, 1) == 939.5
    held = 2 * dense_layer + 6 * expert_layer + head
    assert costs.param_count(s) == held == 5_665_783_808
    assert round(2 * held / 1e9, 2) == 11.33
    assert round(100 * 2 * held / 16e9, 1) == 70.8
    # what a step reads whatever the routing: everything but the routed
    # experts and the input embedding
    assert costs.fixed_weight_bytes(s) == 2 * (
        held - 6 * 64 * 11_010_048 - 131072 * 3584)
    # the routed experts are 8.46 of the 11.33 GB
    assert round(2 * 6 * 64 * 11_010_048 / 1e9, 2) == 8.46


def test_the_programs_pool_is_what_the_costs_say(config, published):
    """The program's own reckoning at the published sizes, without
    allocating anything: a latent pool of [8, 1 + 8 x 512, 16, 640]
    bfloat16, 0.67 GB; 12.0 GB with the weights, 75% of the chip."""
    import jax.numpy as jnp

    from benchmarks.adapters import mhc_mla_moe_paged_engine as adapter
    from tritonclient_tpu.models import mla_moe

    cfg = adapter.program_config(published)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.rope_scaling == mla_moe.YarnScaling(
        factor=64.0, original_max_len=4096, beta_fast=32.0, beta_slow=1.0,
        mscale=1.0, mscale_all_dim=1.0)
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.max_len) == (8, 2, 8192)
    model = mla_moe.MlaMoePaged(cfg)
    n_blocks = 1 + 8 * (8192 // 16)
    (pool,) = jax.eval_shape(lambda: model.pool_arrays(n_blocks, 16))
    assert pool.shape == (8, 4097, 16, 640) and pool.dtype == jnp.bfloat16
    pool_bytes = 8 * 4097 * 16 * 640 * 2
    assert round(pool_bytes / 1e9, 2) == 0.67
    assert costs.latent_bytes_per_position(published) == 8 * 576 * 2
    total = 2 * costs.param_count(published) + pool_bytes
    assert round(total / 1e9, 1) == 12.0 and round(100 * total / 16e9) == 75
    # the program's parameter tree holds what the costs count (and the
    # norms' vectors, the router's bias and the maps' b and alpha)
    tree = jax.eval_shape(
        lambda: mla_moe.init_params(jax.random.PRNGKey(0), cfg))
    matrices = sum(leaf.size for leaf in jax.tree.leaves(tree)
                   if leaf.ndim >= 3 or leaf.shape[0] == 131072
                   or leaf.shape[-1] == 131072)
    assert matrices == costs.param_count(published)


def test_the_maps_bytes_and_operations_are_the_hand_counts(published):
    s = published
    # one (row, sublayer) pair: three passes over 4 x 3584 bfloat16, and a
    # 14336 x 24 projection
    assert costs.maps_work(s, 1) == {"flops": 2.0 * 14336 * 24,
                                     "bytes": 3.0 * 14336 * 2}
    assert costs.maps_work(s, 1)["bytes"] == 86_016          # "about 86 KB"
    # a token: 16 sublayers in 8 layers: 1.38 MB
    assert costs.maps_work(s, 16)["bytes"] == 1_376_256
    # a full 8-lane x 128-row chunk dispatch: 1.41 GB, 1.72 ms at 819 GB/s
    full = costs.maps_work(s, 8 * 128 * 16)
    assert full["bytes"] == 1_409_286_144
    assert costs.roofline_seconds(full, PEAKS) == full["bytes"] / 819e9
    assert round(1e3 * full["bytes"] / 819e9, 2) == 1.72
    # bound by bytes, not by the projection's operations
    assert full["flops"] / 197e12 < 0.05 * full["bytes"] / 819e9
    # a token's operations gain the 16 projections
    from benchmarks import costs_mla_moe

    assert costs.token_flops(s, 100.0) == (
        costs_mla_moe.token_flops(s, 100.0) + 16 * 2.0 * 14336 * 24)


def test_dispatch_work_adds_the_maps_to_the_familys_counts(published):
    from benchmarks import costs_mla_moe

    s = published
    chunk = {"phase": "prefill_chunk", "batch_size": 8, "micro_steps": 1,
             "tokens": 1000, "ctx_tokens": 24000, "experts_hit": 380,
             "experts_held": 384, "hc_streams": 4, "hc_rows": 16000}
    base = costs_mla_moe.dispatch_work(s, chunk)
    work = costs.dispatch_work(s, chunk)
    phi = 2 * 8 * 688_128                   # the maps' phi, read once
    assert work["bytes"] == base["bytes"] + 16000 * 86_016 + phi
    assert work["flops"] == base["flops"] + 16000 * 2.0 * 14336 * 24
    decode = {"phase": "decode", "batch_size": 6, "micro_steps": 4,
              "tokens": 24, "ctx_tokens": 9000, "experts_hit": 500,
              "experts_held": 1536, "hc_streams": 4, "hc_rows": 24 * 16}
    base = costs_mla_moe.dispatch_work(s, decode)
    work = costs.dispatch_work(s, decode)
    assert work["bytes"] == base["bytes"] + 384 * 86_016 + 4 * phi
    # decode is bound by its bytes; the maps are under a hundredth of them
    assert costs.roofline_seconds(work, PEAKS) == work["bytes"] / 819e9
    assert 384 * 86_016 < 0.01 * work["bytes"]
    # a record the delivery thread has not reached, and another phase
    assert costs.dispatch_work(
        s, {k: v for k, v in decode.items() if k != "hc_rows"}) is None
    assert costs.dispatch_work(
        s, {k: v for k, v in decode.items() if k != "experts_hit"}) is None
    assert costs.dispatch_work(s, dict(decode, phase="join")) is None


# --------------------------------------------------------------------------- #
# the configuration and the cell                                              #
# --------------------------------------------------------------------------- #


def test_the_configuration_keeps_every_published_number(config):
    """Against the catalog row this configuration was drawn from, written
    out here: every key under its own name and value but the three the file
    lists under ``reduced``; the nested ``rope_scaling`` whole."""
    published_config = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
        "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
        "kv_lora_rank": 512, "max_position_embeddings": 262144,
        "model_type": "xing4_0", "moe_intermediate_size": 1024,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 32,
        "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
        "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30,
        "mhc_h_res_clamp_max": 30, "q_lora_rank": 768,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "rms_norm_eps": 1e-06, "rope_theta": 10000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                         "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 4096,
                         "type": "yarn"},
        "routed_scaling_factor": 2, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}
    differs = sorted(k for k, v in published_config.items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers",
        "num_nextn_predict_layers"]
    assert config["published"] == {k: published_config[k] for k in differs}
    assert [config[k] for k in differs] == [8192, 8, 0]
    # no width, head count, expert count, top-k, stream count, iteration
    # count or scaling is cut
    assert not {"hidden_size", "intermediate_size", "moe_intermediate_size",
                "n_routed_experts", "num_experts_per_tok", "vocab_size",
                "hc_mult", "hc_sinkhorn_iters", "rope_scaling",
                "kv_lora_rank", "q_lora_rank"} & set(config["reduced"])
    for key in ("hc_map_sets", "hc_streams_start_and_end", "hc_eps_and_norm",
                "hc_coefficients", "rope_interleave", "yarn", "hc_weights",
                *config["reduced"]):
        assert len(config["assumed"][key]) > 40, key
    assert "stage 0 of a 5-stage" in config["deployment"]
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entry = next(c for c in json.load(f)["configs"]
                     if c["name"] == "xing4.0-29b-a4b")
    assert entry["reduced"] == config["reduced"]
    assert entry["source"] == config["source"] == (
        "https://huggingface.co/XingChen-AGI/Xing4.0-29B-A4B/blob/main/"
        "config.json")


def test_the_shape_is_read_from_the_published_keys(config, published):
    s = published
    assert (s.n_layer, s.n_dense_layer, s.n_moe_layer) == (8, 2, 6)
    assert (s.d_model, s.n_head, s.q_lora_rank, s.kv_lora_rank) == (
        3584, 32, 768, 512)
    assert (s.n_experts, s.experts_per_token, s.d_expert,
            s.routed_scaling_factor) == (64, 4, 1024, 2.0)
    assert (s.hc_mult, s.hc_sinkhorn_iters, s.hc_eps, s.hc_res_clamp_min,
            s.hc_res_clamp_max) == (4, 20, 1e-6, -30.0, 30.0)
    assert (s.yarn_factor, s.yarn_original_positions, s.yarn_beta_fast,
            s.yarn_beta_slow, s.yarn_mscale, s.yarn_mscale_all_dim) == (
        64.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert (s.rope_theta, s.n_positions, s.vocab_size) == (1e4, 8192, 131072)
    for key, value in (
            ("num_nextn_predict_layers", 1), ("scoring_func", "softmax"),
            ("n_group", 2), ("ep_size", 8), ("rope_interleave", False),
            ("topk_method", "greedy"), ("hc_mult", 0),
            ("rope_scaling", {"type": "linear", "factor": 4}),
            ("rope_scaling", dict(config["rope_scaling"], truncate=False)),
            ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            costs.mhc_mla_moe_shape(dict(config, **{key: value}))
    # no scaling at all is the family's plain rotary
    plain = costs.mhc_mla_moe_shape(dict(config, rope_scaling=None))
    assert plain.yarn_factor == 1.0


def test_the_cell_resolves_and_its_longest_request_fits(config):
    """What `test_benchmark_spec.py` holds a GPT-keyed configuration to,
    under this family's key names (tests/conftest.py says why)."""
    from benchmarks import spec, traffic

    cell = spec.load_cell(REAL_CELL)
    assert cell.chips == 1 and cell.config == config
    mix = cell.traffic
    assert (mix["loop"], mix["clients"]) == ("closed", 8)
    assert mix["clients"] == config["engine"]["max_slots"]
    assert (mix["shared_prefix_tokens"], mix["length_scale"],
            mix["set_size"]) == (0, 1.0, 16)
    prompts, outputs = traffic.length_set(mix)
    assert (prompts[0], prompts[-1], sum(prompts)) == (344, 6534, 31770)
    assert (outputs[0], outputs[-1], sum(outputs)) == (2, 130, 397)
    assert round(sum(prompts) / sum(outputs)) == 80
    # nothing of the set is clipped by the mix's cuts
    assert mix["prompt_tokens"]["min"] < 344 and 6534 < (
        mix["prompt_tokens"]["max"])
    assert 130 < mix["output_tokens"]["max"]
    # the longest pair a seed can make fits the positions served and passes
    # the 4096 original positions YaRN scales from
    assert traffic.longest_request(mix) == 6664
    assert 4096 < 6664 < config["max_position_embeddings"] == 8192
    assert (mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
            <= config["max_position_embeddings"])
    shape = costs.mhc_mla_moe_shape(cell.config)
    assert shape.n_positions % config["engine"]["block_size"] == 0
    limits = [config["check"][k] for k in (
        "served_logit_gap_max_limit", "served_logit_gap_p99_limit",
        "served_logit_gap_mean_limit")]
    assert any(v is not None for v in limits)
    assert all(v is None or v > 0 for v in limits)
    reported = {m["name"] for m in cell.per_layer}
    assert NEW_METRICS <= reported and len(reported) == 15 + 3
    assert not {"step_mfu", "mla_moe_step_mfu", "moe_experts_hit_share",
                "swa_moe_step_mfu"} & reported


def test_benchmark_json_gained_one_configuration_one_cell_three_metrics():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [c["name"] for c in bench["configs"]][-1] == "xing4.0-29b-a4b"
    assert bench["workloads"][-1] == {
        "name": REAL_CELL, "config": "xing4.0-29b-a4b", "traffic": "code",
        "chips": 1, "why": bench["workloads"][-1]["why"]}
    assert len(bench["configs"]) == len(bench["workloads"]) == 5
    assert [m["name"] for m in bench["per_layer"]][-3:] == [
        "mhc_mla_moe_step_mfu", "mhc_mla_moe_step_roofline_share",
        "mhc_moe_experts_hit_share"]
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == [REAL_CELL]
        assert m["moves"] == "output_tokens_per_s"
    for entry in bench["configs"] + bench["workloads"]:
        assert len(entry["why"]) <= 200


# --------------------------------------------------------------------------- #
# the new readers, on records written out here                                #
# --------------------------------------------------------------------------- #


def _reader(name):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return harness.load_reader(bench, "layer_metrics", name, REPO)


def _obs(shape, steps, device_ops=(), **kw):
    cell = SimpleNamespace(config={"engine": {
        "block_size": 16, "prefill_chunk": 128, "max_slots": 8}})
    return SimpleNamespace(
        shape=shape, cell=cell, chips=1, steps=list(steps), peaks=PEAKS,
        trace={"device_ops": [list(op) for op in device_ops],
               "span_ns": (0, 10**9), "busy_s": 0.9},
        decode_steps=lambda: [r for r in steps if r["phase"] == "decode"],
        **kw)


CHUNK = {"phase": "prefill_chunk", "start_ns": 5, "lanes": 8,
         "batch_size": 7, "micro_steps": 1, "tokens": 800,
         "ctx_tokens": 20000, "experts_hit": 380, "experts_held": 384,
         "routed_tokens": 800, "hc_streams": 4, "hc_rows": 800 * 16}
DECODE = {"phase": "decode", "start_ns": 6, "lanes": 8, "batch_size": 5,
          "micro_steps": 2, "tokens": 10, "ctx_tokens": 9000,
          "experts_hit": 190, "experts_held": 768, "routed_tokens": 10,
          "hc_streams": 4, "hc_rows": 10 * 16}


def test_the_steps_roofline_share_sums_the_dispatches_least_times(published):
    read = _reader("mhc_mla_moe_step_roofline_share")
    least = sum(costs.roofline_seconds(costs.dispatch_work(published, r),
                                       PEAKS) for r in (CHUNK, DECODE))
    assert read(_obs(published, [CHUNK, DECODE])) == pytest.approx(
        100 * least / 0.9)
    # a dispatch outside the traced span, one without its counters yet
    late = dict(CHUNK, start_ns=2 * 10**9)
    bare = {k: v for k, v in DECODE.items() if k != "hc_rows"}
    assert read(_obs(published, [CHUNK, DECODE, late, bare])) == (
        pytest.approx(100 * least / 0.9))
    assert read(_obs(object(), [CHUNK])) is None
    obs = _obs(published, [CHUNK])
    obs.peaks = None
    assert read(obs) is None


def test_the_experts_counter_reads_the_decode_records(published):
    read = _reader("mhc_moe_experts_hit_share")
    assert read(_obs(published, [CHUNK, DECODE])) == pytest.approx(
        100 * 190 / 768)
    assert read(_obs(published, [CHUNK])) is None
    assert read(_obs(object(), [DECODE])) is None


def test_step_mfu_counts_the_maps_projections(published):
    import numpy as np

    read = _reader("mhc_mla_moe_step_mfu")
    log = SimpleNamespace(
        request=SimpleNamespace(prompt=np.zeros((1, 1000), np.int32)),
        token_ns=[10, 20, 30], error=None)
    obs = SimpleNamespace(
        shape=published, peaks=PEAKS, chips=1, window_s=2.0,
        window={"start_ns": 0, "end_ns": 100}, finished=lambda: [log])
    flops = (1000 * costs.token_flops(published, 0, with_head=False)
             + costs.attend_flops(published, 1000 * 1001 / 2)
             + 2.0 * 3584 * 131072
             + costs.token_flops(published, 1001)
             + costs.token_flops(published, 1002))
    assert read(obs) == pytest.approx(100 * flops / (2.0 * 197e12))
    obs.shape = object()
    assert read(obs) is None


def test_every_reader_of_this_family_is_a_metric_the_cell_reports():
    # A reader no entry names is never run; an entry that lists a cell in
    # which its reader finds nothing is refused (a share of the maps' own
    # roofline was both: the harness keeps ten operations and the maps'
    # are a hundredth of device time, PERF.md section 7).
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    listed = {m["name"] for m in bench["per_layer"]
              if REAL_CELL in m.get("workloads", ())}
    files = {name[:-3] for name in os.listdir(
        os.path.join(REPO, "benchmarks", "layer_metrics"))
        if name.startswith("mhc_") and name.endswith(".py")}
    assert files == listed == NEW_METRICS
