"""The MLA / routed-expert family in the benchmark, on the CPU at a
test-only tiny cell added as files and entries: a whole run of both kinds,
the timed path broken underneath coming out as not correct by this family's
reference, and the cost functions against hand counts. It says nothing
about the device: every number here is from the CPU backend."""

import json
import os

import jax
import pytest

from _bench_tiny import REPO
from _bench_tiny_mla_moe import CELL, tiny_benchmark_file
from benchmarks import costs_mla_moe as costs
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


def test_untraced_run_is_correct_and_prints_the_contracts_keys(bench_file):
    result = _run(bench_file, trace=False)
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 8
    assert set(result["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert set(result["check"]) == {"served_logit_gap_max", "failed_requests",
                                    "checked_tokens"}
    assert result["check"]["checked_tokens"]["value"] >= 8
    assert json.loads(json.dumps(result)) == result


def test_traced_run_reports_the_shared_layers_and_the_routers_counters(
        bench_file):
    """The fifteen readers the families share read this cell unchanged (the
    two of them that need no chip's peak among them), the router's two
    counters are there, and the shares of a peak are left out off the chip,
    never 0; the GPT family's two cost readers are not this cell's."""
    result = _run(bench_file, trace=True)
    assert result["correct"] is True
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        entries = json.load(f)["per_layer"]
    shared = {m["name"] for m in entries if "workloads" not in m}
    assert len(shared) == 15
    off_chip = shared - {"device_idle_share"}
    assert set(result["metrics"]) == off_chip | {"moe_experts_hit_share",
                                                 "moe_load_imbalance"}
    assert 0 < result["metrics"]["moe_experts_hit_share"]["value"] <= 100
    assert result["metrics"]["moe_load_imbalance"]["value"] >= 1
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


def _stale_page(monkeypatch):
    """A prefill chunk writes its latents one page off: decode reads a page
    the prompt never reached."""
    from tritonclient_tpu.models import mla_moe

    prefill = mla_moe._prefill_chunk_latent

    def broken(params, pool, chunks, btabs, *rest, **kw):
        shifted = jax.numpy.roll(btabs, 1, axis=1)
        return prefill(params, pool, chunks, shifted, *rest, **kw)

    monkeypatch.setattr(mla_moe, "_prefill_chunk_latent", broken)


@pytest.mark.parametrize("fault", [_alter_a_token, _stale_page],
                         ids=["token_altered", "latent_page_misplaced"])
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

    monkeypatch.setitem(sys.modules, "tritonclient_tpu.models.mla_moe", None)
    monkeypatch.delattr(tritonclient_tpu.models, "mla_moe", raising=False)
    monkeypatch.delitem(sys.modules, "_bench_adapters_mla_moe_paged_engine",
                        raising=False)
    with pytest.raises(ImportError):
        harness.run(CELL, 1, 1.0, False, require_tpu=False,
                    benchmark_file=tiny_benchmark_file(tmp_path))


# --------------------------------------------------------------------------- #
# costs_mla_moe.py against hand counts                                        #
# --------------------------------------------------------------------------- #


@pytest.fixture(scope="module")
def published():
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        return costs.mla_moe_shape(json.load(f))


def test_parameter_counts_are_the_hand_counts(published):
    s = published
    # 2048*1536 + 1536*6144 + 2048*576 + 512*8192 + 4096*2048
    assert costs.attention_params(s) == 26_345_472
    assert costs.expert_params(s) == 3 * 2048 * 768 == 4_718_592
    assert costs.dense_ffn_params(s) == 3 * 2048 * 7168
    assert costs.shared_params(s) == 4_718_592 + 2048 * 256
    # 1 dense + 4 expert layers + embedding and head, 2 bytes each: 11.12 GB
    held = (5 * 26_345_472 + 44_040_192
            + 4 * (256 * 4_718_592 + 5_242_880) + 2 * 129280 * 2048)
    assert costs.param_count(s) == held == 5_558_108_160
    assert costs.fixed_weight_bytes(s) == 2 * (
        5 * 26_345_472 + 44_040_192 + 4 * 5_242_880 + 129280 * 2048)
    assert costs.latent_bytes_per_position(s) == 5 * 576 * 2


def test_token_operations_count_the_active_experts_only(published):
    s = published
    active = (5 * 26_345_472 + 44_040_192
              + 4 * (8 * 4_718_592 + 5_242_880))        # 347.7 M
    assert costs.token_flops(s, 0, with_head=False) == 2.0 * active
    assert costs.attend_flops(s, 1) == 2.0 * 5 * 32 * (192 + 128)
    assert costs.token_flops(s, 600) == (
        2.0 * (active + 129280 * 2048) + 600 * 2.0 * 5 * 32 * 320)


def test_dispatch_work_reads_the_record_and_the_experts_hit(published):
    s = published
    fixed, expert = costs.fixed_weight_bytes(s), 2 * 4_718_592
    decode = {"phase": "decode", "batch_size": 8, "micro_steps": 2,
              "tokens": 16, "ctx_tokens": 4800, "experts_hit": 450}
    work = costs.dispatch_work(s, decode)
    # 2 micro-steps: the first holds 4800 positions, the second 4808
    held = 4800 + 4808
    assert work["bytes"] == 2 * fixed + 450 * expert + held * 5760
    assert work["flops"] == (16 * costs.token_flops(s, 0)
                             + costs.attend_flops(s, held))
    chunk = {"phase": "prefill_chunk", "batch_size": 2, "micro_steps": 1,
             "tokens": 300, "ctx_tokens": 900, "experts_hit": 1000}
    work = costs.dispatch_work(s, chunk)
    assert work["bytes"] == fixed + 1000 * expert + 900 * 5760
    # two lanes of 150 rows ending at 450: a row attends 450 - 74.5 positions
    assert work["flops"] == (
        300 * costs.token_flops(s, 0, with_head=False)
        + costs.attend_flops(s, 300 * (450 - 74.5))
        + 2 * 2.0 * 2048 * 129280)
    # a record the delivery thread has not reached, or another family's
    assert costs.dispatch_work(s, dict(decode, phase="join")) is None
    del decode["experts_hit"]
    assert costs.dispatch_work(s, decode) is None
    # decode at these sizes is bound by bytes, not by operations
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert costs.roofline_seconds(work, peaks) == work["bytes"] / 819e9


def test_the_configuration_keeps_every_published_number():
    """Against the catalog row this configuration was drawn from, written
    out here: every key under its own name and value but the three the file
    lists under ``reduced``."""
    published_config = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    with open(os.path.join(REPO, "benchmarks", "configs",
                           "joyai-llm-flash.json")) as f:
        config = json.load(f)
    differs = sorted(k for k, v in published_config.items()
                     if config.get(k, "absent") != v)
    assert differs == sorted(config["reduced"]) == [
        "max_position_embeddings", "num_hidden_layers",
        "num_nextn_predict_layers"]
    assert config["published"] == {k: published_config[k] for k in differs}
    assert (config["num_hidden_layers"], config["max_position_embeddings"],
            config["num_nextn_predict_layers"]) == (5, 4096, 0)


def test_the_cell_resolves_and_its_longest_request_fits():
    """What `test_benchmark_spec.py` holds a GPT-keyed configuration to,
    under this family's key names (tests/conftest.py says why)."""
    from benchmarks import spec, traffic

    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = next(c for c in bench["configs"] if c["name"] == "joyai-llm-flash")
    cell = spec.load_cell("joyai-llm-flash.chat_half")
    assert cell.chips == 1 and cell.config["reduced"] == entry["reduced"]
    assert cell.config["source"] == entry["source"]
    assert (traffic.longest_request(cell.traffic) == 1709
            <= cell.config["max_position_embeddings"])
    shape = costs.mla_moe_shape(cell.config)
    assert shape.n_positions % cell.config["engine"]["block_size"] == 0
    limits = [cell.config["check"][k] for k in (
        "served_logit_gap_max_limit", "served_logit_gap_p99_limit")]
    assert any(v is not None for v in limits)
    assert all(v is None or v > 0 for v in limits)
    reported = {m["name"] for m in cell.per_layer}
    assert {"mla_moe_step_mfu", "mla_moe_step_roofline_share",
            "moe_experts_hit_share", "moe_load_imbalance"} <= reported
    assert not {"step_mfu", "step_roofline_share"} & reported
    assert len(reported) == 15 + 4
