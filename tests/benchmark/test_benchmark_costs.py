"""The operation and byte counts against numbers worked by hand for gpt2-xl
(48 layers, d 1600, ffn 6400, 25 heads, 1024 positions, vocabulary 50257)."""

import json
import os

import pytest

from benchmarks import costs, spec
from benchmarks.stats import percentile


def _shape(name):
    with open(os.path.join(spec.ROOT, "benchmarks", "configs",
                           name + ".json")) as f:
        return costs.gpt_shape(json.load(f))


XL = _shape("gpt2-xl")


def test_gpt2_xl_shape():
    assert (XL.d_ff, XL.head_dim, XL.n_layer) == (6400, 64, 48)


def test_parameter_count_by_hand():
    # 4 * 1600^2 + 2 * 1600 * 6400 = 10.24 M + 20.48 M
    assert costs.layer_matmul_params(XL) == 30_720_000
    # + biases 4800 + 1600 + 6400 + 1600 and two layer norms 6400 = 20 800
    per_layer = 30_720_000 + 20_800
    total = 48 * per_layer + 50257 * 1600 + 1024 * 1600 + 3200
    assert total == 1_557_611_200      # the published "1.5B"
    assert costs.param_count(XL) == total


def test_bytes_by_hand():
    # bf16: 2 bytes; the position table (1024 x 1600) is read by row
    assert costs.step_weight_bytes(XL) == (1_557_611_200 - 1_638_400) * 2
    # K and V of one position: 2 x 48 layers x 1600 x 2 bytes
    assert costs.kv_bytes_per_position(XL) == 307_200
    # the default pool: 1 scratch + 8 slots x 64 pages, 16 positions a page
    assert costs.kv_pool_bytes(XL, 513, 16) == 2_521_497_600


def test_token_flops_by_hand():
    # layers: 48 x 2 x 30.72 M = 2 949 120 000; head: 2 x 1600 x 50257
    assert costs.token_flops(XL, 0) == 2_949_120_000 + 160_822_400
    assert costs.token_flops(XL, 0, with_head=False) == 2_949_120_000
    # attention: 4 x context x 1600 a layer
    assert (costs.token_flops(XL, 200) - costs.token_flops(XL, 0)
            == 48 * 4 * 200 * 1600)


def test_decode_dispatch_is_bound_by_bytes():
    peaks = costs.peaks_for("TPU v5 lite")
    work = costs.decode_dispatch(XL, active=8, micro_steps=4,
                                 mean_context=200.0)
    assert work["flops"] == 4 * 8 * costs.token_flops(XL, 200)
    assert work["bytes"] == 4 * (costs.step_weight_bytes(XL)
                                 + 8 * 200 * 307_200)
    # 4 x (3.112 GB + 0.49 GB) / 819 GB/s = 17.6 ms; FLOPs need 0.5 ms
    assert costs.roofline_seconds(work, peaks) == pytest.approx(
        work["bytes"] / 819e9)
    assert 0.0170 < costs.roofline_seconds(work, peaks) < 0.0180


def test_prefill_dispatch_turns_from_bytes_to_operations_with_its_lanes():
    peaks = costs.peaks_for("TPU v5 lite")
    work = costs.prefill_dispatch(XL, lanes=8, mean_tokens=32.0,
                                  mean_context=128.0)
    per_token = costs.token_flops(XL, 128, with_head=False)
    assert work["flops"] == 8 * (32 * per_token + 160_822_400)
    assert work["bytes"] == costs.step_weight_bytes(XL) + 8 * 128 * 307_200
    # 8 lanes: 3.43 GB / 819 GB/s = 4.18 ms still outweighs 3.89 ms of FLOPs
    assert costs.roofline_seconds(work, peaks) == pytest.approx(
        work["bytes"] / 819e9)
    assert work["flops"] / 197e12 == pytest.approx(3.89e-3, rel=1e-2)
    # 16 lanes would be bound by operations
    wide = costs.prefill_dispatch(XL, 16, 32.0, 128.0)
    assert costs.roofline_seconds(wide, peaks) == pytest.approx(
        wide["flops"] / 197e12)


def test_cerebras_shape_and_count():
    c = _shape("cerebras-gpt-1.3b")
    assert (c.d_ff, c.head_dim) == (8192, 128)
    per_layer = 4 * 2048 ** 2 + 2 * 2048 * 8192 + (3 + 1 + 1) * 2048 + 8192 + 4 * 2048
    assert costs.param_count(c) == (24 * per_layer + 50257 * 2048
                                    + 2048 * 2048 + 4096)
    assert costs.kv_pool_bytes(c, 1 + 8 * 128, 16) == 3_224_371_200


def test_a_device_without_peaks_is_an_error():
    assert costs.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12
    assert costs.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        costs.peaks_for("cpu")


@pytest.mark.parametrize("values,q,want", [
    ([1, 2, 3, 4, 5], 50, 3), ([1, 2, 3, 4], 50, 2.5), ([5], 95, 5),
    (list(range(101)), 95, 95), ([10, 20], 95, 19.5),
])
def test_percentile(values, q, want):
    assert percentile(values, q) == pytest.approx(want)
