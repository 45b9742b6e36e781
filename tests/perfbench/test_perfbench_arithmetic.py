"""Ops-and-bytes functions against hand counts at the cells' shapes, and the
percentile, rate and spread arithmetic."""

import math

import pytest

from perfbench import opsbytes, peaks, stats

GPT2 = dict(hidden_size=1024, num_layers=24, vocab_size=50304)
MISTRAL = dict(hidden_size=4096, intermediate_size=14336,
               num_attention_heads=32, num_key_value_heads=8,
               num_hidden_layers=16, vocab_size=32768)


def test_gpt2_flops_a_token_by_hand():
    # a layer: 12 h^2 weights at 2 FLOPs, plus QK^T and PV over 512.5 keys
    layer = 2 * 12 * 1024 ** 2 + 4 * 1024 * 512.5
    forward = 24 * layer + 2 * 1024 * 50304
    assert opsbytes.gpt2_forward_flops_per_token(GPT2, 1024) == forward
    assert opsbytes.gpt2_train_flops_per_token(GPT2, 1024) == \
        pytest.approx(2.272e9, rel=1e-3)


def test_flash_counts_the_causal_half_only():
    full = 4 * 32 * 128 * 4096 * 4096           # QK^T and PV, every pair
    causal = opsbytes.flash_causal_flops(1, 4096, 32, 128)
    assert causal == 4 * 32 * 128 * (4096 * 4097 / 2)
    assert 0.5 < causal / full < 0.5002
    # q and o at 32 heads, k and v at 8, bf16
    assert opsbytes.flash_causal_bytes(1, 4096, 32, 8, 128) == \
        4096 * 128 * 2 * (64 + 16)


def test_mistral_weights_and_decode_bytes_by_hand():
    layer = 4096 * 4096 * 2 + 4096 * 1024 * 2 + 3 * 4096 * 14336
    assert opsbytes.llama_layer_weights(MISTRAL) == layer == 218_103_808
    weights = 16 * layer + 4096 * 32768
    assert opsbytes.llama_weight_count(MISTRAL) == weights
    assert opsbytes.kv_bytes_per_token(MISTRAL) == 64 * 1024
    assert opsbytes.llama_decode_bytes(MISTRAL, [100, 28]) == \
        2 * weights + 128 * 64 * 1024
    assert opsbytes.llama_decode_flops(MISTRAL, 0) == 2 * weights
    assert opsbytes.llama_decode_flops(MISTRAL, 10) - 2 * weights == \
        16 * 4 * 32 * 128 * 10


def test_prefill_flops_count_the_head_once():
    one = opsbytes.llama_prefill_flops(MISTRAL, 1)
    assert one == 2 * 16 * 218_103_808 + 16 * 4 * 32 * 128 * 1 \
        + 2 * 4096 * 32768


def test_training_kernels_bytes():
    assert opsbytes.layer_norm_bwd_bytes(16 * 1024, 1024) == 3 * 2 ** 24 * 2
    assert opsbytes.causal_softmax_bytes(256, 1024) == \
        2 * 256 * (1024 * 1025 / 2) * 2


def test_roofline_says_which_bound():
    peak = peaks.peak("TPU v5 lite")
    assert peak == {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9}
    assert opsbytes.roofline_seconds(197e12, 1.0, peak) == (1.0, "flops")
    assert opsbytes.roofline_seconds(1.0, 819e9, peak) == (1.0, "bytes")
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_percentile_is_nearest_rank():
    values = list(range(1, 101))
    assert stats.percentile(values, 90) == 90
    assert stats.percentile(values, 50) == 50
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([], 90) is None
    assert stats.percentile([3, 1, 2], 100) == 3


def test_a_stalled_window_moves_the_tail_and_the_rate():
    steady = [10.0] * 100
    stalled = [10.0] * 88 + [500.0] * 12        # a stall that hits 12 requests
    assert stats.percentile(steady, 90) == 10.0
    assert stats.percentile(stalled, 90) == 500.0
    assert stats.percentile([10.0] * 95 + [math.inf] * 5, 90) == 10.0
    assert stats.percentile([10.0] * 89 + [math.inf] * 11, 90) == math.inf
    # the same tokens over a window that a stall made longer
    assert stats.window_rate(9000, 10.0) == 900.0
    assert stats.window_rate(9000, 12.5) == 720.0
    with pytest.raises(ValueError):
        stats.window_rate(1, 0.0)


def test_spread_is_the_quartile_distance_over_the_median():
    values = [100, 101, 102, 103, 104, 105]
    assert stats.spread(values) == pytest.approx((104.25 - 100.75) / 102.5)
