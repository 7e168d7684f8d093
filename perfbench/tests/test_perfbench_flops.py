"""Operation and byte counts against shapes worked by hand, and the table
of peaks."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from harness import flops  # noqa: E402

MEDIUM = flops.Dims(layers=24, d_model=1024, heads=16, kv_heads=16,
                    head_dim=64, d_ff=4096, vocab=50257)


def test_matmul_and_unembed_flops_medium():
    # per layer: Q,K,V,O 4 * 1024^2 MACs + MLP 2 * 1024 * 4096 MACs
    per_layer = 4 * 1024 * 1024 + 2 * 1024 * 4096
    assert flops.matmul_flops_per_token(MEDIUM) == 2 * 24 * per_layer
    assert flops.matmul_flops_per_token(MEDIUM) == 603_979_776
    assert flops.unembed_flops(MEDIUM) == 2 * 1024 * 50257
    assert flops.decode_token_flops(MEDIUM, 100) == (
        603_979_776 + 2 * 1024 * 50257 + 4 * 24 * 16 * 64 * 100)


def test_gqa_counts_kv_heads_apart():
    d = flops.Dims(layers=1, d_model=64, heads=8, kv_heads=2, head_dim=8,
                   d_ff=128, vocab=10)
    # Q 64x64, K and V 64x16 each, O 64x64, MLP 2 x 64x128
    assert flops.matmul_flops_per_token(d) == 2 * (
        64 * 64 + 2 * 64 * 16 + 64 * 64 + 2 * 64 * 128)
    f, b = flops.decode_attn_cost(d, 10)
    assert f == 4 * 8 * 8 * 10
    assert b == 2 * 10 * 2 * 8 * 2 + 2 * 8 * 8 * 2


def test_decode_attention_cost_medium():
    f, b = flops.decode_attn_cost(MEDIUM, 100)
    assert f == 4 * 24 * 16 * 64 * 100 == 9_830_400
    # K and V of 100 positions, q in and out once, per layer, bf16
    assert b == 24 * (2 * 100 * 16 * 64 * 2 + 2 * 16 * 64 * 2) == 9_928_704


def test_prefill_attention_cost_counts_only_fresh_queries():
    f, b = flops.prefill_attn_cost(MEDIUM, 16, 20)
    keys = 17 + 18 + 19 + 20
    assert f == 4 * 24 * 16 * 64 * keys
    assert b == 24 * (2 * 20 * 1024 * 2 + 2 * 4 * 1024 * 2
                      + 2 * 4 * 1024 * 2)
    assert flops.prefill_flops(MEDIUM, 20, 20) == 0
    assert flops.prefill_flops(MEDIUM, 16, 20) == (
        4 * 603_979_776 + 4 * 24 * 16 * 64 * keys + 2 * 1024 * 50257)


def test_least_time_takes_the_binding_bound():
    pk = flops.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9
    assert flops.least_time(197e12, 1.0, pk) == pytest.approx(1.0)
    assert flops.least_time(1.0, 819e9, pk) == pytest.approx(1.0)
    f, b = flops.decode_attn_cost(MEDIUM, 512)
    assert flops.least_time(f, b, pk) == pytest.approx(b / 819e9)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        flops.peaks("cpu")
